"""paged_attn_roofline: kernels (``kernels/paged_attention``).

Share of the HBM roofline the paged-attention kernel reaches in the
traced decode calls, in %: the bytes those calls need (each decoding
row's live K/V pages, its query and its output, over every layer;
``harness/costs.py``) over the chip's HBM bandwidth, divided by the
kernel's device time inside those calls.  Decode attention does about
one FLOP per byte, far below v5e's ridge, so bandwidth bounds it.
"""
from harness.costs import paged_attn_bytes


def read(tr):
    spans = tr.call_spans("decode")
    kernel = tr.kernel_events("paged_attention")
    if not spans or not kernel:
        return None
    need, busy = 0.0, 0
    for s in spans:
        inside = [k.dur for k in kernel if s.start <= k.start < s.end]
        if not inside:
            continue
        busy += sum(inside)
        need += paged_attn_bytes(tr.config,
                                 tr.calls[int(s.stats["call"])]["contexts"],
                                 tr.page_size)
    if busy <= 0:
        return None
    return need / tr.peaks["hbm_bytes_per_s"] / (busy * 1e-9) * 100.0
