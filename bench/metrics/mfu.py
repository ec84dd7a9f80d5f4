"""mfu: the whole served step (every program of the traced window).

Model FLOPs of the tokens the traced window processed (prompt tokens of
each prefill chunk and each decoded token, with attention at their
contexts and the head where logits are computed; ``harness/costs.py``)
over the traced window's length times the chip's bf16 peak, in %.
"""
from harness.costs import chunk_flops, token_flops


def read(tr):
    if not tr.calls or tr.window_s <= 0:
        return None
    flops = 0.0
    for c in tr.calls.values():
        if c["kind"] == "decode":
            flops += sum(token_flops(tr.config, ctx, True)
                         for ctx in c["contexts"])
        else:
            flops += sum(chunk_flops(tr.config, s, cl) for s, cl in c["rows"])
    return flops / (tr.window_s * tr.peaks["bf16_flops_per_s"]) * 100.0
