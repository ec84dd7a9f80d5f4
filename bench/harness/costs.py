"""Operations and bytes of the served programs, from shapes alone.

These are what the algorithm needs, not what the program happens to
move: a roofline share or a utilization divides them by measured device
time, so counting here anything the work does not need would flatter
the program.  Widths come from the configuration file's published keys.
"""
from __future__ import annotations

BF16_BYTES = 2


def _dims(config: dict):
    d = int(config["hidden_size"])
    hd = int(config["head_dim"])
    hq = int(config["num_attention_heads"]) * hd
    hk = int(config["num_key_value_heads"]) * hd
    f = int(config["intermediate_size"])
    return d, hd, hq, hk, f


def matmul_params_per_layer(config: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, _, hq, hk, f = _dims(config)
    return d * hq + 2 * d * hk + hq * d + 3 * d * f


def token_flops(config: dict, context: int, logits: bool) -> float:
    """FLOPs of one token through every layer, attending ``context``
    keys (itself included), plus the LM head when its logits are
    computed."""
    d, _, hq, _, _ = _dims(config)
    L = int(config["num_hidden_layers"])
    per_layer = 2 * matmul_params_per_layer(config) + 4 * context * hq
    head = 2 * d * int(config["vocab_size"]) if logits else 0
    return float(L * per_layer + head)


def chunk_flops(config: dict, start: int, length: int) -> float:
    """FLOPs of one row's prefill chunk of ``length`` tokens from
    position ``start``: causal, so token ``p`` attends ``p + 1`` keys;
    the head runs at the chunk's last token only, as served."""
    if length <= 0:
        return 0.0
    keys = length * start + length * (length + 1) // 2
    d, _, hq, _, _ = _dims(config)
    L = int(config["num_hidden_layers"])
    return float(L * (2 * matmul_params_per_layer(config) * length
                      + 4 * keys * hq)
                 + 2 * d * int(config["vocab_size"]))


def paged_attn_bytes(config: dict, contexts, page_size: int) -> float:
    """HBM bytes the paged-attention kernel needs for one decode call,
    over all layers: the live pages of each decoding row's K and V, its
    query and its output."""
    _, hd, hq, hk, _ = _dims(config)
    L = int(config["num_hidden_layers"])
    total = 0
    for ctx in contexts:
        pages = -(-int(ctx) // page_size)
        total += 2 * pages * page_size * hk * BF16_BYTES     # K and V
        total += 2 * hq * BF16_BYTES                         # q and out
    return float(L * total)
