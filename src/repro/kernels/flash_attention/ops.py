"""Wrapper: model layout [B, S, H, D] <-> kernel layout, padding,
backend dispatch (compiled on TPU, interpret=True elsewhere; not jitted
itself, for the reason given in ``decode_attention/ops.py``)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import kernels
from repro.kernels.flash_attention.kernel import flash_attention_fwd


def flash_attention(
    q: jnp.ndarray,  # [B, S, Hq, D] (model layout)
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    B, S, Hq, D = q.shape
    scale = D ** -0.5 if scale is None else scale
    if interpret is None:
        interpret = kernels.interpret_default()
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    pad = (-S) % max(blk_q, blk_k)
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = flash_attention_fwd(
        qt, kt, vt, scale=scale, causal=causal, window=window,
        softcap=attn_softcap, blk_q=blk_q, blk_k=blk_k, seq_len=S,
        interpret=interpret)
    if pad:
        out = out[:, :, :S]
    return jnp.moveaxis(out, 1, 2)
