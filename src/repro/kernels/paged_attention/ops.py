"""Wrapper for paged flash-decode, model layout in/out (not jitted
itself, for the reason given in ``decode_attention/ops.py``)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import kernels
from repro.kernels.paged_attention.kernel import paged_attention_fwd


def paged_attention(
    q: jnp.ndarray,            # [B, 1, Hq, D] (model layout)
    k_pool: jnp.ndarray,       # [P, page, Hkv, D] shared page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, maxp] int32 (unused slots -> 0)
    lens,                      # [B] int32: valid tokens incl. current
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    B, _, Hq, D = q.shape
    scale = D ** -0.5 if scale is None else scale
    if interpret is None:
        interpret = kernels.interpret_default()
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (B,))
    out = paged_attention_fwd(
        q.reshape(B, Hq, D), k_pool, v_pool, page_table, lens,
        scale=scale, window=window, softcap=attn_softcap,
        interpret=interpret)
    return out.reshape(B, 1, Hq, D)
