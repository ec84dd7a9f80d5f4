"""Wrapper for flash-decode, model layout in/out.

Not jitted itself: it runs inside the model's jitted step, and resolving
``interpret`` at call time keeps the compiled-or-interpreted decision
out of any trace cache."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import kernels
from repro.kernels.decode_attention.kernel import decode_attention_fwd


def decode_attention(
    q: jnp.ndarray,        # [B, 1, Hq, D] (model layout)
    k_cache: jnp.ndarray,  # [B, S, Hkv, D]
    v_cache: jnp.ndarray,
    cache_len,             # scalar or [B]: index of current token
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    blk_k: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    B, _, Hq, D = q.shape
    S = k_cache.shape[1]
    scale = D ** -0.5 if scale is None else scale
    if interpret is None:
        interpret = kernels.interpret_default()
    blk_k = min(blk_k, S)
    pad = (-S) % blk_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32) + 1, (B,))
    out = decode_attention_fwd(
        q.reshape(B, Hq, D), k_cache, v_cache, lens, scale=scale,
        window=window, softcap=attn_softcap, blk_k=blk_k,
        interpret=interpret)
    return out.reshape(B, 1, Hq, D)
