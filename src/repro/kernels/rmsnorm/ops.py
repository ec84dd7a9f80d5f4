"""Wrapper: arbitrary leading dims, padding, dispatch (not jitted
itself, for the reason given in ``decode_attention/ops.py``)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import kernels
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
            blk: int = 256, interpret: Optional[bool] = None) -> jnp.ndarray:
    if interpret is None:
        interpret = kernels.interpret_default()
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    N = x2.shape[0]
    blk = min(blk, N)
    pad = (-N) % blk
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_fwd(x2, w, eps=eps, blk=blk, interpret=interpret)
    if pad:
        out = out[:N]
    return out.reshape(shape)
