"""Wrapper: grouped B/C -> per-head, padding, dispatch (not jitted
itself, for the reason given in ``decode_attention/ops.py``)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro import kernels
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd


def ssd_scan(
    xb: jnp.ndarray,      # [B, S, H, P]
    a: jnp.ndarray,       # [B, S, H]
    B_mat: jnp.ndarray,   # [B, S, G, N]
    C_mat: jnp.ndarray,   # [B, S, G, N]
    *,
    chunk: int,
    initial_state: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if interpret is None:
        interpret = kernels.interpret_default()
    B, S, H, P = xb.shape
    G = B_mat.shape[2]
    rep = H // G
    Bh = jnp.repeat(B_mat, rep, axis=2)
    Ch = jnp.repeat(C_mat, rep, axis=2)
    pad = (-S) % chunk
    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad decay with zeros -> exp(0)=1, but padded xb=0 contributes 0
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, state = ssd_scan_fwd(xb, a.astype(jnp.float32), Bh, Ch, chunk=chunk,
                            interpret=interpret)
    if initial_state is not None:
        # fold an initial state in linearly: y += C . (decay * s0)
        cuma = jnp.cumsum(a.astype(jnp.float32), axis=1)  # [B,Sp,H]
        Chf = Ch.astype(jnp.float32)
        extra = jnp.einsum("bshn,bhpn->bshp", Chf,
                           initial_state.astype(jnp.float32))
        y = y + (extra * jnp.exp(cuma)[..., None]).astype(y.dtype)
        state = state + initial_state * jnp.exp(cuma[:, -1])[..., None, None]
    if pad:
        y = y[:, :S]
    return y, state
