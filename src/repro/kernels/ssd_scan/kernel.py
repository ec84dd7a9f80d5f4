"""Pallas TPU Mamba2 SSD chunked scan.

Grid: (batch, heads, chunks) — chunks sequential; the inter-chunk SSM
state [P, N] lives in VMEM scratch across chunk iterations (reset at
chunk 0). Each iteration does the intra-chunk quadratic term (two MXU
matmuls over [Q, Q]) plus the state update — the same math as
``repro.models.ssm.ssd_chunked`` (the oracle), chunk-at-a-time.

Inputs arrive in the model's [B, S, H, ...] layout (B/C already
head-expanded by ops.py — a gather-free repeat); ``ssd_scan_fwd`` moves
heads ahead of the sequence so each (batch, head, chunk) block is a
``[chunk, P]`` / ``[chunk, N]`` tile (and the decays a ``[1, chunk]``
row) whose last two dimensions tile on the TPU.  The chunk's prefix sum
is a matmul with a triangular ones matrix, so the body needs only 2-D
matmuls, broadcasts and lane reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xb_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xb = xb_ref[...].astype(jnp.float32)         # [Q, P]
    a = a_ref[...].astype(jnp.float32)           # [1, Q]
    Bm = b_ref[...].astype(jnp.float32)          # [Q, N]
    Cm = c_ref[...].astype(jnp.float32)          # [Q, N]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum of the decays, as a row and as a column
    cum = jax.lax.dot_general(a, (ii <= jj).astype(jnp.float32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [1,Q]
    cum_col = jnp.sum(jnp.where(ii == jj, cum, 0.0), axis=1,
                      keepdims=True)             # [Q, 1]
    # intra-chunk: M[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * (i >= j)
    L = jnp.where(ii >= jj, jnp.exp(cum_col - cum), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    y_intra = jax.lax.dot_general(cb * L, xb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) * C_i . state^T   (state: [P, N])
    prev = state_ref[...]                        # [P, N]
    y_inter = jax.lax.dot_general(Cm, prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y_intra + y_inter * jnp.exp(cum_col)
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: S' = S * exp(cum_last) + sum_j exp(cum_last - cum_j)
    #                                             * xb_j (x) B_j
    a_last = jnp.sum(a, axis=1, keepdims=True)   # [1, 1] == cum_last
    decay = jnp.exp(a_last - cum_col)            # [Q, 1]
    contrib = jax.lax.dot_general(
        xb * decay, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # [P, N]
    state_ref[...] = prev * jnp.exp(a_last) + contrib

    @pl.when(ic == nc - 1)
    def _final():
        state_out_ref[...] = state_ref[...].astype(state_out_ref.dtype)


def ssd_scan_fwd(
    xb: jnp.ndarray,   # [B, S, H, P] dt-weighted inputs
    a: jnp.ndarray,    # [B, S, H] log decay
    Bh: jnp.ndarray,   # [B, S, H, N] (already head-expanded)
    Ch: jnp.ndarray,   # [B, S, H, N]
    *,
    chunk: int,
    interpret: bool = True,
):
    B, S, H, P = xb.shape
    N = Bh.shape[-1]
    assert S % chunk == 0, (S, chunk)
    grid = (B, H, S // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)

    def tile(width):
        return pl.BlockSpec((None, None, chunk, width),
                            lambda b, h, c: (b, h, c, 0))

    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            tile(P),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda b, h, c: (b, h, 0, c)),
            tile(N),
            tile(N),
        ],
        out_specs=[
            tile(P),
            pl.BlockSpec((None, None, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), xb.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(jnp.swapaxes(xb, 1, 2), jnp.moveaxis(a, 1, 2)[:, :, None, :],
      jnp.swapaxes(Bh, 1, 2), jnp.swapaxes(Ch, 1, 2))
    return jnp.swapaxes(y, 1, 2), state
