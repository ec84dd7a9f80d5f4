"""Pallas TPU flash-decode: one query token vs a chunked KV cache.

Grid: (batch, kv_chunks) — chunks sequential, (acc, m, l) in VMEM
scratch. The same (max, sum)-LSE combination is what the sequence-parallel
decode path psums across shards, so this kernel is the single-shard body
of distributed decode.

Cache layout: [B, S, Hkv, D] (model layout). The wrapper views it as
``[B, S*Hkv, D]`` (a free reshape), so a chunk of ``blk_k`` tokens is one
``[blk_k*Hkv, D]`` block whose last two dimensions tile the TPU's
(sublane, lane) grid.  Every query head attends the whole block at once;
GQA is a mask that keeps each head on its own KV head's rows
(:func:`flash_decode_update`).  Valid lengths ride in as a scalar-prefetch
operand, which also lets the index map stop fetching chunks past a row's
length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_decode_update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                        k_start, seq_len, hkv: int, scale: float,
                        window: int, softcap: float):
    """One online-softmax step of every query head against a block of
    ``T`` tokens stored token-major with their KV heads interleaved.

    q_ref: [Hq, D]; k_ref, v_ref: [T*Hkv, D], row ``r`` holding token
    ``k_start + r // Hkv`` of KV head ``r % Hkv``; acc [Hq, D] and
    m, l [Hq, 1] f32 carries.  Query head ``h`` reads KV head
    ``h // (Hq // Hkv)``; the other heads' rows are masked out."""
    hq = q_ref.shape[0]
    n = k_ref.shape[0]
    group = hq // hkv
    q = q_ref[...].astype(jnp.float32)                 # [Hq, D]
    k = k_ref[...].astype(jnp.float32)                 # [T*Hkv, D]
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Hq, T*Hkv]
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    row = jax.lax.broadcasted_iota(jnp.int32, (hq, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, n), 1)
    k_pos = k_start + col // hkv
    mask = jnp.logical_and(col % hkv == row // group, k_pos < seq_len)
    if window > 0:
        mask = jnp.logical_and(mask, k_pos > seq_len - 1 - window)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, window: int, softcap: float, blk_k: int,
                   hkv: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[b]             # valid entries incl. current token
    k_start = ik * blk_k

    # skip chunks entirely past the valid length (or below the window)
    needed = k_start < cache_len
    if window > 0:
        needed = jnp.logical_and(
            needed, k_start + blk_k - 1 > cache_len - 1 - window)
    pl.when(needed)(functools.partial(
        flash_decode_update, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
        k_start=k_start, seq_len=cache_len, hkv=hkv, scale=scale,
        window=window, softcap=softcap))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_fwd(
    q: jnp.ndarray,        # [B, Hq, D]
    k_cache: jnp.ndarray,  # [B, S, Hkv, D]
    v_cache: jnp.ndarray,
    lens: jnp.ndarray,     # [B] int32: valid entries (incl. current token)
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    blk_k: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert S % blk_k == 0, (S, blk_k)
    grid = (B, S // blk_k)
    rows = blk_k * Hkv

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        blk_k=blk_k, hkv=Hkv)

    def kv_index(b, ik, ln):
        # chunks past the length re-name the last needed one, so their
        # DMA is elided (the body is skipped for them anyway)
        last = jnp.maximum(ln[b] - 1, 0) // blk_k
        return b, jnp.minimum(ik, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # lens
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, Hq, D), lambda b, ik, ln: (b, 0, 0)),
            pl.BlockSpec((None, rows, D), kv_index),
            pl.BlockSpec((None, rows, D), kv_index),
        ],
        out_specs=pl.BlockSpec((None, Hq, D), lambda b, ik, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), q, k_cache.reshape(B, S * Hkv, D),
      v_cache.reshape(B, S * Hkv, D))
