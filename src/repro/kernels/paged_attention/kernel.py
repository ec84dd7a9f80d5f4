"""Pallas TPU paged flash-decode: one query token vs a page-table KV pool.

The KV cache lives in a shared page pool ``[num_pages, page, Hkv, D]``;
each sequence owns a row of ``page_table`` naming its pages in order.
The page table and the per-sequence lengths ride in as **scalar-prefetch
operands** (:class:`pltpu.PrefetchScalarGridSpec`), so each grid step's
``BlockSpec`` index map can look its page id up *before* the body runs —
the gather is a DMA of exactly one page, never a dense copy of the pool.

The wrapper views the pool as ``[num_pages, page*Hkv, D]`` (a free
reshape): one page is one block whose last two dimensions are the
array's own, so the block tiles on the TPU whatever the page size and
head count.  Every query head attends the page at once and GQA is a mask
(:func:`~repro.kernels.decode_attention.kernel.flash_decode_update`).

Grid: (batch, pages) — pages sequential per row with the (acc, m, l)
online-softmax carry of the dense flash-decode kernel.  Pages entirely
past a sequence's length (or below its window) skip the body, and the
index map re-names the row's last live page for every step past its
length, so their DMA is elided.  Unused ``page_table`` slots must still
hold a *valid* page id (the allocator parks them on page 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.kernel import (NEG_INF,
                                                   flash_decode_update)


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, window: int, softcap: float, page: int,
                  hkv: int):
    b = pl.program_id(0)
    ip = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]               # valid tokens incl. current one
    k_start = ip * page

    needed = k_start < seq_len
    if window > 0:
        needed = jnp.logical_and(
            needed, k_start + page - 1 > seq_len - 1 - window)
    pl.when(needed)(functools.partial(
        flash_decode_update, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
        k_start=k_start, seq_len=seq_len, hkv=hkv, scale=scale,
        window=window, softcap=softcap))

    @pl.when(ip == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_fwd(
    q: jnp.ndarray,            # [B, Hq, D]
    k_pool: jnp.ndarray,       # [P, page, Hkv, D]
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, maxp] int32 (unused slots -> page 0)
    lens: jnp.ndarray,         # [B] int32: valid tokens incl. current
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = True,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    P, page, Hkv = k_pool.shape[:3]
    maxp = page_table.shape[1]
    assert page_table.shape[0] == B, (page_table.shape, B)
    rows = page * Hkv

    kernel = functools.partial(
        _paged_kernel, scale=scale, window=window, softcap=softcap,
        page=page, hkv=Hkv)

    def kv_index(b, ip, pt, ln):
        # the paged gather: this block's page id comes from the
        # prefetched table; steps past the length re-name the last
        # live page, so the pipeline skips their fetch
        last = jnp.maximum(ln[b] - 1, 0) // page
        return pt[b, jnp.minimum(ip, last)], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # page_table, lens
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((None, Hq, D), lambda b, ip, pt, ln: (b, 0, 0)),
            pl.BlockSpec((None, rows, D), kv_index),
            pl.BlockSpec((None, rows, D), kv_index),
        ],
        out_specs=pl.BlockSpec((None, Hq, D),
                               lambda b, ip, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lens.astype(jnp.int32),
      q, k_pool.reshape(P, rows, D), v_pool.reshape(P, rows, D))
