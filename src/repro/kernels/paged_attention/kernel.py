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


# ---------------------------------------------------------------------------
# chunked prefill: a [B, C] chunk of queries per row against the pool
# ---------------------------------------------------------------------------

def prefill_tiles(pool: jnp.ndarray) -> bool:
    """Whether Mosaic tiles the prefill kernel's page copies out of a
    ``[P, page, Hkv, D]`` pool: a page is a whole number of sublane tiles
    (8 rows of 32 bits: 16 of a bf16 pool) and ``Hkv*D`` a whole number
    of 128-lane tiles (every served width; not the tiny test configs)."""
    page, hkv, d = pool.shape[1:]
    return page % (32 // jnp.dtype(pool.dtype).itemsize) == 0 \
        and (hkv * d) % 128 == 0


def _prefill_kernel(pt_ref, start_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                    k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *,
                    scale: float, window: int, softcap: float, page: int,
                    pages_per_block: int, hkv: int, chunk: int):
    """One grid step is one row: a loop over the row's live KV blocks,
    each fetched page by page from the pool into a double buffer while
    the block before it is attended."""
    b = pl.program_id(0)
    kv_len = len_ref[b]
    q0 = start_ref[b]
    maxp = pt_ref.shape[1]
    blk = page * pages_per_block
    d = q_ref.shape[-1]
    rows = q_ref.shape[1]                  # G * C query rows a KV head
    live_pages = (kv_len + page - 1) // page
    hi = (kv_len + blk - 1) // blk         # 0 for an inactive row
    lo = 0
    if window > 0:   # blocks left of the first query's window are dead
        lo = jnp.minimum(jnp.maximum(q0 - window + 1, 0) // blk, hi)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def page_loop(j, slot, op):
        """Start (or wait for) the copies of block ``j``'s live pages,
        K and V, one page per DMA; pages past the row's length are
        neither fetched nor waited for."""
        first = j * pages_per_block

        def one(i, carry):
            pid = pt_ref[b, jnp.minimum(first + i, maxp - 1)]
            dst = pl.ds(pl.multiple_of(i * page, page), page)
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                op(pltpu.make_async_copy(hbm.at[pid], buf.at[slot, dst],
                                         sems.at[slot]))
            return carry

        jax.lax.fori_loop(
            0, jnp.clip(live_pages - first, 0, pages_per_block), one, 0)

    def fetch(j, slot):
        page_loop(j, slot, lambda cp: cp.start())

    def wait(j, slot):
        page_loop(j, slot, lambda cp: cp.wait())

    def attend(j, slot):
        k0 = j * blk
        # query row r of a KV head is chunk token r % C of head r // C
        q_pos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows // chunk, chunk, blk), 1).reshape(rows, blk)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
        mask = jnp.logical_and(k_pos <= q_pos, k_pos < kv_len)
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        # V rows past the length hold stale or unfetched data: zero them
        # so that a zero weight cannot meet a non-finite value
        v_live = k0 + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0) \
            < kv_len
        for h in range(hkv):
            k = k_buf[slot, :, h * d:(h + 1) * d]          # [blk, D]
            v = jnp.where(v_live, v_buf[slot, :, h * d:(h + 1) * d], 0)
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rows, blk]
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(lo < hi)
    def _first():
        fetch(lo, 0)

    def body(j, carry):
        slot = (j - lo) % 2

        @pl.when(j + 1 < hi)
        def _next():
            fetch(j + 1, 1 - slot)

        wait(j, slot)
        attend(j, slot)
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention_fwd(
    q: jnp.ndarray,            # [B, C, Hq, D] (model layout)
    k_pool: jnp.ndarray,       # [P, page, Hkv, D]
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, maxp] int32 (unused slots -> page 0)
    start: jnp.ndarray,        # [B] int32: position of each row's query 0
    kv_lens: jnp.ndarray,      # [B] int32: valid tokens after the chunk
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    block_tokens: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Causal attention of each row's C chunk tokens (at positions
    ``start + 0..C-1``) over its first ``kv_lens`` pooled tokens.

    Grid: (batch,).  The pools stay in HBM (``pl.ANY``), viewed as
    ``[P, page, Hkv*D]`` (a free reshape); each row walks its live
    blocks of ``block_tokens`` (whole pages, at most the table), copying
    one page per DMA into a double buffer ``[2, blk, Hkv*D]`` in which
    KV head ``h`` is the lane slice ``h*D:(h+1)*D``.  The G query heads
    of a KV head share each fetched block as one ``[G*C, D]`` operand.
    A row with ``kv_lens == 0`` fetches and computes nothing; blocks past
    a row's length, or (with ``window``) left of its first query's
    window, are never visited.  Numerics as the gather path: bf16
    operands, f32 accumulation, scale and online softmax in f32.
    -> [B, C, Hq, D]."""
    B, C, Hq, D = q.shape
    P, page, Hkv = k_pool.shape[:3]
    maxp = page_table.shape[1]
    assert page_table.shape[0] == B, (page_table.shape, B)
    G = Hq // Hkv
    ppb = max(1, min(block_tokens // page, maxp))
    blk = ppb * page
    # [B, Hkv, G*C, D]: row g*C + c is chunk token c of query head h*G+g
    qh = q.reshape(B, C, Hkv, G, D).transpose(0, 2, 3, 1, 4).reshape(
        B, Hkv, G * C, D)
    kernel = functools.partial(
        _prefill_kernel, scale=scale, window=window, softcap=softcap,
        page=page, pages_per_block=ppb, hkv=Hkv, chunk=C)
    q_spec = pl.BlockSpec((None, Hkv, G * C, D),
                          lambda b, pt, st, ln: (b, 0, 0, 0))
    item = jnp.dtype(q.dtype).itemsize
    vmem = (4 * Hkv * G * C * D * item          # q and out, two buffers
            + 4 * blk * Hkv * D * item          # K and V, two slots
            + Hkv * G * C * (D + 2 * 128) * 4   # acc, m, l (lane-padded)
            + 6 * G * C * blk * 4)              # logits and temporaries
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # page_table, start, kv_lens
        grid=(B,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, blk, Hkv * D), k_pool.dtype),
            pltpu.VMEM((2, blk, Hkv * D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hkv, G * C, D), jnp.float32),
            pltpu.VMEM((Hkv, G * C, 1), jnp.float32),
            pltpu.VMEM((Hkv, G * C, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem) + (16 << 20)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      kv_lens.astype(jnp.int32), qh,
      k_pool.reshape(P, page, Hkv * D), v_pool.reshape(P, page, Hkv * D))
    return out.reshape(B, Hkv, G, C, D).transpose(0, 3, 1, 2, 4).reshape(
        B, C, Hq, D)
