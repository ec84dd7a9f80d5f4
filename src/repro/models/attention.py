"""Grouped-query attention with the full option set used by the assigned archs.

Pure-XLA path (default; what the multi-pod dry-run lowers) plus a Pallas
flash-attention path (TPU target; interpret=True validated on CPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import kernels
from repro.models.layers import softcap

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, *, causal: bool,
          window: int, kv_len: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Boolean [.., Q, K] mask of *allowed* positions.

    q_pos: [Q] or [B, Q]; k_pos: [K] or [B, K].
    """
    qp = q_pos[..., :, None].astype(jnp.int32)
    kp = k_pos[..., None, :].astype(jnp.int32)
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= kp > qp - window
    if kv_len is not None:
        kv = jnp.asarray(kv_len, jnp.int32)
        kv = kv.reshape(kv.shape + (1, 1)) if kv.ndim else kv
        ok &= kp < kv
    return ok


def attention(
    q: jnp.ndarray,            # [B, Q, Hq, D]
    k: jnp.ndarray,            # [B, K, Hkv, D]
    v: jnp.ndarray,            # [B, K, Hkv, D]
    *,
    causal: bool = True,
    q_positions: Optional[jnp.ndarray] = None,  # [Q] or [B,Q]
    k_positions: Optional[jnp.ndarray] = None,  # [K] or [B,K]
    kv_len: Optional[jnp.ndarray] = None,       # scalar or [B]: valid cache len
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    use_pallas: bool = False,
    f32_logits: bool = True,
) -> jnp.ndarray:
    """Returns [B, Q, Hq, D]. Softmax in fp32 (or bf16 with explicit
    max-subtraction when ``f32_logits=False`` — the §Perf lever that
    halves S^2 softmax HBM traffic)."""
    B, Q, Hq, D = q.shape
    _, K, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    if use_pallas and Q > 1 and causal and kv_len is None and Q == K:
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(
            q, k, v, causal=True, window=window,
            attn_softcap=attn_softcap, scale=scale)

    if q_positions is None:
        q_positions = jnp.arange(Q)
    if k_positions is None:
        k_positions = jnp.arange(K)

    ldt = jnp.float32 if f32_logits else q.dtype
    qg = q.reshape(B, Q, Hkv, G, D)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=ldt
    ) * jnp.asarray(scale, ldt)
    if attn_softcap > 0.0:
        logits = softcap(logits, attn_softcap).astype(ldt)
    mask = _mask(q_positions, k_positions, causal=causal, window=window,
                 kv_len=kv_len)
    # mask broadcast: [.., Q, K] -> [B?, 1, 1, Q, K]
    while mask.ndim < logits.ndim:
        mask = mask[..., None, :, :] if mask.ndim >= 3 else mask[None]
    neg = jnp.asarray(-3e4 if ldt == jnp.bfloat16 else NEG_INF, ldt)
    logits = jnp.where(mask, logits, neg)
    if f32_logits:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp((logits - m).astype(jnp.float32)).astype(ldt)
        probs = e / jnp.maximum(jnp.sum(e.astype(jnp.float32), -1,
                                        keepdims=True), 1e-9).astype(ldt)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32 if f32_logits else v.dtype,
    )
    return out.reshape(B, Q, Hq, D).astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,            # [B, 1, Hq, D]
    k_pool: jnp.ndarray,       # [P, page, Hkv, D] shared page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, maxp] int32 (unused slots -> page 0)
    lens: jnp.ndarray,         # [B] int32: valid tokens incl. current
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    f32_logits: bool = True,
) -> jnp.ndarray:
    """One-token attention against a page-table KV pool; each row has its
    own length (no shared position counter).

    ``use_pallas`` None runs the Pallas kernel wherever it compiles (a
    TPU) and the XLA gather elsewhere; True / False force one of them
    (the gather is the test oracle and the kernel's on-chip reference).
    """
    if use_pallas is None:
        use_pallas = not kernels.interpret_default()
    if use_pallas:
        from repro.kernels.paged_attention import ops as pa_ops
        return pa_ops.paged_attention(
            q, k_pool, v_pool, page_table, lens,
            window=window, attn_softcap=attn_softcap, scale=scale)
    from repro.kernels.paged_attention.ref import gather_pages
    k = gather_pages(k_pool, page_table)       # [B, maxp*page, Hkv, D]
    v = gather_pages(v_pool, page_table)
    lens = jnp.asarray(lens, jnp.int32)
    return attention(
        q, k, v, causal=True,
        q_positions=(lens - 1)[:, None], k_positions=jnp.arange(k.shape[1]),
        kv_len=lens, window=window, attn_softcap=attn_softcap,
        scale=scale, use_pallas=False, f32_logits=f32_logits)


def paged_prefill_attention(
    q: jnp.ndarray,            # [B, C, Hq, D]
    k_pool: jnp.ndarray,       # [P, page, Hkv, D] shared page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, maxp] int32 (unused slots -> page 0)
    start: jnp.ndarray,        # [B] int32: position of chunk token 0
    kv_lens: jnp.ndarray,      # [B] int32: valid tokens after the chunk
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    f32_logits: bool = True,
) -> jnp.ndarray:
    """Causal attention of a chunk of C tokens per row, at positions
    ``start + 0..C-1``, over the row's first ``kv_lens`` pooled tokens.

    ``use_pallas`` None runs the Pallas kernel wherever it compiles (a
    TPU, at widths whose pages tile) and the XLA gather elsewhere; True /
    False force one of them (the gather is the test oracle and the
    kernel's on-chip reference).
    """
    from repro.kernels.paged_attention import kernel as pa_kernel
    if use_pallas is None:
        use_pallas = not kernels.interpret_default() and \
            pa_kernel.prefill_tiles(k_pool)
    if use_pallas:
        return pa_kernel.paged_prefill_attention_fwd(
            q, k_pool, v_pool, page_table, start, kv_lens,
            scale=q.shape[-1] ** -0.5 if scale is None else scale,
            window=window, softcap=attn_softcap,
            interpret=kernels.interpret_default())
    from repro.kernels.paged_attention.ref import gather_pages
    k = gather_pages(k_pool, page_table)       # [B, maxp*page, Hkv, D]
    v = gather_pages(v_pool, page_table)
    start = jnp.asarray(start, jnp.int32)
    return attention(
        q, k, v, causal=True,
        q_positions=start[:, None] + jnp.arange(q.shape[1])[None, :],
        k_positions=jnp.arange(k.shape[1]), kv_len=kv_lens, window=window,
        attn_softcap=attn_softcap, scale=scale, use_pallas=False,
        f32_logits=f32_logits)


def decode_attention(
    q: jnp.ndarray,            # [B, 1, Hq, D]
    k_cache: jnp.ndarray,      # [B, S, Hkv, D]
    v_cache: jnp.ndarray,      # [B, S, Hkv, D]
    cache_len: jnp.ndarray,    # scalar int32: number of valid entries
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: Optional[float] = None,
    use_pallas: bool = False,
    f32_logits: bool = True,
) -> jnp.ndarray:
    """One-token attention against a (possibly partially filled) KV cache."""
    if use_pallas:
        from repro.kernels.decode_attention import ops as da_ops
        return da_ops.decode_attention(
            q, k_cache, v_cache, cache_len,
            window=window, attn_softcap=attn_softcap, scale=scale)
    q_pos = jnp.asarray(cache_len, jnp.int32).reshape(1)  # query at index len
    return attention(
        q, k_cache, v_cache, causal=True,
        q_positions=q_pos, k_positions=jnp.arange(k_cache.shape[1]),
        kv_len=cache_len + 1, window=window,
        attn_softcap=attn_softcap, scale=scale, use_pallas=False,
        f32_logits=f32_logits)
