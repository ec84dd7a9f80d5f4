"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (1, 64, 2, 2, 16), (2, 96, 4, 2, 32), (1, 128, 8, 1, 64),
    (2, 80, 4, 4, 16),  # padded (80 % 32 != 0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, Hq, Hkv, D, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hq, D)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), dtype)
    out = flash_attention(q, k, v, blk_q=32, blk_k=32)
    ref = jnp.moveaxis(attention_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        scale=D ** -0.5), 1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window,softcap,causal", [
    (16, 0.0, True), (0, 30.0, True), (32, 50.0, True), (0, 0.0, False),
])
def test_flash_attention_variants(window, softcap, causal):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          attn_softcap=softcap, blk_q=32, blk_k=32)
    ref = jnp.moveaxis(attention_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        scale=D ** -0.5, causal=causal, window=window, softcap=softcap),
        1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,Hq,Hkv,D,ln", [
    (1, 64, 2, 2, 16, 10), (2, 96, 8, 2, 32, 95), (1, 64, 4, 1, 64, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, Hq, Hkv, D, ln, dtype):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), dtype)
    kc = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), dtype)
    vc = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), dtype)
    out = decode_attention(q, kc, vc, ln, blk_k=32)
    ref = jnp.moveaxis(decode_attention_ref(
        jnp.moveaxis(q, 2, 1), kc, vc, jnp.full((B,), ln + 1, jnp.int32),
        scale=D ** -0.5), 1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("B,S,Hq,Hkv,D,ln", [
    (1, 50, 3, 1, 16, 7),     # odd S, odd Hq (padding + GQA remainder)
    (2, 33, 5, 1, 32, 30),    # S far from the 32-wide block grid
    (1, 96, 6, 3, 48, 11),    # non-pow2 head dim, odd KV head count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_odd_shapes(B, S, Hq, Hkv, D, ln, dtype):
    """Non-power-of-two sweeps vs the jnp oracle in both dtypes."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), dtype)
    kc = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), dtype)
    vc = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), dtype)
    out = decode_attention(q, kc, vc, ln, blk_k=32)
    ref = jnp.moveaxis(decode_attention_ref(
        jnp.moveaxis(q, 2, 1), kc, vc, jnp.full((B,), ln + 1, jnp.int32),
        scale=D ** -0.5), 1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_matches_model_path():
    """Kernel agrees with the model's own decode_attention (XLA path)."""
    from repro.kernels.decode_attention.ops import decode_attention as kd
    from repro.models.attention import decode_attention as md
    B, S, Hq, Hkv, D, ln = 2, 64, 4, 2, 16, 21
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    a = kd(q, kc, vc, ln, blk_k=32)
    b = md(q, kc, vc, jnp.asarray(ln), use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan (Mamba2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 16, 16), (2, 48, 4, 8, 2, 8, 16),
    (1, 40, 2, 16, 1, 32, 16),  # padded
])
def test_ssd_scan_sweep(B, S, H, P, G, N, chunk):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    xb = jnp.asarray(rng.normal(0, 0.5, (B, S, H, P)), jnp.float32)
    a = jnp.asarray(-np.abs(rng.normal(0, 0.3, (B, S, H))), jnp.float32)
    Bm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    y, st = ssd_scan(xb, a, Bm, Cm, chunk=chunk)
    yr, sr = ssd_scan_ref(xb, a, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=1e-4)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 37, 3, 8, 1, 8, 16),    # odd S (ragged last chunk), odd H
    (2, 50, 2, 24, 2, 12, 16),  # non-pow2 P and N
    (1, 21, 5, 8, 5, 8, 8),     # S barely above 2 chunks, G == H
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_odd_shapes(B, S, H, P, G, N, chunk, dtype):
    """Non-power-of-two sweeps vs the jnp oracle in both dtypes."""
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    xb = jnp.asarray(rng.normal(0, 0.5, (B, S, H, P)), dtype)
    a = jnp.asarray(-np.abs(rng.normal(0, 0.3, (B, S, H))), dtype)
    Bm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), dtype)
    Cm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), dtype)
    y, st = ssd_scan(xb, a, Bm, Cm, chunk=chunk)
    yr, sr = ssd_scan_ref(xb, a, Bm, Cm, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=tol)
    np.testing.assert_allclose(
        np.asarray(st, np.float32), np.asarray(sr, np.float32), atol=tol)


def test_ssd_scan_initial_state():
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    B, S, H, P, G, N, chunk = 1, 48, 2, 8, 1, 16, 16
    xb = jnp.asarray(rng.normal(0, 0.5, (B, S, H, P)), jnp.float32)
    a = jnp.asarray(-np.abs(rng.normal(0, 0.3, (B, S, H))), jnp.float32)
    Bm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    init = jnp.asarray(rng.normal(0, 0.5, (B, H, P, N)), jnp.float32)
    y, st = ssd_scan(xb, a, Bm, Cm, chunk=chunk, initial_state=init)
    yr, sr = ssd_scan_ref(xb, a, Bm, Cm, chunk=chunk, initial_state=init)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=1e-4)


def test_ssd_scan_chunk_invariance():
    """Same result regardless of chunk size (associativity of the scan)."""
    from repro.kernels.ssd_scan.ops import ssd_scan
    B, S, H, P, G, N = 1, 64, 2, 8, 1, 8
    xb = jnp.asarray(rng.normal(0, 0.5, (B, S, H, P)), jnp.float32)
    a = jnp.asarray(-np.abs(rng.normal(0, 0.3, (B, S, H))), jnp.float32)
    Bm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    y16, s16 = ssd_scan(xb, a, Bm, Cm, chunk=16)
    y32, s32 = ssd_scan(xb, a, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y32), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s32), atol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 37, 64), (2, 5, 7, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    x = jnp.asarray(rng.normal(0, 1, shape), dtype)
    w = jnp.asarray(rng.normal(1, 0.1, shape[-1:]), dtype)
    out = rmsnorm(x, w, blk=16)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_model_attention_uses_flash_when_enabled():
    """cfg.use_pallas routes prefill attention through the kernel and the
    result matches the XLA path."""
    from repro.configs import get_config
    from repro.models import model
    cfg = get_config("qwen3-14b", smoke=True).replace(
        param_dtype="float32", compute_dtype="float32")
    params = model.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
    h1, _ = model.forward_train(params, cfg, {"tokens": tokens})
    h2, _ = model.forward_train(params, cfg.replace(use_pallas=True),
                                {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-3)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

def _paged_case(B, P, page, Hq, Hkv, D, lens, dtype=jnp.float32, seed=0):
    """Random pool + a page table whose live entries are distinct pages
    (shuffled, so physical order != logical order) and whose parked
    slots point at scratch page 0."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(0, 1, (B, 1, Hq, D)), dtype)
    kp = jnp.asarray(r.normal(0, 1, (P, page, Hkv, D)), dtype)
    vp = jnp.asarray(r.normal(0, 1, (P, page, Hkv, D)), dtype)
    maxp = -(-max(lens) // page)
    perm = list(r.permutation(np.arange(1, P)))
    table = np.zeros((B, maxp), np.int32)
    for b, ln in enumerate(lens):
        need = -(-ln // page)
        for i in range(need):
            table[b, i] = perm.pop()
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def test_paged_attention_smoke():
    """One fast interpret-mode case; the full sweep is tier-2 (each
    distinct shape recompiles the Pallas interpreter)."""
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    B, P, page, Hq, Hkv, D = 2, 16, 8, 4, 2, 16
    q, kp, vp, table, ln = _paged_case(B, P, page, Hq, Hkv, D, [5, 23])
    out = paged_attention(q, kp, vp, table, ln)
    ref = jnp.moveaxis(paged_attention_ref(
        jnp.moveaxis(q, 2, 1), kp, vp, table, ln, scale=D ** -0.5), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("B,P,page,Hq,Hkv,D,lens", [
    (2, 16, 8, 4, 2, 16, [5, 23]),     # partial pages, GQA
    (1, 8, 16, 2, 1, 32, [48]),        # MQA, exact page multiple
    (3, 32, 4, 8, 8, 64, [1, 9, 17]),  # MHA, tiny pages
    (2, 16, 8, 6, 3, 48, [12, 31]),    # odd head counts / head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, P, page, Hq, Hkv, D, lens, dtype):
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    q, kp, vp, table, ln = _paged_case(B, P, page, Hq, Hkv, D, lens, dtype)
    out = paged_attention(q, kp, vp, table, ln)
    ref = jnp.moveaxis(paged_attention_ref(
        jnp.moveaxis(q, 2, 1), kp, vp, table, ln, scale=D ** -0.5), 1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.slow
@pytest.mark.parametrize("window,softcap", [(16, 0.0), (0, 30.0), (8, 50.0)])
def test_paged_attention_variants(window, softcap):
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    B, P, page, Hq, Hkv, D = 2, 16, 8, 4, 2, 32
    q, kp, vp, table, ln = _paged_case(B, P, page, Hq, Hkv, D, [21, 37])
    out = paged_attention(q, kp, vp, table, ln, window=window,
                          attn_softcap=softcap)
    ref = jnp.moveaxis(paged_attention_ref(
        jnp.moveaxis(q, 2, 1), kp, vp, table, ln, scale=D ** -0.5,
        window=window, softcap=softcap), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_matches_dense_gather_path():
    """Pallas paged kernel agrees with the model's gather-then-dense
    paged_decode_attention (the XLA fallback the backends default to)."""
    from repro.models.attention import paged_decode_attention
    B, P, page, Hq, Hkv, D = 2, 16, 8, 4, 2, 16
    q, kp, vp, table, ln = _paged_case(B, P, page, Hq, Hkv, D, [11, 29])
    a = paged_decode_attention(q, kp, vp, table, ln, use_pallas=True)
    b = paged_decode_attention(q, kp, vp, table, ln, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def _prefill_case(Hq, Hkv, starts, chunk_lens, *, C=8, page=4, D=16,
                  P=48, dtype=jnp.float32, seed=0):
    """A chunk of C queries per row at ``starts``, ``chunk_lens`` of them
    valid (0: an inactive row, start 0), over a shuffled pool; each
    table is one slot wider than its row needs, the spare slots parked
    on scratch page 0."""
    r = np.random.default_rng(seed)
    B = len(starts)
    q = jnp.asarray(r.normal(0, 1, (B, C, Hq, D)), dtype)
    kp = jnp.asarray(r.normal(0, 1, (P, page, Hkv, D)), dtype)
    vp = jnp.asarray(r.normal(0, 1, (P, page, Hkv, D)), dtype)
    kv_lens = [s + cl for s, cl in zip(starts, chunk_lens)]
    table = np.zeros((B, -(-max(kv_lens) // page) + 1), np.int32)
    perm = list(r.permutation(np.arange(1, P)))
    for b, ln in enumerate(kv_lens):
        for i in range(-(-ln // page)):
            table[b, i] = perm.pop()
    return (q, kp, vp, jnp.asarray(table), jnp.asarray(starts, jnp.int32),
            jnp.asarray(kv_lens, jnp.int32))


@pytest.mark.parametrize("Hq,Hkv,starts,chunk_lens,window,softcap,block,dtype", [
    # rows at different starts, chunks across page boundaries, a short
    # final chunk, an inactive row; blocks of two pages
    (4, 2, [0, 13, 0, 22], [8, 5, 0, 8], 0, 0.0, 8, jnp.float32),
    (10, 2, [4, 21, 0], [8, 3, 0], 0, 0.0, 8, jnp.float32),   # G = 5
    (4, 2, [20, 3, 0], [8, 6, 0], 6, 0.0, 8, jnp.float32),    # window
    (4, 2, [9, 0], [7, 8], 0, 20.0, 8, jnp.float32),          # softcap
    (10, 2, [27, 0, 5], [8, 0, 2], 0, 0.0, 512, jnp.bfloat16),  # one block
], ids=["starts-g2", "g5", "window", "softcap", "bf16-one-block"])
def test_paged_prefill_kernel_matches_gather_path(Hq, Hkv, starts, chunk_lens,
                                                  window, softcap, block,
                                                  dtype):
    """The chunked-prefill kernel (interpret mode, KV blocks of
    ``block`` tokens) agrees with the gather + dense ``attention`` path
    on every valid query of every active row; inactive rows' outputs
    are garbage on both paths and not compared."""
    from repro.kernels.paged_attention.kernel import (
        paged_prefill_attention_fwd)
    from repro.models.attention import paged_prefill_attention
    q, kp, vp, table, start, kv_lens = _prefill_case(
        Hq, Hkv, starts, chunk_lens, dtype=dtype)
    D = q.shape[-1]
    out = paged_prefill_attention_fwd(
        q, kp, vp, table, start, kv_lens, scale=D ** -0.5, window=window,
        softcap=softcap, block_tokens=block, interpret=True)
    ref = paged_prefill_attention(q, kp, vp, table, start, kv_lens,
                                  window=window, attn_softcap=softcap,
                                  use_pallas=False)
    assert out.shape == ref.shape == q.shape
    for b, cl in enumerate(chunk_lens):
        np.testing.assert_allclose(
            np.asarray(out[b, :cl], np.float32),
            np.asarray(ref[b, :cl], np.float32),
            atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("page,hkv,dtype,tiles", [
    (16, 8, jnp.bfloat16, True),     # qwen3 widths, the served page
    (8, 8, jnp.bfloat16, False),     # half a bf16 sublane tile
    (8, 8, jnp.float32, True),       # a whole f32 sublane tile
    (16, 1, jnp.bfloat16, False),    # Hkv*D of 16 lanes: a test config
], ids=["bf16-page16", "bf16-page8", "f32-page8", "narrow-heads"])
def test_prefill_kernel_taken_only_where_pages_tile(page, hkv, dtype, tiles):
    """The chunk path picks the prefill kernel only for pools whose page
    copies Mosaic tiles; elsewhere it keeps the XLA gather."""
    from repro.kernels.paged_attention.kernel import prefill_tiles
    D = 128 if hkv > 1 else 16
    pool = jax.ShapeDtypeStruct((4, page, hkv, D), dtype)
    assert prefill_tiles(pool) is tiles
