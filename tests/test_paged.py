"""Paged KV backends: allocator invariants, chunked prefill, the
dense-shim accounting fixes, and paged-vs-dense acceptance.

Fast tier drives the virtual-time backends (PagedSimBackend /
DenseSimBackend); @slow covers the real jax path, including the
paged-vs-dense token-stream equivalence golden.
"""
import numpy as np
import pytest

from repro.sched.resources import ResourceVector
from repro.serve import (DenseSimBackend, Engine, PagedSimBackend,
                         Request, ServingDemand, pages_for)
from repro.serve.backends import _shrink_bucket
from repro.serve.paged import PageAllocator


def make_requests(n, seed=0, rate=20.0, prompt=(8, 32), new=(8, 40)):
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return [Request(rid=i, prompt_len=int(rng.integers(*prompt)),
                    max_new_tokens=int(rng.integers(*new)),
                    arrival=float(t[i])) for i in range(n)]


# --- PageAllocator ----------------------------------------------------------

def test_page_allocator_ledgers():
    a = PageAllocator(num_pages=9, page_size=4)
    assert a.usable_pages == 8        # page 0 is scratch
    a.reserve(1, 3)
    a.reserve(2, 5)
    assert not a.can_reserve(1)       # pool fully reserved
    with pytest.raises(RuntimeError):
        a.reserve(3, 1)
    with pytest.raises(RuntimeError):
        a.reserve(1, 1)               # double reservation
    assert a.grow_to(1, 5) == a.pages_of(1)
    assert len(a.pages_of(1)) == pages_for(5, 4) == 2
    assert 0 not in a.pages_of(1)     # scratch never handed out
    a.grow_to(2, 17)
    assert a.allocated_pages == 2 + 5
    assert a.free_pages == 8 - 7
    a.release(1)
    assert a.allocated_pages == 5 and a.can_reserve(3)
    a.release(2)
    assert a.free_pages == a.usable_pages == 8
    assert a.reserved_pages == 0


def test_page_allocator_growth_never_exceeds_reservation():
    a = PageAllocator(num_pages=5, page_size=2)
    a.reserve(7, 2)
    with pytest.raises(AssertionError):
        a.grow_to(7, 5)               # 3 pages > the 2 reserved


def test_page_allocator_rejects_degenerate_pools():
    with pytest.raises(ValueError):
        PageAllocator(num_pages=1, page_size=4)
    with pytest.raises(ValueError):
        PageAllocator(num_pages=8, page_size=0)


# --- conservation: allocated pages == sum(ceil(live / page)) every step ----

class _CheckedPaged(PagedSimBackend):
    def decode(self, running):
        cost = super().decode(running)
        live = sum(pages_for(self._live_tokens(r), self.page_size)
                   for r in self._slots)
        assert live == self.alloc.allocated_pages, \
            (live, self.alloc.allocated_pages)
        assert self.alloc.allocated_pages + self.alloc.free_pages \
            == self.alloc.usable_pages
        return cost


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_page_conservation_invariant_every_step(seed):
    """Allocated pages exactly cover live tokens at every decode step —
    no leaks, no double-allocation — and the pool drains to empty."""
    demand = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                           host_ram_per_req_gb=0.01, page_size=8)
    budget = ResourceVector(hbm=0.5 + 2e-4 * 72 * 3.0,
                            host_ram=0.01 * 6.0)
    be = _CheckedPaged(num_pages=1 + 16 * pages_for(80, 8), page_size=8,
                       prefill_chunk=8)
    eng = Engine(make_requests(24, seed=seed), demand, budget, be,
                 max_batch=16)
    s = eng.run()
    assert s["completed"] == 24
    assert be.alloc.allocated_pages == 0
    assert be.alloc.reserved_pages == 0
    assert be.alloc.free_pages == be.alloc.usable_pages
    for dec in eng.metrics.steps:
        assert dec.booked.fits(dec.budget) or dec.forced


def test_paged_joinable_is_position_independent():
    """The lifted constraint: a prompt LONGER than every running context
    can join mid-stream (dense joinable demands prefill <= position)."""
    be = PagedSimBackend(num_pages=1 + 40, page_size=4, prefill_chunk=8)
    be.join([Request(rid=0, prompt_len=6, max_new_tokens=4)], 0.0)
    assert not be.empty and be.position == 0
    long_req = Request(rid=1, prompt_len=50, max_new_tokens=8)
    assert be.joinable(long_req)      # pages fit; position irrelevant
    dense = DenseSimBackend(max_len=80, sync=8)
    dense.join([Request(rid=2, prompt_len=6, max_new_tokens=4)], 0.0)
    assert not dense.joinable(long_req)   # prefill 50 > position


def test_paged_filter_joinable_is_cumulative():
    """The pool is a collective constraint: each accepted candidate
    shrinks what the next can reserve (prefix admission stays safe)."""
    be = PagedSimBackend(num_pages=1 + 10, page_size=4, prefill_chunk=8)
    reqs = [Request(rid=i, prompt_len=12, max_new_tokens=4)
            for i in range(4)]                 # 4 pages worst-case each
    picked = be.filter_joinable(reqs)
    assert [r.rid for r in picked] == [0, 1]   # 2 fit, not 4
    assert all(be.joinable(r) for r in reqs)   # individually all fit


# --- chunked prefill --------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 3])
def test_chunked_prefill_cuts_short_request_ttft(seed):
    """Head-of-line blocking: short requests arriving around a few very
    long prompts see lower TTFT when prefill runs in chunks interleaved
    with decode than when each join stalls on the full prompt."""
    def bimodal(seed):
        rng = np.random.default_rng(seed)
        t = np.cumsum(rng.exponential(1.0 / 200.0, size=16))
        longs = set(int(x) for x in rng.choice(16, 3, replace=False))
        reqs = [Request(rid=i,
                        prompt_len=int(rng.integers(300, 500))
                        if i in longs else int(rng.integers(4, 12)),
                        max_new_tokens=int(rng.integers(4, 12)),
                        arrival=float(t[i])) for i in range(16)]
        return reqs, longs

    def short_ttft(chunk):
        reqs, longs = bimodal(seed)
        demand = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                               page_size=8)
        be = PagedSimBackend(num_pages=1 + 8 * 64, page_size=8,
                             prefill_chunk=chunk)
        eng = Engine(reqs, demand, ResourceVector(hbm=100.0), be,
                     max_batch=8)
        s = eng.run()
        assert s["completed"] == 16
        return float(np.mean([r.first_token_t - r.arrival
                              for r in eng.requests
                              if r.rid not in longs]))

    assert short_ttft(16) < short_ttft(10 ** 6)


def test_paged_token_streams_match_dense_sim():
    """Same deterministic synthesis, so every request's stream is
    identical across backends — scheduling changes, content does not."""
    def run(be):
        demand = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4)
        eng = Engine(make_requests(12, seed=4), demand,
                     ResourceVector(hbm=100.0), be, max_batch=8)
        assert eng.run()["completed"] == 12
        return {r.rid: list(r.tokens) for r in eng.requests}

    paged = run(PagedSimBackend(num_pages=1 + 8 * 10, page_size=8,
                                prefill_chunk=8))
    dense = run(DenseSimBackend(max_len=80, sync=8))
    assert paged == dense


# --- paged-vs-dense acceptance (the ISSUE bar, sim tier) -------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_paged_beats_dense_on_waste(seed):
    """Contended cell: paged residency waste strictly below dense (which
    holds the full bucket * max_len grid), goodput no worse."""
    demand_p = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                             page_size=8)
    demand_d = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4)
    budget = ResourceVector(hbm=0.5 + 2e-4 * 72 * 3.0)
    paged = PagedSimBackend(num_pages=1 + 16 * pages_for(80, 8),
                            page_size=8, prefill_chunk=8)
    dense = DenseSimBackend(max_len=80, sync=8)
    ep = Engine(make_requests(24, seed=seed), demand_p, budget, paged,
                max_batch=16)
    sp = ep.run()
    ed = Engine(make_requests(24, seed=seed), demand_d, budget, dense,
                max_batch=16)
    sd = ed.run()
    assert sp["completed"] == sd["completed"] == 24
    assert paged.waste_ratio() < dense.waste_ratio()
    assert sp["goodput_tok_s"] >= sd["goodput_tok_s"] * 0.95


# --- S1: dense join cost charges the padded position -----------------------

def test_dense_sim_join_cost_charges_padded_position():
    be = DenseSimBackend(max_len=48, sync=8)
    r0 = Request(rid=0, prompt_len=5, max_new_tokens=30)
    cost = be.join([r0], 0.0)
    assert be.position == 8           # 5 rounds up to the sync stride
    assert cost == pytest.approx(be._timer.t_prefill_per_token * 8)
    r1 = Request(rid=1, prompt_len=3, max_new_tokens=30)
    cost = be.join([r1], 0.0)         # mid-stream: re-prefills to pos
    assert cost == pytest.approx(be._timer.t_prefill_per_token * 8)


# --- S2: bucket shrink hysteresis ------------------------------------------

def test_shrink_bucket_hysteresis_pure():
    # above/equal target: no shrink, streak resets
    assert _shrink_bucket(8, 8, 2, 3) == (8, 0)
    assert _shrink_bucket(8, 5, 2, 3) == (8, 0)   # bucket(5) == 8
    # below target: streak builds, shrink only at patience
    assert _shrink_bucket(8, 4, 0, 3) == (8, 1)
    assert _shrink_bucket(8, 4, 1, 3) == (8, 2)
    assert _shrink_bucket(8, 4, 2, 3) == (4, 0)
    # patience=1 shrinks immediately (the old behaviour)
    assert _shrink_bucket(8, 4, 0, 1) == (4, 0)
    # shrink lands on the CURRENT bucket, not one step down
    assert _shrink_bucket(16, 2, 1, 2) == (2, 0)


def test_dense_sim_cap_survives_join_finish_oscillation():
    """A batch oscillating on a power-of-two edge must keep ONE cache
    shape under hysteresis (patience > churn period)."""
    be = DenseSimBackend(max_len=64, sync=1, shrink_patience=4)
    rs = [Request(rid=i, prompt_len=4, max_new_tokens=50)
          for i in range(6)]
    be.join(rs[:5], 0.0)              # cap -> 8
    caps = {be.kv_resident_tokens() // be.max_len}
    for _ in range(6):                # finish one, admit one, repeat
        be.remove([be._slots[-1]])
        caps.add(be.kv_resident_tokens() // be.max_len)
        nxt = Request(rid=100 + _, prompt_len=4, max_new_tokens=50)
        assert be.joinable(nxt)
        be.join([nxt], 0.0)
        caps.add(be.kv_resident_tokens() // be.max_len)
    assert caps == {8}                # zero re-bucketing under churn


# --- S3: reserved-axis leakage rejected at construction --------------------

def test_serving_demand_rejects_reserved_extra_axes():
    with pytest.raises(ValueError, match="reserved"):
        ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                      extra_axes={"hbm": 99.0})
    with pytest.raises(ValueError, match="reserved"):
        ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                      extra_axes={"host_ram": 1.0, "net": 0.1})
    # non-reserved side-cars still pass through
    sd = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                       extra_axes={"net": 0.1})
    assert sd.per_request_axes()["net"] == pytest.approx(0.1)


def test_poisoned_estimate_raises_not_overwrites():
    """Regression: a (buggy) estimator leaking an 'hbm' curve used to
    silently overwrite the computed KV term in request_vector; now the
    construction path raises."""
    from types import SimpleNamespace
    fn = SimpleNamespace(family="affine", m=0.5, b=0.2)
    dm = SimpleNamespace(primary_fn=fn, primary_axis="kv",
                         curves={"hbm": SimpleNamespace(b=123.0)})
    with pytest.raises(ValueError, match="reserved"):
        ServingDemand.from_demand_model(dm, max_len=40)


# --- page-quantized demand --------------------------------------------------

def test_demand_books_page_quantized_kv():
    sd = ServingDemand(weights_gb=0.0, kv_gb_per_token=1e-3,
                       page_size=16)
    assert sd.kv_gb(1) == pytest.approx(1e-3 * 16)
    assert sd.kv_gb(16) == pytest.approx(1e-3 * 16)
    assert sd.kv_gb(17) == pytest.approx(1e-3 * 32)
    # page_size=1 (default) stays the exact dense-token model
    exact = ServingDemand(weights_gb=0.0, kv_gb_per_token=1e-3)
    assert exact.kv_gb(17) == pytest.approx(1e-3 * 17)
    req = Request(rid=0, prompt_len=5, max_new_tokens=4)
    vec = sd.request_vector(req)
    assert vec["hbm"] == pytest.approx(1e-3 * 16)
    with pytest.raises(ValueError):
        ServingDemand(weights_gb=0.0, kv_gb_per_token=1e-3, page_size=0)


def test_model_target_carries_page_size():
    from repro.sched import ModelTarget
    t = ModelTarget(object(), 32, page_size=8)
    assert t.page_size == 8
    assert ModelTarget(object(), 32).page_size == 1


# --- the real jax path ------------------------------------------------------

def _smoke_cfg():
    from repro.configs import get_config
    return get_config("qwen3-0.6b", smoke=True)


@pytest.mark.slow
def test_paged_jax_matches_dense_jax_token_streams():
    """The migration golden: equal prompt lengths + sync=1 +
    simultaneous arrival make the dense shim prefill with no left-pad,
    so the paged backend (chunked prefill + per-request lengths over the
    page pool) must reproduce its greedy streams bit-for-bit."""
    from repro.serve import JaxBackend, PagedJaxBackend
    cfg = _smoke_cfg()
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(3, cfg.vocab_size, 11))
               for _ in range(4)]

    def reqs():
        return [Request(rid=i, prompt_len=11, max_new_tokens=6,
                        arrival=0.0, prompt=list(prompts[i]))
                for i in range(4)]

    demand = ServingDemand(weights_gb=0.01, kv_gb_per_token=1e-6)
    budget = ResourceVector(hbm=100.0)

    def run(be):
        eng = Engine(reqs(), demand, budget, be, max_batch=4)
        assert eng.run()["completed"] == 4
        return {r.rid: list(r.tokens) for r in eng.requests}

    dense = run(JaxBackend(cfg, max_len=32, sync=1, seed=0))
    paged = run(PagedJaxBackend(cfg, num_pages=1 + 4 * 5, page_size=4,
                                prefill_chunk=4, seed=0))
    assert paged == dense


def test_prefill_chunk_kernel_path_matches_gather_path():
    """Three chunks of prefill on the tiny dense config, once through the
    paged prefill kernel (interpret mode) and once through the XLA
    gather: every active row's logits, and the KV written into the
    rows' pages, agree to bf16 tolerance.  Row 0 takes 11 prompt tokens
    in chunks of 4, 4 and 3; row 1 takes 7 (4 and 3, then idles); row 2
    stays idle.  Page 0, the idle rows' scratch, is not compared."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib
    cfg = _smoke_cfg()
    params = model_lib.init(cfg, jax.random.key(0))
    table = np.zeros((3, 4), np.int32)
    table[0, :3] = [7, 2, 9]
    table[1, :2] = [4, 11]
    prompts = np.random.default_rng(3).integers(3, cfg.vocab_size, (2, 11))
    chunks = [([0, 0], [4, 4]), ([4, 4], [4, 3]), ([8, 0], [3, 0])]

    def run(use_pallas):
        step = jax.jit(lambda c, t, s, n, a: model_lib.prefill_chunk(
            params, cfg, c, t, s, n, a, use_pallas=use_pallas))
        cache = dict(model_lib.init_paged_cache(cfg, 3, 12, 4, max_pages=4),
                     table=jnp.asarray(table))
        logits = []
        for starts, lens in chunks:
            tokens = np.zeros((3, 4), np.int32)
            for b, (s, n) in enumerate(zip(starts, lens)):
                tokens[b, :n] = prompts[b, s:s + n]
            active = np.asarray([n > 0 for n in lens] + [False])
            out, cache = step(cache, jnp.asarray(tokens),
                              jnp.asarray(starts + [0], jnp.int32),
                              jnp.asarray(lens + [0], jnp.int32),
                              jnp.asarray(active))
            logits.append(np.asarray(out[active], np.float32))
        return logits, cache

    kernel_logits, kernel_cache = run(True)
    gather_logits, gather_cache = run(False)
    for a, b in zip(kernel_logits, gather_logits):
        np.testing.assert_allclose(a, b, atol=2e-2, rtol=2e-2)
    # bf16 pools: the two paths round attention differently, and each
    # layer's K/V carries the layers below; compare each layer's pages
    # by relative norm at the same 2e-2
    for name in ("k", "v"):
        got = np.asarray(kernel_cache[name][:, 1:], np.float32)
        want = np.asarray(gather_cache[name][:, 1:], np.float32)
        for layer in range(cfg.num_layers):
            err = np.linalg.norm(got[layer] - want[layer])
            assert err <= 2e-2 * np.linalg.norm(want[layer]), (name, layer)
    np.testing.assert_array_equal(np.asarray(kernel_cache["lens"]),
                                  [11, 7, 0])


def test_paged_jax_table_sized_by_longest_request():
    """The page table is as wide as the longest request the backend
    accepts, not the pool (the decode kernel keeps it in scalar memory),
    and the narrow table serves the pool-wide table's greedy streams."""
    from repro.serve import PagedJaxBackend
    cfg = _smoke_cfg()
    rng = np.random.default_rng(3)
    lens = rng.integers(6, 21, 4)
    prompts = [list(rng.integers(3, cfg.vocab_size, n)) for n in lens]
    demand = ServingDemand(weights_gb=0.01, kv_gb_per_token=1e-6)

    def run(be):
        reqs = [Request(rid=i, prompt_len=len(p), max_new_tokens=4,
                        arrival=0.0, prompt=list(p))
                for i, p in enumerate(prompts)]
        eng = Engine(reqs, demand, ResourceVector(hbm=100.0), be,
                     max_batch=4)
        assert eng.run()["completed"] == 4
        return {r.rid: list(r.tokens) for r in eng.requests}

    num_pages = 1 + 4 * pages_for(24, 4)
    wide = PagedJaxBackend(cfg, num_pages=num_pages, page_size=4,
                           prefill_chunk=8, seed=0)
    narrow = PagedJaxBackend(cfg, num_pages=num_pages, page_size=4,
                             prefill_chunk=8, seed=0, max_len=24)
    assert run(narrow) == run(wide)
    assert narrow.max_len == 24
    assert narrow._cache["table"].shape[1] == pages_for(24, 4)
    assert wide._cache["table"].shape[1] == num_pages - 1


@pytest.mark.slow
def test_paged_jax_preemption_and_staggered_arrivals():
    """Tight budget on the real paged backend: mid-stream joins at
    arbitrary progress, eviction + full-context recompute on rejoin,
    exact token counts, pool drained at the end."""
    from repro.serve import PagedJaxBackend
    cfg = _smoke_cfg()
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt_len=int(rng.integers(4, 20)),
                    max_new_tokens=int(rng.integers(4, 10)),
                    arrival=float(i) * 1e-3) for i in range(8)]
    sd = ServingDemand(weights_gb=0.01, kv_gb_per_token=1e-4,
                       page_size=4)
    budget = ResourceVector(hbm=0.01 + 1e-4 * 32 * 2.0)
    be = PagedJaxBackend(cfg, num_pages=1 + 8 * pages_for(32, 4),
                         page_size=4, prefill_chunk=8, seed=1)
    eng = Engine(reqs, sd, budget, be, max_batch=8)
    s = eng.run()
    assert s["completed"] == 8
    for r in eng.requests:
        assert len(r.tokens) == r.max_new_tokens
        assert all(isinstance(t, int) for t in r.tokens)
    assert be.alloc.allocated_pages == 0
    assert be.alloc.reserved_pages == 0


@pytest.mark.slow
def test_jax_dense_join_cost_golden():
    """S1 pin: the dense shim charges prefill at the PADDED position it
    actually computes (every row prefills to self._pos), not the raw
    prompt length."""
    from repro.serve import JaxBackend
    be = JaxBackend(_smoke_cfg(), max_len=48, sync=8, seed=0)
    cost = be.join([Request(rid=0, prompt_len=5, max_new_tokens=30)],
                   0.0)
    assert be._pos == 8
    assert cost == pytest.approx(be._timer.t_prefill_per_token * 8)
    cost = be.join([Request(rid=1, prompt_len=3, max_new_tokens=30)],
                   0.0)
    assert cost == pytest.approx(be._timer.t_prefill_per_token * 8)


@pytest.mark.slow
def test_jax_dense_cache_shape_hysteresis():
    """S2 pin: removals only re-bucket the batch axis down after
    `shrink_patience` consecutive shrink-eligible removals."""
    from repro.serve import JaxBackend
    be = JaxBackend(_smoke_cfg(), max_len=48, sync=8, seed=0,
                    shrink_patience=3)
    rs = [Request(rid=10 + i, prompt_len=4, max_new_tokens=40)
          for i in range(5)]
    be.join(rs, 0.0)
    caps = [be._last.shape[0]]
    for r in rs[:4]:
        be.remove([r])
        caps.append(be._last.shape[0])
    # cap 8 holds through 2 removals (streak < patience), shrinks on
    # the 3rd, then holds again
    assert caps == [8, 8, 8, 2, 2]


# --- what the served path names in a profiler trace -------------------------

SCOPES = {"embed", "qkv", "kv_write", "attention", "mlp", "head"}


def _within(inner, outer):
    return outer.start_ns <= inner.start_ns \
        and inner.start_ns + inner.duration_ns \
        <= outer.start_ns + outer.duration_ns


def test_paged_jax_serve_records_program_spans(tmp_path):
    """Under ``jax.profiler.trace`` a served run records the engine's
    and the backend's ``serve.*`` spans with their stats, nested as
    step > plan and call > inputs / dispatch / readback."""
    import jax
    from repro.serve import PagedJaxBackend
    cfg = _smoke_cfg()
    reqs = [Request(rid=i, prompt_len=6, max_new_tokens=3, arrival=0.0,
                    prompt=[3 + i] * 6) for i in range(2)]
    be = PagedJaxBackend(cfg, num_pages=9, page_size=4, prefill_chunk=4,
                         seed=0)
    eng = Engine(reqs, ServingDemand(weights_gb=0.01, kv_gb_per_token=1e-6),
                 ResourceVector(hbm=100.0), be, max_batch=2)
    with jax.profiler.trace(str(tmp_path)):
        assert eng.run()["completed"] == 2
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    ev = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    ev.setdefault(e.name, []).append(e)
    assert set(ev) == {"serve.step", "serve.plan", "serve.retire",
                       "serve.prefill_call", "serve.decode_call",
                       "serve.inputs", "serve.dispatch", "serve.readback"}
    steps = ev["serve.step"]
    assert len(steps) == len(ev["serve.plan"]) == len(ev["serve.retire"])
    for child in ev["serve.plan"] + ev["serve.retire"]:
        assert any(_within(child, s) for s in steps)
    last = dict(max(steps, key=lambda e: e.start_ns).stats)
    assert last["admitted"] == 2 and last["admission_wait_s"] >= 0.0
    # two 6-token prompts in chunks of 4: two chunk calls of 2 rows
    chunks = ev["serve.prefill_call"]
    assert [dict(c.stats)["tokens"] for c in chunks] == [8, 4]
    # the K/V each chunk call attends: start + chunk, summed over rows
    assert [dict(c.stats)["kv_tokens"] for c in chunks] == [8, 12]
    for call in chunks + ev["serve.decode_call"]:
        st = dict(call.stats)
        assert st["rows"] == 2 and st["pool"] == 8
        assert 0 < st["pages"] <= st["reserved"] <= st["pool"]
        phases = [next(e for e in ev[name] if _within(e, call))
                  for name in ("serve.inputs", "serve.dispatch",
                               "serve.readback")]
        assert [p.start_ns for p in phases] == sorted(
            p.start_ns for p in phases)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_step_programs_name_their_scopes(program):
    """Each step program's lowering names the model step's six scopes;
    the decode program names the paged kernel too."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib
    from repro.train.step import (build_paged_decode_step,
                                  build_prefill_chunk_step)
    cfg = _smoke_cfg()
    params = model_lib.abstract(cfg)
    cache = model_lib.init_paged_cache(cfg, 2, 9, 4, abstract_only=True,
                                       max_pages=4)
    rows = jax.ShapeDtypeStruct((2,), jnp.int32)
    active = jax.ShapeDtypeStruct((2,), jnp.bool_)
    if program == "decode":
        fn = build_paged_decode_step(cfg, use_pallas=True)
        args = (jax.ShapeDtypeStruct((2, 1), jnp.int32), active)
    else:
        fn = build_prefill_chunk_step(cfg)
        args = (jax.ShapeDtypeStruct((2, 4), jnp.int32), rows, rows,
                active)
    text = jax.jit(fn).lower(params, cache, *args).as_text(debug_info=True)
    # name-stack locations, not source-file ones
    names = {part for loc in re.findall(r'loc\("([^"]*)"', text)
             if not loc.endswith(".py") for part in loc.split("/")}
    assert SCOPES <= names
    assert ("paged_attention" in names) == (program == "decode")
