"""Observability subsystem (repro/obs/): tracing, telemetry, reports.

* ``Tracer`` — trace_event JSON schema validity, lazy track metadata,
  B/E nesting enforcement, seeded byte-determinism;
* ``validate_chrome_trace`` — rejects every malformed-shape class the
  benchmarks' schema gate guards against;
* zero-cost default — a traced engine / simulator run produces the
  SAME summary dict as the untraced run, bit for bit (the acceptance
  bar that lets tracing ride every run without a goldens fork);
* ``Telemetry`` on the runtime — per-kind event counters, stale drops,
  the engine's first admissions and their waits;
* ``span`` — the served path's wall-clock ``serve.*`` spans: inert and
  nestable with no profiler session;
* structured admission rejects + decision provenance;
* per-link utilization ledgers (``Topology.link_stats``) and the
  rejected-join axis counters in ``ServingMetrics``;
* ``repro.obs.report.summarize`` reproducing a traced run's goodput
  and migration count from the trace alone.
"""
import json

import numpy as np
import pytest

from repro.core import (MoEPredictor, SimConfig, Simulator,
                        spark_sim_suite, training_apps)
from repro.core.simulator import OursPolicy
from repro.obs import (NullTracer, Telemetry, Tracer, span,
                       validate_chrome_trace)
from repro.obs.report import summarize
from repro.sched import ClusterRuntime, ClusterState
from repro.sched.admission import AdmissionController
from repro.sched.resources import DemandModel, ResourceVector
from repro.sched.topology import Topology, get_topology
from repro.serve import Engine, Request, ServingDemand, SimBackend
from repro.core.experts import MemoryFunction


def make_requests(n, seed=0, rate=20.0, prompt=(8, 32), new=(8, 40)):
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return [Request(rid=i,
                    prompt_len=int(rng.integers(*prompt)),
                    max_new_tokens=int(rng.integers(*new)),
                    arrival=float(t[i]))
            for i in range(n)]


def _reference_engine(mode="continuous", tracer=None, **kw):
    demand = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                           host_ram_per_req_gb=0.01)
    full = 32 + 40
    budget = ResourceVector(hbm=0.5 + 2e-4 * full * 3.0,
                            host_ram=0.01 * 6.0)
    if kw.get("replicas", 1) == 1:
        kw.setdefault("backend", SimBackend())
    return Engine(make_requests(24, seed=0), demand, budget,
                  mode=mode, placement="fcfs", max_batch=16,
                  tracer=tracer, **kw)


def _topo_engine(migrate=True, tracer=None):
    demand = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4,
                           extra_axes={"net": 0.1})
    budget = ResourceVector(hbm=0.5 + 2e-4 * 56 * 2.5, net=1.0)
    topo = get_topology("two-rack", nodes=4, gbps=10.0,
                        uplink_gbps=(0.2, 4.0))
    reqs = [Request(rid=r.rid, prompt_len=r.prompt_len,
                    max_new_tokens=r.max_new_tokens, arrival=r.arrival,
                    ttft_deadline=0.5, tpot_deadline=0.05)
            for r in make_requests(24, seed=9, rate=120.0,
                                   prompt=(12, 25), new=(8, 33))]
    return Engine(reqs, demand, budget, mode="continuous",
                  placement="fcfs", max_batch=32, replicas=4,
                  router="topo-aware",
                  backends=[SimBackend(t_prefill_per_token=2e-3)
                            for _ in range(4)],
                  topology=topo, migrate=migrate,
                  ingress_gb_per_token=2e-3, tracer=tracer)


# --- Tracer -----------------------------------------------------------------

def test_tracer_emits_schema_valid_trace_with_track_metadata():
    tr = Tracer()
    tr.complete("step", 0.0, 0.5, process="replica0", thread="steps",
                cat="serving", args={"batch": 3})
    tr.instant("join", 0.1, process="replica0", thread="events")
    tr.counter("node0:util", 0.5, {"hbm": 0.7, "host_ram": 0.2},
               process="replica0")
    tr.async_begin("req", 0.0, 7, cat="request", process="requests",
                   thread="lifecycle")
    tr.async_end("req", 0.9, 7, cat="request", process="requests",
                 thread="lifecycle", args={"tokens": 12})
    tr.begin("outer", 1.0)
    tr.begin("inner", 1.1)
    tr.end(1.2, name="inner")
    tr.end(1.3)
    payload = tr.chrome()
    validate_chrome_trace(payload)          # does not raise
    # lazy track registry: one process_name M event per process, one
    # thread_name per (process, thread), stable first-use pids
    meta = [e for e in payload["traceEvents"] if e["ph"] == "M"]
    procs = {e["args"]["name"]: e["pid"] for e in meta
             if e["name"] == "process_name"}
    assert set(procs) == {"replica0", "requests", "runtime"}
    assert procs["replica0"] == 1           # first-use order
    # virtual seconds became microseconds
    step = next(e for e in payload["traceEvents"] if e["name"] == "step")
    assert step["ts"] == 0.0 and step["dur"] == pytest.approx(5e5)
    assert len(tr) == len(payload["traceEvents"])


def test_tracer_end_enforces_nesting():
    tr = Tracer()
    with pytest.raises(ValueError, match="no open span"):
        tr.end(1.0)
    tr.begin("a", 0.0)
    with pytest.raises(ValueError, match="does not match"):
        tr.end(0.5, name="b")
    tr.end(0.6, name="a")                   # the mismatch didn't pop
    validate_chrome_trace(tr.chrome())


@pytest.mark.parametrize("bad", [
    "not a dict",
    {"no": "traceEvents"},
    {"traceEvents": "not a list"},
    {"traceEvents": [{"ph": "Z", "name": "x", "pid": 1, "tid": 1,
                      "ts": 0}]},
    {"traceEvents": [{"ph": "i", "name": "", "pid": 1, "tid": 1,
                      "ts": 0}]},
    {"traceEvents": [{"ph": "i", "name": "x", "pid": "1", "tid": 1,
                      "ts": 0}]},
    {"traceEvents": [{"ph": "i", "name": "x", "pid": 1, "tid": 1,
                      "ts": -1.0}]},
    {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                      "ts": 0}]},                      # missing dur
    {"traceEvents": [{"ph": "b", "name": "x", "pid": 1, "tid": 1,
                      "ts": 0}]},                      # async sans id/cat
    {"traceEvents": [{"ph": "C", "name": "x", "pid": 1, "tid": 1,
                      "ts": 0, "args": {"v": "high"}}]},
    {"traceEvents": [{"ph": "E", "name": "x", "pid": 1, "tid": 1,
                      "ts": 0}]},                      # E with no B
    {"traceEvents": [{"ph": "B", "name": "x", "pid": 1, "tid": 1,
                      "ts": 0}]},                      # unclosed B
])
def test_validator_rejects_malformed_traces(bad):
    with pytest.raises(ValueError):
        validate_chrome_trace(bad)


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert not nt.enabled
    nt.complete("x", 0, 1)
    nt.begin("x", 0)
    nt.end(1)
    nt.instant("x", 0)
    nt.counter("x", 0, {"v": 1})
    nt.async_begin("x", 0, 1, cat="c")
    nt.async_end("x", 1, 1, cat="c")
    assert len(nt) == 0 and nt.chrome()["traceEvents"] == []


# --- zero-cost default: traced == untraced, bit for bit ---------------------

def test_traced_engine_summary_bit_identical_to_untraced():
    untraced = _reference_engine().run()
    tracer = Tracer()
    traced = _reference_engine(tracer=tracer).run()
    assert traced == untraced               # dict ==, every key exact
    assert len(tracer) > 0
    validate_chrome_trace(tracer.chrome())


def test_traced_trace_is_seed_deterministic():
    """Two identical seeded runs emit byte-identical traces — no
    wall-clock value ever enters a trace."""
    blobs = []
    for _ in range(2):
        tr = Tracer()
        _reference_engine(tracer=tr).run()
        blobs.append(json.dumps(tr.chrome(), sort_keys=True))
    assert blobs[0] == blobs[1]


@pytest.fixture(scope="module")
def suite():
    apps = spark_sim_suite()
    moe = MoEPredictor().fit(training_apps(apps))
    return apps, moe


def test_traced_simulator_bit_identical_and_spans_balanced(suite):
    apps, moe = suite
    jobs = [(apps[i], 30.0) for i in (0, 5, 11, 17)]
    untraced = Simulator(jobs, OursPolicy(moe), SimConfig(n_hosts=6),
                         seed=3).run()
    tracer = Tracer()
    traced = Simulator(jobs, OursPolicy(moe), SimConfig(n_hosts=6),
                       seed=3, tracer=tracer).run()
    assert traced == untraced
    validate_chrome_trace(tracer.chrome())
    evs = tracer.events
    # every job/exec async span that opened also closed
    for cat in ("job", "exec"):
        opened = {e["id"] for e in evs
                  if e["ph"] == "b" and e.get("cat") == cat}
        closed = {e["id"] for e in evs
                  if e["ph"] == "e" and e.get("cat") == cat}
        assert opened and opened == closed


# --- Telemetry on the runtime -----------------------------------------------

def test_runtime_counts_events_and_stale_drops():
    rt = ClusterRuntime(ClusterState.homogeneous(
        1, ResourceVector(hbm=1.0)))
    rt.on("ev", lambda t, p: None)
    rt.on("stale", lambda t, p: False)
    for t in (1.0, 2.0, 3.0):
        rt.push(t, "ev", None)
    rt.push(2.5, "stale", None)
    rt.run()
    tm = rt.telemetry
    assert tm.counter("events.ev") == 3
    assert tm.counter("events.stale.stale") == 1
    assert tm.counter("events.dispatched") == 4
    assert tm.gauges["wall_s"] >= 0.0       # wall gauges exist but are
    #   never copied into summaries (the bit-identical check above
    #   would break on machine speed if they were)
    s = tm.summary()
    assert s["counters"]["events.ev"] == 3


def test_telemetry_summary_holds_counters_and_gauges():
    tm = Telemetry()
    tm.inc("x")
    tm.inc("x", 2.0)
    tm.set_gauge("g", 3)
    assert tm.summary() == {"counters": {"x": 3.0}, "gauges": {"g": 3.0}}


def _first_joins(tracer):
    """rid -> virtual seconds of its first ``join`` instant."""
    joins = {}
    for e in tracer.chrome()["traceEvents"]:
        if e["ph"] == "i" and e["name"] == "join":
            joins.setdefault(e["args"]["rid"], e["ts"] / 1e6)
    return joins


def test_engine_counts_first_admissions_and_their_waits():
    """``serve.admitted`` counts each request once, at its first join,
    and ``serve.admission_wait_s`` adds its wait from arrival."""
    tracer = Tracer()
    eng = _reference_engine(tracer=tracer)
    eng.run()
    tm = eng.telemetry
    joins = _first_joins(tracer)
    assert tm.counter("serve.admitted") == len(joins) == len(eng.requests)
    want = sum(joins[r.rid] - r.arrival for r in eng.requests)
    assert tm.counter("serve.admission_wait_s") == pytest.approx(
        want, abs=1e-9)
    assert want > 0.0                       # the budget made some wait


def test_admission_wait_matches_the_reports_queueing():
    """Requests that all arrive at once are routed at their arrival, so
    the wait the engine counts equals the routed -> first-join queueing
    ``obs.report.summarize`` rebuilds from the trace alone."""
    demand = ServingDemand(weights_gb=0.5, kv_gb_per_token=2e-4)
    budget = ResourceVector(hbm=0.5 + 2e-4 * 72 * 3.0)
    reqs = [Request(rid=r.rid, prompt_len=r.prompt_len,
                    max_new_tokens=r.max_new_tokens, arrival=0.0)
            for r in make_requests(12, seed=4)]
    tracer = Tracer()
    eng = Engine(reqs, demand, budget, backend=SimBackend(),
                 mode="continuous", placement="fcfs", max_batch=16,
                 tracer=tracer)
    eng.run()
    rep = summarize(tracer.chrome())
    assert eng.telemetry.counter("serve.admitted") == 12
    assert eng.telemetry.counter("serve.admission_wait_s") == pytest.approx(
        rep["breakdown"]["queueing_s"], rel=1e-9)
    assert rep["breakdown"]["queueing_s"] > 0.0


# --- wall-clock spans -------------------------------------------------------

def test_span_is_inert_without_a_profiler_and_nests():
    order = []
    with span("serve.step", admitted=3, admission_wait_s=0.5):
        order.append("step")
        with span("serve.plan"):
            order.append("plan")
        with span("serve.retire"):
            order.append("retire")
    assert order == ["step", "plan", "retire"]
    with pytest.raises(KeyError):           # exceptions pass through
        with span("serve.step"):
            raise KeyError("x")


# --- structured admission rejects + provenance ------------------------------

def test_admit_reject_reason_names_axis_and_deficit():
    ctrl = AdmissionController()
    dm = DemandModel({"hbm": MemoryFunction("affine", 0.0, 5.0)})
    dec = ctrl.admit(dm, ResourceVector(hbm=2.0), floor=1.0)
    assert dec.units == 0.0
    rej = dec.info["reject"]
    assert rej["axis"] == "hbm"
    assert rej["floor"] == 1.0
    # the smallest useful grant (1 unit = 5 GB) overshoots by 3 GB
    assert rej["deficit"]["hbm"] == pytest.approx(3.0)


def test_admit_target_records_provenance(suite):
    apps, moe = suite
    from repro.sched.estimator import JobTarget, get_estimator
    ctrl = AdmissionController(
        estimator=get_estimator("moe", predictor=moe))
    free = ResourceVector(host_ram=40.0)
    dec = ctrl.admit_target(JobTarget(apps[0], 100.0), free, cap=64.0,
                            rng=np.random.default_rng(0))
    prov = dec.info["provenance"]
    assert prov["free"] == dict(free.items())
    assert prov["binding_axis"] == dec.binding_axis
    assert set(prov["confidence"]) >= {"host_ram"}
    assert isinstance(prov["conservative"], bool)
    # the shaded budget the inverse actually saw, not the raw free
    assert prov["budget"]["host_ram"] <= prov["free"]["host_ram"]


def test_serving_metrics_count_rejects_by_axis():
    out = _reference_engine().run()
    assert out["rejected_joins"] == sum(out["rejects_by_axis"].values())
    if out["rejected_joins"]:
        assert all(isinstance(k, str) and v > 0
                   for k, v in out["rejects_by_axis"].items())


# --- per-link utilization ledgers -------------------------------------------

def test_link_stats_conserve_bytes_and_busy_time():
    topo = Topology("pair")
    topo.add_node("a")
    topo.add_node("b")
    topo.add_link("a", "b", 1.0)
    rt = ClusterRuntime(ClusterState.homogeneous(
        1, ResourceVector(hbm=1.0)))
    topo.attach(rt)
    topo.transmit("a", "b", 1.0, now=0.0)
    topo.transmit("a", "b", 1.0, now=0.5)   # overlaps: peak 2 flows
    rt.run()
    stats = topo.link_stats(elapsed=2.0)
    (st,) = stats.values()
    assert st["bytes_gb"] == pytest.approx(2.0)
    assert st["busy_s"] == pytest.approx(2.0)   # busy 0.0 -> 2.0
    assert st["busy_frac"] == pytest.approx(1.0)
    assert st["peak_flows"] == 2


def test_topology_engine_reports_link_stats():
    out = _topo_engine(migrate=True).run()
    assert out["migrations"] > 0
    links = out["links"]
    assert links and all(
        set(st) >= {"busy_s", "busy_frac", "bytes_gb", "peak_flows"}
        for st in links.values())
    # KV actually moved over at least one link
    assert sum(st["bytes_gb"] for st in links.values()) > 0.0


# --- trace -> report round trip ---------------------------------------------

def test_report_reproduces_goodput_and_migrations_from_trace():
    untraced = _topo_engine(migrate=True).run()
    tracer = Tracer()
    traced = _topo_engine(migrate=True, tracer=tracer).run()
    assert traced == untraced               # tracing changed nothing
    payload = tracer.chrome()
    validate_chrome_trace(payload)
    rep = summarize(payload)
    # the acceptance bar: the trace alone reproduces the run's metrics
    assert rep["goodput_tok_s"] == untraced["goodput_tok_s"]
    assert rep["migrations"] == untraced["migrations"]
    assert rep["completed"] == untraced["completed"]
    assert rep["elapsed_s"] == untraced["elapsed_s"]
    # breakdown + occupancy are populated and sane
    assert rep["breakdown"]["decode_s"] > 0.0
    assert rep["per_node"] and all(
        0.0 <= st["occupancy"] <= 1.0 for st in rep["per_node"].values())
    assert rep["events_by_kind"].get("step", 0) > 0


def test_report_format_is_printable():
    tracer = Tracer()
    out = _reference_engine(tracer=tracer).run()
    from repro.obs.report import format_report
    rep = summarize(tracer.chrome())
    txt = format_report(rep, title="ref")
    assert "goodput" in txt and "breakdown" in txt
    assert rep["goodput_tok_s"] == out["goodput_tok_s"]
