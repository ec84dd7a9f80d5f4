"""One run of one cell: set-up, the measured window, the trace, the
output check and the result line.

Set-up (timed as ``setup_s``, from process start to the window's first
due arrival): weights from the seed, the served backend, and a warm-up
serve that grows the backend's row capacity to the cell's batch bucket
and compiles every program the window uses at that one shape (the
backend's capacity never shrinks, so the window runs no other shape).
The window then serves the cell's traffic on the wall clock, drains
every request to its last token outside the timing, and the output
check runs the reference over the check requests once the KV cache is
freed and the memory peak has been read.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from harness import check, e2e, spec, traffic, trace, weights
from harness.serving import (CallLog, Clock, CompileCounter, TimedBackend,
                             wall_clock_engine_class)

#: the traced part of the window: it opens at this share of the window
#: and lasts TRACE_SECONDS or a quarter of the window, whichever is
#: less, and then until it holds a prefill-chunk call and a decode call
#: (at the latest, until the window closes)
TRACE_AT = 0.4
TRACE_SECONDS = 5.0


def require_chip(chips: int):
    """The devices and their peaks; exits (no result) unless the first
    device is a TPU listed in ``peaks.json`` and there are enough."""
    import jax
    devices = jax.devices()
    first = devices[0]
    print(f"device: platform={first.platform} kind={first.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if first.platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX's first device is "
                         f"{first.platform} {first.device_kind!r}); this "
                         f"benchmark measures only on a chip")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices, spec.peaks(first.device_kind)


@dataclass
class Served:
    """A built backend with what the engine needs beside it."""
    cell: spec.Cell
    params: dict
    backend: TimedBackend
    demand: object
    budget: object

    def engine(self, requests, clock, **kw):
        Engine = wall_clock_engine_class()
        return Engine(requests, self.demand, self.budget,
                      backend=self.backend, mode="continuous",
                      placement="fcfs", router="single",
                      max_batch=int(self.cell.deployment["max_batch"]),
                      clock=clock, **kw)


def build(cell: spec.Cell, seed: int, device) -> Served:
    """Weights, backend and admission as ``launch/serve.py`` builds them
    (kv-growth estimator, paged backend), budget = weights + pool."""
    from repro.models import model as model_lib
    from repro.sched import ModelTarget, ResourceVector, get_estimator
    from repro.serve import ServingDemand
    dep = cell.deployment
    cfg = spec.program_config(cell.config)
    params = weights.make(cell.config, seed, device)
    weights.check_layout(params, model_lib.abstract(cfg))
    backend = TimedBackend(
        cfg, params=params, num_pages=int(dep["num_pages"]),
        page_size=int(dep["page_size"]),
        prefill_chunk=int(dep["prefill_chunk"]),
        max_len=int(dep["max_len"]), device=device)
    est = get_estimator("kv-growth").estimate(ModelTarget(
        cfg, int(dep["max_len"]), page_size=int(dep["page_size"])))
    demand = ServingDemand.from_estimate(est, int(dep["max_len"]))
    pool = (int(dep["num_pages"]) - 1) * int(dep["page_size"])
    budget = ResourceVector(hbm=demand.weights_gb + demand.kv_gb(pool))
    return Served(cell, params, backend, demand, budget)


def warm_up(s: Served) -> None:
    """Serve ``max_batch`` two-chunk requests at once: the backend's
    capacity reaches the batch bucket and the chunk, decode and token
    read-back programs compile at the window's shapes."""
    from repro.serve import Request
    n = int(s.cell.deployment["max_batch"])
    plen = int(s.cell.deployment["prefill_chunk"]) + 1
    reqs = [Request(rid=i, prompt_len=plen, max_new_tokens=2, arrival=0.0,
                    prompt=[traffic.FIRST_ID + i] * plen) for i in range(n)]
    s.backend.log = CallLog()
    s.engine(reqs, Clock()).run()
    want = 1 << max(n - 1, 0).bit_length()
    if s.backend._cap != want:
        raise RuntimeError(f"warm-up left the backend at {s.backend._cap} "
                           f"rows, not the batch bucket {want}")


class TraceWindow:
    """Starts and stops the profiler at engine-step boundaries."""

    def __init__(self, directory: Optional[str], start: float,
                 length: float, close: float, log: CallLog):
        self.directory, self.start, self.end = directory, start, \
            start + length
        self.close, self.log, self.first_call = close, log, 0
        self.state = "off" if directory is None else "armed"

    def on_step(self, now: float) -> None:
        import jax
        if self.state == "armed" and now >= self.start:
            jax.profiler.start_trace(self.directory)
            self.state = "on"
            self.first_call = len(self.log.calls)
        elif self.state == "on" and now >= self.end:
            kinds = {c["kind"] for c in self.log.calls[self.first_call:]}
            if kinds >= {"chunk", "decode"} or now >= self.close:
                self.stop()

    def stop(self) -> None:
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"


def serve_window(s: Served, planned, seconds: float,
                 trace_dir: Optional[str] = None):
    """Serve ``planned`` on the wall clock and drain it; returns
    (engine, call log, host time the window opened)."""
    from repro.serve import Request
    requests = [Request(rid=p.rid, prompt_len=len(p.prompt),
                        max_new_tokens=p.max_new, arrival=p.due,
                        prompt=p.prompt.tolist()) for p in planned]
    log = CallLog()
    tw = TraceWindow(trace_dir, TRACE_AT * seconds,
                     min(TRACE_SECONDS, 0.25 * seconds), seconds, log)
    clock = Clock()
    engine = s.engine(requests, clock, annotate=trace_dir is not None,
                      on_step=tw.on_step, log=log)
    s.backend.log, s.backend.clock = log, clock
    s.backend.annotate = trace_dir is not None
    clock.t0 = time.perf_counter()
    try:
        engine.run()
    finally:
        tw.stop()
        s.backend.annotate = False
    return engine, log, clock.t0


def lateness(steps, planned) -> np.ndarray:
    """Seconds from each request's due time to the start of the first
    engine step that could release it."""
    steps = np.asarray(sorted(steps))
    due = np.asarray([p.due for p in planned])
    idx = np.searchsorted(steps, due - 1e-12)
    ok = idx < len(steps)
    return steps[idx[ok]] - due[ok]


def load_reader(name: str, home=spec.BENCH):
    """``<home>/metrics/<name>.py``'s ``read`` function."""
    path = home / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: spec.Cell, tr: trace.Trace) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"], cell.home)(tr)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, device_check: bool = True,
             trace_dir: Optional[str] = None, served: Optional[Served] = None,
             control: bool = False) -> dict:
    """One run; returns the result line's object (``checks`` last).
    ``served`` reuses a built and warmed backend (new weights from
    ``seed``); ``control`` also reads the control's gaps (``control``
    key), for the readings the output check's limit is set from."""
    import jax
    if device_check:
        devices, peaks = require_chip(cell.chips)
    else:
        devices = jax.devices()
        peaks = next(iter(spec._load(spec.BENCH / "peaks.json")
                          ["devices"].values()))
    device = devices[0]
    compiles = CompileCounter.get()
    t = time.perf_counter()
    if served is None:
        served = build(cell, seed, device)
        say(f"set-up: weights and backend {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        warm_up(served)
        say(f"set-up: warm-up serve {time.perf_counter() - t:.3f} s")
    else:
        # the old weights go first (two 14B stages do not fit a chip);
        # the new ones are committed to the device like the backend's
        # own, or the served programs would compile again
        served.params = served.backend.params = None
        gc.collect()
        served.params = jax.device_put(
            weights.make(cell.config, seed, device), device)
        served.backend.params = served.params
    planned = traffic.plan(cell.traffic, cell.rate_per_s, seconds, seed,
                           int(cell.config["vocab_size"]))
    own_dir = traced and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        engine, log, t0 = serve_window(served, planned, seconds,
                                       trace_dir if traced else None)
        t_drained = time.perf_counter()
        nums = e2e.window_numbers(
            [(p.due, log.tokens.get(p.rid, [])) for p in planned], seconds)
        stats = device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        window_compiles = compiles.between(t0, t0 + seconds)
        tr = None
        if traced:
            if not own_dir:
                with open(os.path.join(trace_dir, "calls.json"), "w") as f:
                    json.dump(log.calls, f)
            ops, modules, spans = trace.read_xplane(trace_dir)
            tr = trace.build(ops, modules, spans, log.calls, cell.config,
                             peaks, int(cell.deployment["page_size"]))
    finally:
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    reqs = {r.rid: r for r in engine.requests}
    unfinished = sum(1 for r in engine.requests if not r.done)
    checks_in = [(p.prompt.tolist(), list(reqs[p.rid].tokens))
                 for p in planned if p.check]
    late = lateness(log.steps, planned)
    chunk_calls = sum(1 for c in log.calls if c["kind"] == "chunk")
    say(f"window: {seconds} s from {t0 - t_start:.3f} s after start; "
        f"{len(planned)} requests due ({sum(p.check for p in planned)} "
        f"check), drained {t_drained - t0 - seconds:.3f} s after the "
        f"close; {len(log.steps)} engine steps, {chunk_calls} chunk calls, "
        f"{len(log.calls) - chunk_calls} decode calls; preemptions "
        f"{sum(r.preemptions for r in engine.requests)}")
    say(f"window: ttft p50 {nums['ttft_p50_s']:.4f} s p95 "
        f"{nums['ttft_p95_s']:.4f} s over {nums['ttft_n']} requests; "
        f"token gap p50 {nums['token_gap_p50_ms']:.3f} ms p95 "
        f"{nums['token_gap_p95_ms']:.3f} ms over {nums['token_gap_n']} "
        f"gaps; {nums['output_tokens']} output tokens")
    pool = [c for c in log.calls if "pages" in c]
    if pool:
        say(f"page pool: {pool[0]['pool']} usable; holding KV peak "
            f"{max(c['pages'] for c in pool)} mean "
            f"{np.mean([c['pages'] for c in pool]):.1f}; reserved peak "
            f"{max(c['reserved'] for c in pool)} mean "
            f"{np.mean([c['reserved'] for c in pool]):.1f} pages")
    if late.size:
        say(f"release lateness (due -> engine step that took it): p50 "
            f"{np.percentile(late, 50) * 1e3:.3f} ms p95 "
            f"{np.percentile(late, 95) * 1e3:.3f} ms max "
            f"{late.max() * 1e3:.3f} ms")
    say(f"memory: peak {peak} bytes in use on {device}")
    # the program's state goes before the reference runs
    served.backend._cache = None
    del engine
    gc.collect()
    t = time.perf_counter()
    cmp = check.compare(served.params, cell.config, checks_in, control)
    say(f"output check: {cmp['compared_tokens']} tokens of "
        f"{len(checks_in)} requests in {time.perf_counter() - t:.3f} s; "
        f"widest gap per request "
        f"{[round(r['widest_gap'], 5) for r in cmp['requests']]}")
    floor = traffic.compared_tokens(cell.traffic)
    checks = {
        "served_gap": {"value": cmp["served_gap"],
                       "limit": cell.limit("served_gap")},
        "compared_tokens": {"value": cmp["compared_tokens"],
                            "limit": floor},
        "window_compiles": {"value": window_compiles, "limit": 0},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    correct = (cmp["served_gap"] <= checks["served_gap"]["limit"]
               and cmp["compared_tokens"] >= floor
               and window_compiles == 0 and unfinished == 0)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    setup_s = t0 - t_start
    if traced:
        metrics = per_layer(cell, tr) if tr is not None else {}
        if tr is not None:
            dev["busy_s"] = tr.busy_s()
            dev["window_s"] = tr.window_s
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(nums, setup_s=setup_s)
        metrics = {n: {"value": float(values[n]), "unit": u}
                   for n, u in names.items()}
    result = {"correct": bool(correct), "attempted": len(planned),
              "failed": unfinished, "metrics": metrics, "device": dev}
    if traced and tr is not None:
        result["breakdown"] = trace.breakdown(tr)
    if control:
        result["control"] = {"control_gap": cmp["control_gap"],
                             "requests": cmp["requests"], "window": nums,
                             "setup_s": setup_s}
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
