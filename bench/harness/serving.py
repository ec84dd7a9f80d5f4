"""The served path on the wall clock.

The window drives ``repro.serve.engine.Engine`` in continuous mode, one
replica, over ``PagedJaxBackend`` (chunked prefill, paged decode with
the compiled paged-attention kernel): the path ``launch/serve.py``
builds, with the same estimator, admission and batcher.  Two subclasses
here put it on the host's clock without changing what it computes:

* :class:`TimedBackend` returns the measured wall time of each join and
  decode instead of the modeled cost the program's backend returns, and
  logs the host time at which each token reached the host (each device
  call ends in a host copy of its tokens) and the page pool's use at
  each device call;
* :class:`WallClockEngine` starts every step at the wall clock's
  ``now`` and, when idle, sleeps until the next request is due, so
  arrivals are released when they are due.

The engine's own modeled times are never read.  In a traced run each
engine step, idle wait and device call is a ``jax.profiler``
``TraceAnnotation`` (``bench.*``), on the same clock as the device
events.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

from repro.serve import PagedJaxBackend

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class CallLog:
    """What the harness saw of the served path: per-request token host
    times and one record per device call."""
    tokens: Dict[int, List[float]] = field(default_factory=dict)
    calls: List[dict] = field(default_factory=list)
    steps: List[float] = field(default_factory=list)   # step starts


class Clock:
    """Seconds since the window opened (``perf_counter`` based)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


class CompileCounter:
    """Host times of every XLA compile (or compile-cache load) in this
    process, from JAX's monitoring events."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t < b)


def _span(on: bool, name: str, **stats):
    return jax.profiler.TraceAnnotation(name, **stats) if on \
        else contextlib.nullcontext()


class TimedBackend(PagedJaxBackend):
    """``PagedJaxBackend`` whose join and decode return measured wall
    seconds, logging token arrival times and device calls."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.clock: Callable[[], float] = Clock()
        self.log = CallLog()
        self.annotate = False
        self._tokens_at = 0.0

    def join(self, reqs, now):
        t = time.perf_counter()
        super().join(reqs, now)
        return time.perf_counter() - t

    def decode(self, running):
        t = time.perf_counter()
        super().decode(running)
        return time.perf_counter() - t

    def _pages(self) -> dict:
        """The page pool at a device call: pages holding KV, pages
        reserved by admission (each row's prompt and answer), usable."""
        return {"pages": self.alloc.allocated_pages,
                "reserved": self.alloc.reserved_pages,
                "pool": self.alloc.usable_pages}

    def _stamp(self, reqs, before):
        for r in reqs:
            times = self.log.tokens.setdefault(r.rid, [])
            times.extend([self._tokens_at] * (len(r.tokens) - before[r.rid]))

    def _advance_chunks(self, reqs):
        before = {r.rid: len(r.tokens) for r in reqs}
        cost = super()._advance_chunks(reqs)
        self._stamp(reqs, before)
        return cost

    def _prefill_rows(self, work):
        n = len(self.log.calls)
        self.log.calls.append(dict(
            self._pages(), kind="chunk", cap=self._cap,
            rows=[[s, cl] for _, s, cl in work]))
        with _span(self.annotate, "bench.prefill_call", call=n):
            toks = super()._prefill_rows(work)
        self._tokens_at = self.clock()
        return toks

    def _decode_rows(self, decoding):
        n = len(self.log.calls)
        self.log.calls.append(dict(
            self._pages(), kind="decode", cap=self._cap,
            contexts=[r.context_len for r in decoding]))
        before = {r.rid: len(r.tokens) for r in decoding}
        with _span(self.annotate, "bench.decode_call", call=n):
            cost = super()._decode_rows(decoding)
        self._tokens_at = self.clock()
        self._stamp(decoding, before)
        return cost


def wall_clock_engine_class():
    """The engine subclass (built lazily: importing the engine pulls in
    the scheduler stack)."""
    from repro.serve.engine import Engine

    class WallClockEngine(Engine):
        """Continuous-mode ``Engine`` whose steps start at the wall
        clock's now; an idle engine sleeps until the next arrival is
        due.  ``on_step(now)`` runs before every step (trace control)."""

        def __init__(self, *args, clock, annotate=False, on_step=None,
                     log=None, **kw):
            super().__init__(*args, **kw)
            self.clock = clock
            self.annotate = annotate
            self.on_step = on_step
            self.log = log

        def _on_step(self, t, payload):
            now = self.clock()
            if t > now:
                with _span(self.annotate, "bench.idle_wait"):
                    time.sleep(t - now)
                now = self.clock()
            if self.on_step is not None:
                self.on_step(now)
            if self.log is not None:
                self.log.steps.append(now)
            with _span(self.annotate, "bench.engine_step"):
                return super()._on_step(max(t, now), payload)

    return WallClockEngine
