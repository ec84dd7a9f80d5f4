"""Telemetry registry: counters and gauges.

One :class:`Telemetry` instance rides on every
:class:`~repro.sched.cluster.ClusterRuntime`; the runtime counts the
events it dispatches and the serving engine its first admissions
(``serve.admitted``, ``serve.admission_wait_s``).

The split is deliberate and load-bearing:

* ``counters``  — DETERMINISTIC accumulators (events dispatched per
  kind, stale drops, first admissions and their waits on the engine's
  clock).  Safe to surface in seed-pinned outputs: identical seeds give
  identical counters.
* ``gauges``    — point-in-time values that may come from the WALL
  clock (events/sec of real time).  These must never be copied into an
  engine/simulator summary dict — the traced-vs-untraced bit-identical
  acceptance check (and every golden) would break on machine speed.

Stdlib only, imports nothing from the rest of ``repro``.
"""
from __future__ import annotations

from typing import Dict


class Telemetry:
    """Plain counter / gauge registry (no locking — the runtime is
    single-threaded over a virtual clock)."""

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}

    def inc(self, name: str, by: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + by

    def set_gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    # --- reading ----------------------------------------------------------
    def counter(self, name: str) -> float:
        return self.counters.get(name, 0.0)

    def counters_with_prefix(self, prefix: str) -> Dict[str, float]:
        return {k: v for k, v in self.counters.items()
                if k.startswith(prefix)}

    def summary(self) -> Dict:
        """Counters and gauges verbatim."""
        return {"counters": dict(self.counters),
                "gauges": dict(self.gauges)}
