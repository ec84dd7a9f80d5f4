"""Observability: tracing + telemetry over the virtual-clock runtime,
and wall-clock spans of the served path.

Small primitives the whole stack hooks into:

* ``trace``     — :class:`Tracer`: Chrome/Perfetto ``trace_event``
  JSON spans, instants, counters and async spans, stamped from the
  VIRTUAL clock (``ts = t * 1e6`` µs), so a seeded run emits a
  byte-identical trace on any machine.  :class:`NullTracer` is the
  disabled default; :func:`validate_chrome_trace` checks schema and
  span-nesting invariants before a trace is written.
* ``telemetry`` — :class:`Telemetry`: a plain counter / gauge
  registry.  Deterministic counts (events per kind, stale drops,
  first admissions) live in ``counters``; wall-clock rates (events/sec)
  live ONLY in ``gauges`` so they can never leak into seed-pinned
  summaries.
* ``spans``     — :func:`span`: a ``jax.profiler.TraceAnnotation`` on
  the WALL clock, shared with the device's events in a
  ``jax.profiler`` trace; the served path's ``serve.*`` phases.  Use
  ``Tracer`` for policy runs on the virtual clock, ``span`` for where
  the chip path spends its time.
* ``report``    — :func:`~repro.obs.report.summarize`: rebuild the
  run's story from the trace alone (queueing / prefill / decode /
  transfer breakdown, per-node and per-link occupancy, goodput,
  migrations) — the library behind ``scripts/trace_report.py``.

Like ``repro.sched.cluster``, this package imports nothing from
``repro.core`` or ``repro.serve`` (stdlib only; ``span`` imports jax on
first use), so the runtime can import it without cycles.
"""
from repro.obs.spans import span  # noqa: F401
from repro.obs.telemetry import Telemetry  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    NullTracer,
    Tracer,
    validate_chrome_trace,
)
