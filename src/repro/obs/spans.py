"""Wall-clock spans of the served path, for a ``jax.profiler`` trace.

:func:`span` is a ``jax.profiler.TraceAnnotation``.  While a profiler
session is live (``jax.profiler.trace(dir)`` around a serve) each span
is one host event on the profiler's clock, the clock the device's
program and op events share, with its keyword stats as arguments; with
no session it records nothing and costs well under a microsecond.  No
flag turns it on: the session is the switch.

Names are ``serve.<phase>``; stats are ints or floats.  Use
:class:`~repro.obs.trace.Tracer` for the virtual clock (policy runs,
``scripts/trace_report.py``) and :func:`span` for where the wall time
and the chip's time go on the served path.

jax is imported on the first span, so ``repro.obs`` stays importable
without it.
"""
from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def span(name: str, **stats):
    """A context manager recording ``name`` with ``stats`` while a
    ``jax.profiler`` session is live; nests like any context."""
    return _annotation()(name, **stats)
