"""From a ``jax.profiler`` trace to what the per-layer readers read.

A traced run records a short steady part of the window.  The trace
holds, on one clock:

* device events of the first chip (plane ``/device:TPU:0``): the
  line ``XLA Ops`` has one event per operation, the line ``XLA
  Modules`` one per program run;
* the harness's host spans (``bench.engine_step``, ``bench.idle_wait``,
  ``bench.prefill_call``, ``bench.decode_call``; the last two carry the
  index of their device call in the call log as ``call``).

Programs are found by their jitted names as the trace prints them
(:data:`PROGRAMS`: ``jit_prefill_chunk_step(<hash>)``,
``jit_paged_decode_step(<hash>)``).  The Pallas kernels carry no name
in a v5e trace: an op event's name is its HLO text, and a kernel's is
``custom-call(...)`` with ``custom_call_target="tpu_custom_call"``.  So
a kernel is found as the ``tpu_custom_call`` ops inside the one program
that holds it (:data:`KERNELS`; the paged decode program holds the
paged-attention kernel and no other).  On the ``XLA Ops`` line a loop
op (the layer scan's ``while``) spans the ops of its body; device busy
time is the union of the op intervals, and the breakdown ranks ops by
self time.  The traced window runs from the start of the first engine
step or idle wait inside the trace to the end of the last.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: program -> the text its module events' names contain
PROGRAMS = {"prefill_chunk": "prefill_chunk_step",
            "decode": "paged_decode_step"}
#: kernel -> (the program holding it, the text its op events contain)
KERNELS = {"paged_attention":
           ("decode", 'custom_call_target="tpu_custom_call"')}
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_SPANS = ("bench.engine_step", "bench.idle_wait")


@dataclass
class Event:
    name: str
    start: int          # ns
    dur: int            # ns
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    spans: List[Event]
    window: Tuple[int, int]
    calls: Dict[int, dict]          # call log entries seen in the trace
    config: dict
    peaks: dict
    page_size: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self) -> List[Tuple[int, int]]:
        return union([(e.start, e.end) for e in self.ops])

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy()) * 1e-9

    def call_spans(self, kind: str) -> List[Event]:
        name = {"chunk": "bench.prefill_call",
                "decode": "bench.decode_call"}[kind]
        return [s for s in self.spans if s.name == name
                and int(s.stats.get("call", -1)) in self.calls]

    def program_events(self, program: str) -> List[Event]:
        key = PROGRAMS[program]
        return [m for m in self.modules if key in m.name]

    def kernel_events(self, kernel: str) -> List[Event]:
        program, key = KERNELS[kernel]
        runs = union([(m.start, m.end)
                      for m in self.program_events(program)])
        return [o for o in self.ops if key in o.name
                and covered(runs, o.start, o.end) == o.dur]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: Sequence[Tuple[int, int]], a: int, b: int) -> int:
    """Length of ``[a, b)`` covered by the merged intervals."""
    return sum(max(0, min(b, y) - max(a, x)) for x, y in merged)


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        try:
            stats = dict(ev.stats)
        except (TypeError, ValueError):
            stats = {}
        out.append(Event(ev.name, int(ev.start_ns), int(ev.duration_ns),
                         stats))
    return out


def read_xplane(trace_dir: str) -> Tuple[List[Event], List[Event],
                                         List[Event]]:
    """(device ops, device modules, bench host spans) of a trace."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith("bench."))
    return ops, modules, spans


def build(ops, modules, spans, call_log: Sequence[dict], config: dict,
          peaks: dict, page_size: int) -> Optional[Trace]:
    """The traced window and what lies inside it; None when the trace
    holds no whole engine step."""
    steps = [s for s in spans if s.name in STEP_SPANS]
    if not steps:
        return None
    w0 = min(s.start for s in steps)
    w1 = max(s.end for s in steps)

    def inside(e):
        return e.start >= w0 and e.end <= w1
    calls = {}
    for s in spans:
        if s.name in ("bench.prefill_call", "bench.decode_call") \
                and inside(s):
            i = int(s.stats.get("call", -1))
            if 0 <= i < len(call_log):
                calls[i] = call_log[i]
    return Trace(ops=[o for o in ops if inside(o)],
                 modules=[m for m in modules if inside(m)],
                 spans=[s for s in spans if inside(s)], window=(w0, w1),
                 calls=calls, config=config, peaks=peaks,
                 page_size=page_size)


def _self_times(ops: Sequence[Event]) -> List[Tuple[Event, int]]:
    """Each op with its duration less that of the ops nested in it."""
    out: List[List] = []
    stack: List[List] = []
    for o in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= o.start:
            stack.pop()
        entry = [o, o.dur]
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] -= o.dur
        stack.append(entry)
        out.append(entry)
    return [(o, max(t, 0)) for o, t in out]


def _label(op: Event, modules: Sequence[Event]) -> str:
    """``<program>:<hlo op> <result type>``, shortened."""
    prog = next((m.name for m in modules if m.start <= op.start < m.end),
                "?")
    prog = prog.split("(")[0].replace("jit_", "")
    head, _, rest = op.name.partition(" = ")
    return f"{prog}:{head.lstrip('%')} {rest.split('{')[0]}"[:120]


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most self time, and the longest
    idle gaps labelled by the innermost harness span they fell in."""
    by_op: Dict[str, int] = {}
    for o, t in _self_times(tr.ops):
        label = _label(o, tr.modules)
        by_op[label] = by_op.get(label, 0) + t
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busy = tr.busy()
    gaps, prev = [], tr.window[0]
    for a, b in busy + [(tr.window[1], tr.window[1])]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    labelled = []
    for a, b in gaps:
        mid = (a + b) // 2
        around = [s for s in tr.spans if s.start <= mid < s.end]
        label = min(around, key=lambda s: s.dur).name if around \
            else "outside bench spans"
        labelled.append((label, (b - a) * 1e-9))
    labelled.sort(key=lambda x: -x[1])
    return {"device_ops": [[n, d * 1e-9] for n, d in device_ops],
            "idle_gaps": [[n, s] for n, s in labelled[:top]]}
