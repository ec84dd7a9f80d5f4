"""host_ms_per_step: engine host loop (``serve/engine.py``,
``serve/batcher.py``, the host side of ``serve/paged.py``).

Mean over the traced engine steps of the step span's self time not
covered by device operations, in ms: routing, admission, batching, page
tables, uploads, dispatch and the token read-back's host side.
"""
from harness.trace import covered


def read(tr):
    steps = [s for s in tr.spans if s.name == "bench.engine_step"]
    if not steps:
        return None
    busy = tr.busy()
    host = [s.dur - covered(busy, s.start, s.end) for s in steps]
    return sum(host) / len(host) * 1e-6
