"""rows_per_decode_step: batcher + pages (``serve/batcher.py``,
``serve/paged.py``).

Mean number of rows that decode a token in each traced decode call (a
count).  At a cell's fixed arrival rate the rows in flight are the rate
times each request's time in the system (Little's law), so fewer rows
a call means requests are served sooner; a larger batch also lengthens
each call, and with it the gap between tokens.
"""


def read(tr):
    calls = [tr.calls[int(s.stats["call"])] for s in tr.call_spans("decode")]
    if not calls:
        return None
    return sum(len(c["contexts"]) for c in calls) / len(calls)
