"""kv_pages_in_use_share.ttft: batcher + pages (``serve/paged.py``'s
page allocator), in a cell that judges the wait for the first token.

Pages that hold KV over the usable page pool, averaged over the traced
device calls, in %: how much of the pool the cell's traffic fills.  The
program's own count (``PageAllocator.allocated_pages``), read by the
harness at each call.  A larger share means admission lets more rows in
at once, so requests wait less for their first token.
"""


def read(tr):
    calls = [c for c in tr.calls.values() if "pages" in c]
    if not calls:
        return None
    return 100.0 * sum(c["pages"] / c["pool"] for c in calls) / len(calls)
