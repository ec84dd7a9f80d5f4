"""kv_pages_in_use_share.tokens: the same share as
``kv_pages_in_use_share.ttft``, in a cell that judges the tokens served
in the window: the more of the pool holds KV, the more requests run at
once and complete before the close.
"""


def read(tr):
    calls = [c for c in tr.calls.values() if "pages" in c]
    if not calls:
        return None
    return 100.0 * sum(c["pages"] / c["pool"] for c in calls) / len(calls)
