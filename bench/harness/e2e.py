"""End-to-end numbers of one window, from the host clock's token log.

Times are seconds after the window opened.  The window is ``[0,
seconds)``; every request in it was due inside it.

* time to first token: from when the request was due to when its first
  token reached the host; a request with no token by the close counts
  with its wait so far, so a stall shows;
* gap between tokens: every gap between consecutive tokens of a request
  that both reached the host inside the window, plus, for a request
  still decoding at the close, the open gap since its last token;
* output tokens per second: tokens that reached the host inside the
  window, over the window's seconds.

Percentiles are numpy's (linear interpolation).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def window_numbers(requests: Sequence[Tuple[float, Sequence[float]]],
                   seconds: float) -> Dict[str, float]:
    """``requests`` holds ``(due, token_times)`` per request."""
    T = float(seconds)
    ttft, gaps, tokens = [], [], 0
    for due, times in requests:
        inside = [t for t in times if t < T]
        tokens += len(inside)
        ttft.append((inside[0] if inside else T) - due)
        gaps.extend(np.diff(inside).tolist())
        if inside and len(times) > len(inside):
            gaps.append(T - inside[-1])
    ttft = np.asarray(ttft, float)
    gaps = np.asarray(gaps, float)
    out = {
        "ttft_p95_s": float(np.percentile(ttft, 95)),
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_n": int(ttft.size),
        "token_gap_p95_ms": float(np.percentile(gaps, 95)) * 1e3
        if gaps.size else float("nan"),
        "token_gap_p50_ms": float(np.percentile(gaps, 50)) * 1e3
        if gaps.size else float("nan"),
        "token_gap_n": int(gaps.size),
        "output_tokens_per_s": tokens / T,
        "output_tokens": tokens,
    }
    return out
