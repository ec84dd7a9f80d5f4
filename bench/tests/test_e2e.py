"""The end-to-end arithmetic on a synthetic token log."""
import pytest

from harness.e2e import window_numbers


def test_window_numbers_by_hand():
    # (due, token host times); window of 10 s
    log = [
        (0.0, [1.0, 1.5, 2.5]),        # gaps 0.5, 1.0
        (2.0, [3.0, 9.0, 11.0]),       # gap 6.0; 11.0 after the close
        (8.0, []),                      # no token yet: waits 2.0
    ]
    out = window_numbers(log, 10.0)
    # ttft: 1.0, 1.0, 2.0
    assert out["ttft_n"] == 3
    assert out["ttft_p50_s"] == pytest.approx(1.0)
    assert out["ttft_p95_s"] == pytest.approx(1.0 + 0.9 * 1.0)
    # gaps: 0.5, 1.0, 6.0 and the open gap 10 - 9 = 1.0
    assert out["token_gap_n"] == 4
    assert out["token_gap_p50_ms"] == pytest.approx(1000.0)
    assert out["token_gap_p95_ms"] == pytest.approx(
        (1.0 + 0.85 * 5.0) * 1e3)
    assert out["output_tokens"] == 5
    assert out["output_tokens_per_s"] == pytest.approx(0.5)


def test_a_stall_shows_in_the_tail():
    steady = [(float(i), [i + 0.1 * k for k in range(1, 5)])
              for i in range(9)]
    stalled = steady + [(1.0, [])]
    assert window_numbers(stalled, 10.0)["ttft_p95_s"] \
        > 2 * window_numbers(steady, 10.0)["ttft_p95_s"]


def _row(rate, served, offered, early=0):
    return {"rate": rate, "output_tokens_per_s": served,
            "offered_tokens_per_s": offered, "backlog_due_early": early}


@pytest.mark.parametrize("rows,want", [
    # the served share holds at 0.9, then falls to 0.5 between 0.4 and 0.6
    ([_row(0.2, 18, 20), _row(0.4, 36, 40), _row(0.6, 30, 60)],
     0.4 + 0.2 * (0.9 - 0.765) / (0.9 - 0.5)),
    # a backlog of early requests stops it at the last sustained rate
    ([_row(0.2, 18, 20), _row(0.4, 36, 40), _row(0.6, 54, 60, early=2)],
     0.4),
    # every rate sustained: the highest swept
    ([_row(0.3, 27, 30), _row(0.1, 9, 10)], 0.3),
])
def test_knee(rows, want):
    import sweep
    assert sweep.knee(rows) == pytest.approx(want)
