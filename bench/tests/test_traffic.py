"""The generator: a fixed multiset of sizes and gaps in a seeded order,
check requests drawn from the window's own requests, and a
compared-token count that no seed or window length changes."""
import json
from collections import Counter

import numpy as np
import pytest

from harness import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))
SEEDS = [0, 1, 7, 2**31 + 5, 3_000_000_017]


def _mix(name):
    return json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())


def _sizes(plan, only_check=False):
    return Counter((len(p.prompt), p.max_new) for p in plan
                   if p.check or not only_check)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seconds", [1, 10, 30, 51])
def test_check_requests_do_not_depend_on_seed_or_window(mix, seconds):
    m = _mix(mix)
    want = Counter(traffic.round_pairs(m))
    for seed in SEEDS:
        plan = traffic.plan(m, 0.4, seconds, seed, 151936)
        assert _sizes(plan, only_check=True) == want
        assert sum(p.max_new for p in plan if p.check) \
            == traffic.compared_tokens(m)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("rate,seconds", [(0.2, 51), (0.45, 51), (1.3, 40)])
def test_check_requests_are_counted_among_the_window_and_hold_its_longest(
        mix, rate, seconds):
    m = _mix(mix)
    plan = traffic.plan(m, rate, seconds, 11, 151936)
    assert len(plan) == max(round(rate * seconds), m["round"])
    check = [p for p in plan if p.check]
    assert len(check) == m["round"]
    for size in (lambda p: len(p.prompt), lambda p: p.max_new):
        assert max(map(size, check)) == max(map(size, plan))
    # every size in the window is one of the round's pairs
    assert set(_sizes(plan)) <= set(traffic.round_pairs(m))


@pytest.mark.parametrize("mix", MIXES)
def test_round_follows_the_mix_quantiles(mix):
    m = _mix(mix)
    pairs = traffic.round_pairs(m)
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(n for _, n in pairs)
    k = m["round"]
    for dist, got in ((m["prompt_tokens"], prompts),
                      (m["output_tokens"], outputs)):
        # the middle quantile pair straddles the published median
        assert got[(k - 1) // 2] <= dist["median"] <= got[k // 2]
        assert dist["min"] <= got[0] and got[-1] <= dist["max"]


@pytest.mark.parametrize("mix", MIXES)
def test_every_request_is_due_inside_the_window_and_in_range(mix):
    m = _mix(mix)
    for seed in SEEDS:
        plan = traffic.plan(m, 2.0, 30, seed, 151936)
        due = [p.due for p in plan]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30
        for p in plan:
            lo, hi = m["prompt_tokens"]["min"], m["prompt_tokens"]["max"]
            assert lo <= len(p.prompt) <= hi
            assert ((p.prompt >= traffic.FIRST_ID)
                    & (p.prompt < 151936)).all()


@pytest.mark.parametrize("rate", [1.0, 0.37])
def test_seeds_reorder_the_same_work(rate):
    m = _mix(MIXES[0])
    a = traffic.plan(m, rate, 40, 3, 151936)
    b = traffic.plan(m, rate, 40, 4, 151936)
    assert _sizes(a) == _sizes(b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    gaps = [sorted(np.round(np.diff([p.due for p in x]), 9)) for x in (a, b)]
    assert gaps[0] == gaps[1]
    c = traffic.plan(m, rate, 40, 3, 151936)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               and x.check == y.check for x, y in zip(a, c))
