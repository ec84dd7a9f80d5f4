"""Whole runs of the tiny cell on the CPU, with the device check
skipped: a sound run is correct whatever the window's length; each
fault planted in the timed path, and the control put in the program's
place, comes out not correct.

Faults (the cell can have no exchange between chips: one chip):

* a decode step that returns its cache unchanged (no KV written);
* half of the batch left out of each prefill chunk (the later half of
  the chunk's rows, rounded up, so a chunk of one row leaves it out:
  their KV is never written, though they complete);
* a token altered where it is produced (in every decode call, the
  last decoding row's token off by one).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, runner, spec, traffic


def _run(cell, seed, seconds, patch=None):
    served = runner.build(cell, seed, jax.devices()[0])
    runner.warm_up(served)
    if patch is not None:
        patch(served.backend)
    return runner.run_cell(cell, seed, seconds, False, time.perf_counter(),
                           device_check=False, served=served)


def test_sound_runs_are_correct_and_compare_the_same_count(tiny_cell):
    floor = traffic.compared_tokens(tiny_cell.traffic)
    for seed, seconds in ((3, 1.0), (2**31 + 11, 3.0)):
        r = _run(tiny_cell, seed, seconds)
        assert r["correct"], r["checks"]
        assert r["checks"]["compared_tokens"]["value"] == floor
        assert r["attempted"] == max(round(tiny_cell.rate_per_s * seconds),
                                     tiny_cell.traffic["round"])


def _state_unchanged(be):
    step = be._decode

    def frozen(params, cache, token, active):
        keep = jax.tree.map(jnp.copy, cache)
        logits, _ = step(params, cache, token, active)
        return logits, keep
    be._decode = frozen


def _half_batch(be):
    step = be._chunk

    def half(params, cache, tokens, start, chunk_lens, active):
        active = jnp.asarray(active, bool)
        rank = jnp.cumsum(active) - 1
        keep = rank < jnp.sum(active) // 2
        return step(params, cache, tokens, start, chunk_lens,
                    jnp.logical_and(active, keep))
    be._chunk = half


def _token_altered(be):
    rows = be._decode_rows

    def altered(decoding):
        cost = rows(decoding)
        r = decoding[-1]
        r.tokens[-1] = (r.tokens[-1] + 1) % be.cfg.vocab_size
        return cost
    be._decode_rows = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_a_planted_fault_is_not_correct(tiny_cell, fault):
    r = _run(tiny_cell, 4, 2.0, fault)
    assert not r["correct"]
    assert r["checks"]["served_gap"]["value"] \
        > r["checks"]["served_gap"]["limit"]


def test_the_control_fails_the_limit(tiny_cell, monkeypatch):
    """The reference at fp8 in the program's place: its greedy tokens,
    read against the float32 reference, fall outside the limit."""
    real = check.gaps

    def control_only(params, config, prompt, served, control=False):
        _, low = real(params, config, prompt, served, control=True)
        return low, np.zeros(0)
    monkeypatch.setattr(check, "gaps", control_only)
    r = _run(tiny_cell, 5, 2.0)
    assert not r["correct"]
