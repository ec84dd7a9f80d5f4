"""The program's ``serve.*`` spans beside the harness's, on a recorded
step: one engine step (a prefill-chunk call and a decode call) of a
traced ``qwen3-0.6b.chat`` window on one v5e, with the call log of its
two calls.  Every reader of the harness's own spans and calls reads the
same with the program's spans kept beside them, and the breakdown's idle
gaps then name program phases."""
import gzip
import json

import pytest

from harness import runner, spec, trace

DATA = spec.BENCH / "tests" / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = json.loads((spec.BENCH / "configs" / "qwen3-0.6b.json").read_text())
OLD = ("host_ms_per_step", "rows_per_decode_step",
       "kv_pages_in_use_share.ttft", "kv_pages_in_use_share.tokens",
       "decode_step_device_ms", "prefill_chunk_device_ms",
       "paged_attn_roofline", "mfu", "device_idle_share")


@pytest.fixture(scope="module")
def raw():
    with gzip.open(DATA / "serve_step.json.gz") as f:
        return json.load(f)


def _build(raw, serve: bool):
    ops = [trace.Event(n, s, d, st) for n, s, d, st in raw["ops"]]
    mods = [trace.Event(n, s, d) for n, s, d in raw["modules"]]
    spans = [trace.Event(n, s, d, st) for n, s, d, st in raw["spans"]
             if serve or n.startswith("bench.")]
    calls = [{} for _ in range(1 + max(map(int, raw["calls"])))]
    for i, c in raw["calls"].items():
        calls[int(i)] = c
    return trace.build(ops, mods, spans, calls, CONFIG, PEAKS, 16)


@pytest.fixture(scope="module")
def recorded(raw):
    return _build(raw, serve=True)


def _read(name, tr):
    return runner.load_reader(name)(tr)


def test_the_recording(recorded):
    names = {s.name for s in recorded.spans}
    assert {"bench.engine_step", "serve.step", "serve.plan",
            "serve.prefill_call", "serve.decode_call", "serve.inputs",
            "serve.dispatch", "serve.readback", "serve.retire"} <= names
    assert {c["kind"] for c in recorded.calls.values()} \
        == {"chunk", "decode"}


@pytest.mark.parametrize("name", OLD)
def test_readers_of_the_harness_ignore_program_spans(raw, recorded, name):
    without = _build(raw, serve=False)
    assert _read(name, recorded) == _read(name, without)
    assert _read(name, recorded) is not None


def test_breakdown_labels_gaps_with_program_phases(raw, recorded):
    """Only the gaps' labels change: they name the innermost span,
    which is now a program phase."""
    with_serve = trace.breakdown(recorded)
    without = trace.breakdown(_build(raw, serve=False))
    assert with_serve["device_ops"] == without["device_ops"]
    assert [s for _, s in with_serve["idle_gaps"]] \
        == [s for _, s in without["idle_gaps"]]
    # the longest gaps: the next call's inputs, the token read-back
    assert {n for n, _ in with_serve["idle_gaps"][:3]} \
        == {"serve.inputs", "serve.readback"}
    assert {n for n, _ in without["idle_gaps"][:3]} \
        <= {"bench.engine_step", "bench.prefill_call", "bench.decode_call"}
