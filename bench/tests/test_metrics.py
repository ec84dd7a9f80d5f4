"""Each per-layer reader, the trace reduction and the cost arithmetic,
on a small recorded trace: one engine step (a prefill-chunk call and a
decode call) of a traced ``qwen3-0.6b.chat`` run on one v5e, with a
synthetic call log for its two calls.  Also: a metric added as a file
and an entry is found by name."""
import gzip
import json
import shutil

import pytest

from harness import costs, runner, spec, trace

DATA = spec.BENCH / "tests" / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONTEXTS = [300, 517, 900, 1200, 1500, 2000, 2500]
ROWS = [[0, 128], [256, 128], [1024, 77]]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "chat_step.json.gz") as f:
        raw = json.load(f)
    ops = [trace.Event(n, s, d) for n, s, d in raw["ops"]]
    mods = [trace.Event(n, s, d) for n, s, d in raw["modules"]]
    spans = [trace.Event(n, s, d, st) for n, s, d, st in raw["spans"]]
    calls = [{} for _ in range(29)] + [
        {"kind": "chunk", "cap": 32, "rows": ROWS, "pages": 700,
         "reserved": 900, "pool": 2303},
        {"kind": "decode", "cap": 32, "contexts": CONTEXTS, "pages": 720,
         "reserved": 900, "pool": 2303}]
    config = json.loads((spec.BENCH / "configs" /
                         "qwen3-0.6b.json").read_text())
    return trace.build(ops, mods, spans, calls, config, PEAKS, 16)


def _read(name, tr):
    return runner.load_reader(name)(tr)


def test_window_and_calls(recorded):
    assert recorded.window == (42698601, 42698601 + 443356624)
    assert sorted(recorded.calls) == [29, 30]


def test_device_idle_share(recorded):
    busy, end = 0, None
    for a, b in sorted((o.start, o.end) for o in recorded.ops):
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    idle = 100 * (1 - busy / (recorded.window[1] - recorded.window[0]))
    assert _read("device_idle_share", recorded) == pytest.approx(idle)
    assert 0 < idle < 5          # this step kept the chip busy


def test_program_device_times(recorded):
    chunk = [m.dur for m in recorded.modules
             if "jit_prefill_chunk_step" in m.name]
    decode = [m.dur for m in recorded.modules
              if "jit_paged_decode_step" in m.name]
    assert len(chunk) == len(decode) == 1
    assert _read("prefill_chunk_device_ms", recorded) \
        == pytest.approx(chunk[0] / 1e6)
    assert _read("decode_step_device_ms", recorded) \
        == pytest.approx(decode[0] / 1e6)


def test_host_ms_per_step(recorded):
    step = next(s for s in recorded.spans if s.name == "bench.engine_step")
    busy = trace.covered(recorded.busy(), step.start, step.end)
    assert _read("host_ms_per_step", recorded) \
        == pytest.approx((step.dur - busy) / 1e6)


def test_rows_per_decode_step(recorded):
    assert _read("rows_per_decode_step", recorded) == len(CONTEXTS)


@pytest.mark.parametrize("name", ["kv_pages_in_use_share.ttft",
                                  "kv_pages_in_use_share.tokens"])
def test_kv_pages_in_use_share(recorded, name):
    assert _read(name, recorded) == pytest.approx(100 * 710 / 2303)


def test_paged_attn_roofline(recorded):
    kernel = recorded.kernel_events("paged_attention")
    assert len(kernel) == 28                 # one per layer
    need = 0
    for ctx in CONTEXTS:   # K and V pages, q and out, bf16, 28 layers
        need += 28 * (2 * -(-ctx // 16) * 16 * 8 * 128 * 2
                      + 2 * 16 * 128 * 2)
    want = need / 819e9 / (sum(k.dur for k in kernel) * 1e-9) * 100
    assert _read("paged_attn_roofline", recorded) == pytest.approx(want)
    assert 0 < want <= 100


def test_mfu(recorded):
    cfg = recorded.config
    per = costs.matmul_params_per_layer(cfg)
    assert per == 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 \
        + 3 * 1024 * 3072
    head = 2 * 1024 * 151936
    flops = sum(28 * (2 * per + 4 * c * 2048) + head for c in CONTEXTS)
    for s, n in ROWS:
        keys = sum(p + 1 for p in range(s, s + n))
        flops += 28 * (2 * per * n + 4 * keys * 2048) + head
    want = flops / (recorded.window_s * 197e12) * 100
    assert _read("mfu", recorded) == pytest.approx(want)
    assert 0 < want < 100


def test_breakdown(recorded):
    b = trace.breakdown(recorded)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("prefill_chunk_step:")
    total_self = sum(t for _, t in trace._self_times(recorded.ops))
    assert total_self * 1e-9 <= recorded.window_s
    assert all(s > 0 for _, s in b["idle_gaps"])


def test_a_metric_file_and_entry_are_found_by_name(tmp_path, recorded):
    home = tmp_path / "bench"
    shutil.copytree(DATA / "traffic", home / "traffic")
    shutil.copytree(DATA / "cells", home / "cells")
    shutil.copytree(spec.BENCH / "metrics", home / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (home / "metrics" / "kernel_calls.py").write_text(
        "def read(tr):\n"
        "    return len(tr.kernel_events('paged_attention')) or None\n")
    from conftest import tiny_benchmark
    bm = tiny_benchmark()
    bm["per_layer"].append({
        "name": "kernel_calls", "unit": "calls", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "token_gap_p95_ms", "workloads": ["tiny.chat"]})
    cell = spec.load_cell("tiny.chat", bench=home, benchmark=bm)
    got = runner.per_layer(cell, recorded)
    assert got["kernel_calls"] == {"value": 28.0, "unit": "calls"}
    assert set(got) == {m["name"] for m in bm["per_layer"]}
