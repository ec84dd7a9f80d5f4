"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
root of the checkout.  They run on the CPU at a tiny size (the cell in
``data/``) and, for the compile rehearsals, for a described v5e."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

DATA = BENCH / "tests" / "data"


def tiny_benchmark() -> dict:
    """BENCHMARK.json with its cells replaced by the tiny CPU cell."""
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bm["configs"] = [{"name": "tiny",
                      "file": "bench/tests/data/configs/tiny.json"}]
    bm["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                        "traffic": "tiny", "chips": 1}]
    for m in bm["end_to_end"]:
        m.pop("workloads", None)
    for m in bm["per_layer"]:
        m["workloads"] = ["tiny.chat"]
    return bm


@pytest.fixture
def tiny_cell():
    from harness import spec
    return spec.load_cell("tiny.chat", bench=DATA,
                          benchmark=tiny_benchmark())
