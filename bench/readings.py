"""The readings the output check's limit is set from, for one cell.

    python3 bench/readings.py --workload qwen3-0.6b.chat --seconds 15 \
        --seeds 101,102,103

In one process (one build and warm-up), for each seed: a window of the
cell's traffic at its rate, the check requests served to their last
token, then the program's widest gap (the lower reading) and the
control's (the upper reading: the float32 reference with float8 e4m3
matmuls in the program's place, its greedy token at each position read
against the float32 reference) over the same compared tokens.  One JSON
line per seed on standard output.  Needs the chip, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from harness import process
    process.prepare(ROOT)
    from harness import runner, spec
    cell = spec.load_cell(args.workload)
    devices, _ = runner.require_chip(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    served = runner.build(cell, seeds[0], devices[0])
    runner.warm_up(served)
    for seed in seeds:
        r = runner.run_cell(cell, seed, args.seconds, False,
                            time.perf_counter(), served=served,
                            control=True)
        print(json.dumps({
            "seed": seed, "served_gap": r["checks"]["served_gap"]["value"],
            "control_gap": r["control"]["control_gap"],
            "compared_tokens": r["checks"]["compared_tokens"]["value"],
            "window_compiles": r["checks"]["window_compiles"]["value"],
            "requests": r["control"]["requests"],
            "window": r["control"]["window"]}), flush=True)


if __name__ == "__main__":
    main()
