"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload qwen3-0.6b.chat --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout.  The cell's configuration, traffic and
settings are found by name from ``BENCHMARK.json``.  The run needs the
chip: it exits non-zero, printing no result, when JAX's first device is
not a TPU listed in ``bench/peaks.json`` or there are fewer chips than
the cell asks for.  Everything but the result goes to standard error;
the last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and ``checks`` last).  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer ones.  JAX's compile cache is kept in ``.jax_cache`` at the
root of the checkout.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from harness import process
    process.prepare(ROOT)
    from harness import runner, spec
    cell = spec.load_cell(args.workload)
    result = runner.run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), T_START,
                             trace_dir=args.trace_dir)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
