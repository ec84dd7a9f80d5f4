"""Benchmark orchestrator: one module per paper table/figure.

``python -m benchmarks.run``              runs everything
``python -m benchmarks.run --bench fig06 roofline``  subset
``python -m benchmarks.run --smoke --bench open_arrivals tpu_colocation``
    tiny n_jobs/n_hosts/n_mixes end-to-end pass (the CI gate)
``python -m benchmarks.run --placement sjf --bench fig06``
    run every simulation under a non-default placement policy
    (repro.sched.placement registry: fcfs / sjf / best-fit /
    arrival-aware)
``python -m benchmarks.run --estimator conservative --bench open_arrivals``
    run the OURS policy through a non-default demand estimator
    (sweepable repro.sched.estimator entries: moe / oracle /
    single-family / conservative; baselines keep their defining
    predictors) — the CI smoke gate sweeps moe + conservative
``python -m benchmarks.run --smoke --replicas 2 --router net-aware --bench serving_bench``
    size the serving bench's multi-replica routing cell
    (repro.sched.cluster Router registry: single / least-loaded /
    net-aware / drf — drf is the weighted-DRF fairness router from
    repro.sched.tenancy; the serving bench's noisy-neighbor tenancy
    cell always runs drf internally regardless of --router)

Prints ``name,value,derived`` CSV rows; per-bench JSON lands in results/.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache

BENCHES = [
    "fig06_stp_antt",      # main result: STP/ANTT L1..L10, 5 policies
    "fig07_utilization",   # utilization trace + makespan, L10 mix
    "fig09_unified",       # MoE vs unified single-model predictors
    "fig10_online_search",  # vs descent-search allocation
    "fig11_overhead",      # profiling overhead fractions
    "fig13_cpu_load",      # isolation CPU load distribution
    "fig14_interference",  # pairwise co-location slowdown distribution
    "fig16_clusters",      # PCA cluster structure + selector accuracy
    "fig17_accuracy",      # LOOCV memory prediction error
    "table5_classifiers",  # alternative expert selectors
    "roofline",            # dry-run roofline table (all cells)
    "kernel_bench",        # kernel wrappers (interpret-mode) + XLA refs
    "tpu_colocation",      # beyond-paper: TPU-jobs universe
    "open_arrivals",       # beyond-paper: Poisson stream, windowed STP
    "serving_bench",       # beyond-paper: continuous vs wave serving
    "elastic_bench",       # beyond-paper: elastic vs rigid under failures
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", nargs="*", default=None,
                    help="prefixes of benchmarks to run")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny n_jobs/n_hosts/n_mixes smoke pass (CI)")
    ap.add_argument("--placement", default=None,
                    help="placement policy for every SimConfig "
                         "(fcfs/sjf/best-fit/arrival-aware)")
    ap.add_argument("--estimator", default=None,
                    help="demand estimator for the OURS policy in every "
                         "SimConfig (moe/oracle/single-family/"
                         "conservative)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="replica count for the serving bench's "
                         "multi-replica routing cell")
    ap.add_argument("--router", default=None,
                    help="router for the serving bench's multi-replica "
                         "cell (single/least-loaded/net-aware/drf)")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome/Perfetto trace of the serving "
                         "bench's two-rack cell to this path; the bench "
                         "validates the trace against the trace_event "
                         "schema and asserts the traced run's metrics "
                         "are bit-identical to the untraced run")
    args = ap.parse_args()
    # env, not arguments: bench modules build their SimConfigs
    # themselves; the environment is read at (deferred) import time
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        os.environ.setdefault("REPRO_BENCH_MIXES", "2")
    if args.placement is not None:
        from repro.sched.placement import available_placements
        if args.placement not in available_placements():
            ap.error(f"unknown placement {args.placement!r} "
                     f"(available: {available_placements()})")
        os.environ["REPRO_PLACEMENT"] = args.placement
    if args.estimator is not None:
        from repro.sched.estimator import SWEEPABLE_ESTIMATORS
        if args.estimator not in SWEEPABLE_ESTIMATORS:
            ap.error(f"estimator {args.estimator!r} is not sweepable "
                     f"(choose from: {SWEEPABLE_ESTIMATORS})")
        os.environ["REPRO_ESTIMATOR"] = args.estimator
    if args.replicas is not None:
        if args.replicas < 1:
            ap.error(f"--replicas must be >= 1 (got {args.replicas})")
        os.environ["REPRO_SERVE_REPLICAS"] = str(args.replicas)
    if args.router is not None:
        from repro.sched.cluster import available_routers
        if args.router not in available_routers():
            ap.error(f"unknown router {args.router!r} "
                     f"(available: {available_routers()})")
        os.environ["REPRO_SERVE_ROUTER"] = args.router
    if args.trace is not None:
        os.environ["REPRO_TRACE"] = args.trace
    todo = BENCHES if not args.bench else [
        b for b in BENCHES if any(b.startswith(p) for p in args.bench)]
    failures = []
    for name in todo:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.main()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks completed")


if __name__ == "__main__":
    main()
