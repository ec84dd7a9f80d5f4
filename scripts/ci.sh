#!/usr/bin/env bash
# Fast tier-1 gate with a hard wall-clock timeout, so the red/slow-suite
# regression (hypothesis import killing collection; >2 min runs) cannot
# silently come back.  After the fast pytest selection, a tiny --smoke
# benchmark pass exercises the bench plumbing end-to-end (including the
# multi-axis vector-admission scenario and the net-binding-axis
# scenario), once per demand estimator in $CI_SMOKE_ESTIMATORS
# (default: the default wrap + the conservative registry entry); then a
# replica-routing pass runs the continuous-vs-wave serving sweep
# (asserts continuous >= wave goodput AND routed > single-node goodput
# with 2 replicas net-aware) plus open_arrivals through the
# ClusterRuntime shim; finally a noisy-neighbor tenancy pass re-runs
# the serving bench under --router drf (asserts compliant tenants keep
# >= 0.9 SLO attainment within 10% of their isolated SLO-good tokens
# while aggregate goodput stays within 5% of the untenanted baseline)
# — all inside the SAME wall-clock cap.
#
#   scripts/ci.sh            # fast selection + smoke, <= $CI_TIMEOUT_S (120)
#   CI_FULL=1 scripts/ci.sh  # full suite incl. @slow tier-2 (longer cap)
#   CI_WALL_CAP=300 scripts/ci.sh  # raise the wall cap (slow container)
#   CI_SMOKE_BENCHES="..."   # override the smoke bench subset ("" skips)
#   CI_SMOKE_ESTIMATORS="..."  # override the --estimator sweep
set -euo pipefail
cd "$(dirname "$0")/.."

# CI_WALL_CAP is the coarse knob (whole-gate wall budget, default 120s
# kept); CI_TIMEOUT_S still wins when set explicitly
CI_TIMEOUT_S="${CI_TIMEOUT_S:-${CI_WALL_CAP:-120}}"
PYTHON="${PYTHON:-python}"
# serving_bench ignores --estimator (it builds ServingDemand directly),
# so it runs ONCE, in the replica-routing pass below, not per estimator
CI_SMOKE_BENCHES="${CI_SMOKE_BENCHES-open_arrivals tpu_colocation}"
START_S=$SECONDS

MARK_ARGS=()
if [ "${CI_FULL:-0}" = "1" ]; then
    MARK_ARGS=(-m "")               # include @slow tier-2 tests
    CI_TIMEOUT_S="${CI_FULL_TIMEOUT_S:-600}"
fi

echo "ci: running tier-1 (timeout ${CI_TIMEOUT_S}s)"
rc=0
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    timeout --signal=TERM --kill-after=15 "$CI_TIMEOUT_S" \
    "$PYTHON" -m pytest -x -q "${MARK_ARGS[@]+"${MARK_ARGS[@]}"}" || rc=$?
if [ $rc -eq 124 ]; then
    echo "ci: FAILED — tier-1 exceeded the ${CI_TIMEOUT_S}s budget" >&2
fi
[ $rc -ne 0 ] && exit $rc

# Smoke benchmarks ride the remaining budget of the same cap, swept
# across demand estimators (the moe pass IS the default wrap; the
# conservative pass drives OURS through the registry's no-selector
# fallback estimator end-to-end).
CI_SMOKE_ESTIMATORS="${CI_SMOKE_ESTIMATORS-moe conservative}"
if [ -n "$CI_SMOKE_BENCHES" ]; then
    for EST in $CI_SMOKE_ESTIMATORS; do
        REMAIN_S=$(( CI_TIMEOUT_S - (SECONDS - START_S) ))
        if [ "$REMAIN_S" -lt 10 ]; then
            echo "ci: FAILED — no budget left for smoke benchmarks" \
                 "(${REMAIN_S}s of ${CI_TIMEOUT_S}s)" >&2
            exit 1
        fi
        echo "ci: running smoke benchmarks (--estimator $EST," \
             "${REMAIN_S}s left): $CI_SMOKE_BENCHES"
        # shellcheck disable=SC2086
        PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
            timeout --signal=TERM --kill-after=15 "$REMAIN_S" \
            "$PYTHON" -m benchmarks.run --smoke --estimator "$EST" \
            --bench $CI_SMOKE_BENCHES || rc=$?
        if [ $rc -eq 124 ]; then
            echo "ci: FAILED — smoke benchmarks exceeded the remaining" \
                 "${REMAIN_S}s budget" >&2
        fi
        [ $rc -ne 0 ] && exit $rc
    done
fi

# Multi-replica routing smoke (repro.sched.cluster): the serving bench's
# net-contended cell with 2 replicas routed net-aware (asserts routed >
# single-node goodput) AND its network-topology cell (asserts topo-aware
# + KV migration strictly beats net-aware + local requeue on SLO goodput
# over the asymmetric two-rack fabric, emits BENCH_topology.json), plus
# an open_arrivals pass — which since the ClusterRuntime redesign runs
# the simulator through the event-driven runtime shim end-to-end.  The
# pass runs with --trace: the bench re-runs the two-rack cell traced,
# asserts the traced metrics are bit-identical to the untraced run,
# schema-validates the trace_event JSON, and reproduces the cell's
# goodput + migration count from the trace alone.  Same hard wall cap.
if [ -n "$CI_SMOKE_BENCHES" ]; then
    REMAIN_S=$(( CI_TIMEOUT_S - (SECONDS - START_S) ))
    if [ "$REMAIN_S" -lt 10 ]; then
        echo "ci: FAILED — no budget left for the replica-routing smoke" \
             "(${REMAIN_S}s of ${CI_TIMEOUT_S}s)" >&2
        exit 1
    fi
    mkdir -p results
    echo "ci: running replica-routing smoke (--replicas 2 --router" \
         "net-aware --trace results/ci_trace.json, ${REMAIN_S}s left)"
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout --signal=TERM --kill-after=15 "$REMAIN_S" \
        "$PYTHON" -m benchmarks.run --smoke --replicas 2 \
        --router net-aware --trace results/ci_trace.json \
        --bench serving_bench open_arrivals || rc=$?
    if [ $rc -eq 124 ]; then
        echo "ci: FAILED — replica-routing smoke exceeded the remaining" \
             "${REMAIN_S}s budget" >&2
    fi
    [ $rc -ne 0 ] && exit $rc
    # the trace must summarize standalone too (validates schema again)
    "$PYTHON" scripts/trace_report.py results/ci_trace.json > /dev/null \
        || { echo "ci: FAILED — trace_report.py rejected the CI trace" >&2
             exit 1; }
fi

# Multi-tenant fairness smoke (repro.sched.tenancy): the serving bench's
# noisy-neighbor cell with the drf router — one tenant floods at 4x its
# fair rate and the bench asserts every compliant (high-credit) tenant
# keeps >= 0.9 SLO attainment with SLO-good tokens within 10% of its
# isolated run, while aggregate goodput stays within 5% of the
# untenanted least-loaded baseline (emits BENCH_tenancy.json).  Running
# the whole bench under --router drf also proves the drf router
# UNTENANTED degrades to least-loaded (the route_ratio > 1 assertion in
# the net-contended cell).  Same hard wall cap.
if [ -n "$CI_SMOKE_BENCHES" ]; then
    REMAIN_S=$(( CI_TIMEOUT_S - (SECONDS - START_S) ))
    if [ "$REMAIN_S" -lt 10 ]; then
        echo "ci: FAILED — no budget left for the tenancy smoke" \
             "(${REMAIN_S}s of ${CI_TIMEOUT_S}s)" >&2
        exit 1
    fi
    echo "ci: running noisy-neighbor tenancy smoke (--replicas 2" \
         "--router drf, ${REMAIN_S}s left)"
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout --signal=TERM --kill-after=15 "$REMAIN_S" \
        "$PYTHON" -m benchmarks.run --smoke --replicas 2 \
        --router drf --bench serving_bench || rc=$?
    if [ $rc -eq 124 ]; then
        echo "ci: FAILED — the tenancy smoke exceeded the remaining" \
             "${REMAIN_S}s budget" >&2
    fi
    [ $rc -ne 0 ] && exit $rc
fi

# Elastic-runtime smoke (repro.sched.elastic): rigid vs elastic OURS
# under the same diurnal+failure stream on the simulator (strict: the
# spill-aware shrink admission beats binary admission on STP) and the
# same burst+failure request stream on the serving engine (strict:
# shallow shrunken joins + autoscale beat the rigid fleet on SLO
# goodput; the autoscaler must actually fire).  Emits
# BENCH_elastic.json.  Same hard wall cap.
if [ -n "$CI_SMOKE_BENCHES" ]; then
    REMAIN_S=$(( CI_TIMEOUT_S - (SECONDS - START_S) ))
    if [ "$REMAIN_S" -lt 10 ]; then
        echo "ci: FAILED — no budget left for the elastic smoke" \
             "(${REMAIN_S}s of ${CI_TIMEOUT_S}s)" >&2
        exit 1
    fi
    echo "ci: running elastic-runtime smoke (rigid vs elastic," \
         "${REMAIN_S}s left)"
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout --signal=TERM --kill-after=15 "$REMAIN_S" \
        "$PYTHON" -m benchmarks.run --smoke --bench elastic_bench \
        || rc=$?
    if [ $rc -eq 124 ]; then
        echo "ci: FAILED — the elastic smoke exceeded the remaining" \
             "${REMAIN_S}s budget" >&2
    fi
    [ $rc -ne 0 ] && exit $rc
fi
echo "ci: wall $((SECONDS - START_S))s of ${CI_TIMEOUT_S}s cap"
exit $rc
