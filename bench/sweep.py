"""Find a cell's knee: serve its traffic at several fixed rates in one
process and print what each rate sustained.

    python3 bench/sweep.py --workload qwen3-0.6b.chat --seed 5 \
        --seconds 40 --rates 0.1,0.2,0.3

Each rate gets one window on the wall clock, cut at the close (no
drain, no output check); the backend is built and warmed once.  One
JSON line per rate goes to standard output: the end-to-end numbers, the
output tokens offered per second (the due requests' answers over the
window), the page pool's use (pages holding KV and pages reserved, at
their peak over the device calls, and the mean), rows per decode call,
the backlog at the close (requests due with no first token;
``backlog_due_early``: those due in the window's first 60%) and the
median time to first token of the requests due in each half.  The
last line gives the knee (``knee``): below it the share of offered
tokens that the window serves holds steady, above it the steps slow,
the queue grows and that share falls; and four fifths of it, the rate
of a cell whose tails are judged.  Needs the chip,
like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


class _Closed(Exception):
    pass


#: a rate is sustained while the share of offered tokens served in the
#: window stays above this share of the lowest rate's, and no request
#: due in the window's first 60% is still waiting at the close
SUSTAINED = 0.85


def knee(rows) -> float:
    """The highest rate sustained, from one window per rate: the last
    sustained rate, or where the served share falls through
    ``SUSTAINED`` of the lowest rate's on the way to the first rate
    that is not sustained (linear between the two)."""
    rows = sorted(rows, key=lambda r: r["rate"])
    share = [r["output_tokens_per_s"] / r["offered_tokens_per_s"]
             for r in rows]
    floor = SUSTAINED * share[0]
    best = rows[0]["rate"]
    for i in range(1, len(rows)):
        if share[i] >= floor and rows[i]["backlog_due_early"] == 0:
            best = rows[i]["rate"]
            continue
        a, b = share[i - 1], share[i]
        if b < floor < a:
            best += (rows[i]["rate"] - best) * (a - floor) / (a - b)
        break
    return best


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from harness import process
    process.prepare(ROOT)
    from harness import e2e, runner, spec, traffic
    from harness.serving import CallLog, Clock
    from repro.serve import Request
    cell = spec.load_cell(args.workload)
    devices, _ = runner.require_chip(cell.chips)
    served = runner.build(cell, args.seed, devices[0])
    runner.warm_up(served)
    swept = []
    for rate in [float(r) for r in args.rates.split(",")]:
        planned = traffic.plan(cell.traffic, rate, args.seconds, args.seed,
                               int(cell.config["vocab_size"]))
        requests = [Request(rid=p.rid, prompt_len=len(p.prompt),
                            max_new_tokens=p.max_new, arrival=p.due,
                            prompt=p.prompt.tolist()) for p in planned]

        def close(now):
            if now >= args.seconds:
                raise _Closed

        clock, log = Clock(), CallLog()
        engine = served.engine(requests, clock, on_step=close, log=log)
        served.backend.log, served.backend.clock = log, clock
        clock.t0 = time.perf_counter()
        try:
            engine.run()
        except _Closed:
            pass
        served.backend.remove(list(served.backend._slots))
        nums = e2e.window_numbers(
            [(p.due, log.tokens.get(p.rid, [])) for p in planned],
            args.seconds)
        first = {p.rid: min(log.tokens.get(p.rid, [args.seconds])
                            + [args.seconds]) for p in planned}
        waits = [(p.due, first[p.rid] - p.due) for p in planned]
        backlog = sum(1 for p in planned if first[p.rid] >= args.seconds)
        early = sum(1 for d, w in waits
                    if d < 0.6 * args.seconds and d + w >= args.seconds)
        half = [[w for d, w in waits if (d < args.seconds / 2) == h]
                for h in (True, False)]
        offered = sum(p.max_new for p in planned) / args.seconds
        pool = [c for c in log.calls if "pages" in c]
        rows = [len(c["contexts"]) for c in log.calls
                if c["kind"] == "decode"]
        row = dict(rate=rate, requests=len(planned), backlog=backlog,
                   pool_pages=pool[0]["pool"] if pool else None,
                   pages_peak=max((c["pages"] for c in pool), default=0),
                   reserved_peak=max((c["reserved"] for c in pool),
                                     default=0),
                   pages_mean=float(np.mean([c["pages"] for c in pool]))
                   if pool else 0.0,
                   rows_per_decode_mean=float(np.mean(rows)) if rows
                   else 0.0,
                   backlog_due_early=early,
                   ttft_p50_first_half_s=float(np.median(half[0])),
                   ttft_p50_second_half_s=float(np.median(half[1]))
                   if half[1] else None,
                   offered_tokens_per_s=offered, **nums)
        swept.append(row)
        print(json.dumps(row), flush=True)
        print(f"rate {rate}/s: {len(planned)} due, backlog {backlog}, "
              f"tokens/s {nums['output_tokens_per_s']:.1f} of "
              f"{offered:.1f} offered, ttft p50/p95 "
              f"{nums['ttft_p50_s']:.2f}/{nums['ttft_p95_s']:.2f} s, gap "
              f"p50/p95 {nums['token_gap_p50_ms']:.0f}/"
              f"{nums['token_gap_p95_ms']:.0f} ms", file=sys.stderr,
              flush=True)
    k = knee(swept)
    print(json.dumps({"knee": k, "four_fifths": round(0.8 * k, 3)}),
          flush=True)


if __name__ == "__main__":
    main()
