#!/usr/bin/env python3
"""Find an open-loop cell's knee: one process, one set-up, one window per
offered rate, each after the mix's own ramp.

    python3 benchmark/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 45

For each rate it prints: requests due, failed, the tokens per second offered
(the ``max_new`` of the requests due in the window over its seconds) and
delivered (token events inside it), requests in flight at the window's open,
middle and close, the engine's mean queue depth in the window's second and
last quarter, how late the generator ran, and the tails.  The knee is the
rate at which delivered stops tracking offered: the saturated runs' delivered
tokens per second over the mix's mean ``max_new``.  Requests in flight say
nothing of it unless ramp and window are each longer than a request lives:
a shorter window measures the engine filling up (PR 25's first sweep did).
The cell's fixed rate, 0.8 x the knee, is then written by hand into
workloads/<cell>.json, and the table into PERF.md.  Not part of a check: the
benchmark offers a fixed rate.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from harness import cells
    from harness import metrics as M
    from harness.runner import (Runner, end_to_end, process_env,
                                tick_summary)

    process_env(args.tiny)

    cell = cells.Cell(args.workload)
    run = Runner(cell, seed=args.seed, tiny=args.tiny, t_proc0=T_PROC0)
    if run.traffic["loop"] != "open":
        raise SystemExit("only an open-loop cell has a knee to find")
    table = []
    try:
        run.setup()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            run.seed = args.seed + i
            w = run.window(args.seconds, trace=False, rate_rps=rate)
            wrecs, mid = w["in_window"], w["t_open"] + args.seconds / 2
            q = lambda lo, hi: [t["queue_depth"] for t in w["ticks"]
                                if lo <= t["ts"] - w["t_open"] < hi]
            mean = lambda xs: sum(xs) / len(xs) if xs else None
            e2e = end_to_end(w, run.traffic)
            row = {"rate_rps": rate, "due": len(wrecs),
                   "failed": sum(M.request_failed(r) for r in wrecs),
                   "offered_tokens_per_s": sum(
                       r["max_new"] for r in wrecs) / args.seconds,
                   "in_flight_open": M.in_flight(w["records"], w["t_open"]),
                   "in_flight_mid": M.in_flight(w["records"], mid),
                   "in_flight_end": M.in_flight(w["records"], w["t_close"]),
                   "queue_q2": mean(q(args.seconds / 4, args.seconds / 2)),
                   "queue_q4": mean(q(3 * args.seconds / 4, args.seconds)),
                   "late": M.lateness_ms(wrecs),
                   "compiled": w["compiled_in_window"]["compiled"],
                   "tokens_per_s": e2e["output_tokens_per_s"][0],
                   **{k: e2e[k][0] for k in ("ttft_mean_ms", "ttft_p95_ms",
                                            "itl_p95_ms")
                      if k in e2e},
                   "ticks": tick_summary(w)}
            table.append(row)
            run.say(f"sweep {json.dumps(row)}")
    finally:
        run.stop()
        run.cleanup()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": run.device,
                       "seconds": args.seconds, "table": table}, f,
                      indent=1)
    tag = "[CPU dry run, not a result] " if args.tiny else ""
    print(tag + json.dumps({"workload": args.workload, "table": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
