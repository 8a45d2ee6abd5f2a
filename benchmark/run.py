#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process: needs a TPU with the cell's chips (else a non-zero exit and
no result line), reads the cell's data files, builds the model from the
configuration with weights made on the device from --seed, starts the serving
stack the way a user does, warms every program the traffic can reach, ramps
the traffic up, measures for --seconds, drains, decides ``correct`` against
the plain float32 reference, and prints ONE JSON object as its last stdout
line: correct, attempted, failed, metrics, device (+ breakdown with
--trace 1), and last the numbers compared beside their limits.

--trace 0 reports the cell's end-to-end metrics, --trace 1 its per-layer
metrics (profiler on for a few seconds of the window).  --tiny is the CPU
rehearsal: toy sizes, every line says so, and the last line does not parse
as a result.  --control puts the control in the program's place: after a
run like any other, the tokens that the reference in fp8 puts first stand
where the served tokens stood, and ``correct`` has to come out false (the
program's own reading goes to ``info``; how the limits were set, and never
part of a check).
--keep-records / --keep-trace copy the load generator's records / a summary
of the raw trace out of the run's temporary directory, for a look by hand.
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
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="copy a summary of the raw trace here (a look "
                         "at plane, line and operation names)")
    ap.add_argument("--keep-records", default=None,
                    help="copy the load generator's records here")
    args = ap.parse_args()
    from harness import cells, trace_reduce
    from harness import metrics as M
    from harness.runner import (Runner, end_to_end, process_env,
                                tick_summary)

    process_env(args.tiny)

    cell = cells.Cell(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(cell.run_seconds)
    run = Runner(cell, seed=args.seed, tiny=args.tiny, t_proc0=T_PROC0)
    try:
        run.setup()
        w = run.window(seconds, trace=bool(args.trace))
        run.say(f"window {seconds:g} s: {len(w['in_window'])} requests due "
                f"in it, generator {w['generator']}, sent - due "
                f"{M.lateness_ms(w['in_window'])}")
        run.say("requests in flight at the window's open, middle and "
                "close: " + ", ".join(str(M.in_flight(w["records"], t))
                                      for t in (w["t_open"], (w["t_open"]
                                                + w["t_close"]) / 2,
                                                w["t_close"])))
        run.say(f"ticks in the window: {json.dumps(tick_summary(w))}")
        fault = None
        if w["generator"].get("clients_out_of_requests"):
            fault = ("FAULT of the mix's sizing: clients ran out of "
                     "requests before the window closed; raise per_client")
        bad = w["compiled_in_window"]
        if bad["compiled"] or bad["loaded"] or bad["programs"]:
            fault = (f"FAULT of the warm-up: programs compiled or loaded "
                     f"inside the window: {bad}")
        memory_peak = run.memory_peak_bytes()
        if args.keep_records:
            os.makedirs(os.path.dirname(args.keep_records) or ".",
                        exist_ok=True)
            with open(args.keep_records, "w") as f:
                for r in w["records"]:
                    f.write(json.dumps({k: v for k, v in r.items()
                                        if k != "prompt"}) + "\n")
        ctx = {"cfg": run.cfg, "traffic": run.traffic,
               "workload": run.workload, "window": w,
               "device": run.device}
        if args.trace:
            xplane = trace_reduce.find_xplane(w["trace_dir"])
            raw = trace_reduce.read_xplane(xplane)
            m = raw["marks"]
            if args.tiny and not raw["devices"]:
                run.say(f"no device plane in a CPU trace (planes "
                        f"{list(raw['layout'])}, marks {list(m)}): the "
                        f"dry run reduces the host's first line instead")
                raw["devices"] = trace_reduce.host_as_device(xplane)
            ctx["trace"] = trace_reduce.reduce(
                raw, lo_ns=m.get("bench_mark_open"),
                hi_ns=m.get("bench_mark_close"), ticks=w["ticks"],
                mark_monotonic=w["marks"])
            if args.keep_trace:
                os.makedirs(os.path.dirname(args.keep_trace) or ".",
                            exist_ok=True)
                with open(args.keep_trace, "w") as f:
                    json.dump({"layout": raw["layout"],
                               "marks": raw["marks"],
                               "reduced": ctx["trace"],
                               "head": {p: evs[:400] for p, evs in
                                        raw["devices"].items()}}, f)
        run.stop()
        verdict = run.check(w, control=args.control)
    finally:
        run.stop()
        run.cleanup()

    wrecs = w["in_window"]
    line = {"correct": verdict["correct"], "attempted": len(wrecs),
            "failed": sum(M.request_failed(r) for r in wrecs),
            "metrics": {}, "device": dict(
                run.device, memory_peak_bytes=memory_peak)}
    e2e = end_to_end(w, run.traffic)
    if not args.tiny:
        run.say(f"end to end, every metric the harness knows: "
                f"{json.dumps({k: v[0] for k, v in e2e.items()})}")
    if args.trace:
        line["device"]["busy_s"] = ctx["trace"]["busy_s"]
        line["device"]["window_s"] = ctx["trace"]["window_s"]
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
        for spec in cell.per_layer:
            file_spec, read = cells.layer_metric(spec["name"])
            try:
                value = read(ctx, file_spec.get("params", {}))
            except KeyError as e:
                if not args.tiny:
                    raise
                run.say(f"{spec['name']}: not read in the dry run ({e})")
                value = None
            if value is not None:
                line["metrics"][spec["name"]] = {
                    "value": float(value), "unit": spec["unit"]}
    else:
        for spec in cell.end_to_end:
            if spec["name"] in e2e:
                value, unit = e2e[spec["name"]]
                line["metrics"][spec["name"]] = {"value": value,
                                                 "unit": unit}
    if args.tiny:       # a CPU run reports counts and correctness, no rate
        line["metrics"] = {k: {"value": None, "unit": v["unit"]}
                           for k, v in line["metrics"].items()}
    line["info"] = verdict["info"]
    line["compared"] = verdict["compared"]
    run.say(f"correct: {verdict['correct']}; each number compared, "
            f"beside its limit:")
    for name, c in verdict["compared"].items():
        run.say(f"compared {name}: {c['value']} (limit {c['limit']})")
    out = json.dumps(line)
    if fault:       # no result line: the numbers stand for nothing
        run.say(f"NOT A RESULT {out}")
        run.say(fault)
        return 3
    print(f"[CPU dry run, not a result] {out}" if args.tiny else out,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
