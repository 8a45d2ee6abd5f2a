"""The whole model step's share of the chip's peak FLOP/s.

Numerator: the model's mathematics for every prompt and output token whose
result reached a client inside the window, from the configuration's shapes
(the cell's family counts them: families/<family>/counts.py): a prompt of P
tokens counts its P trunk passes and one head when its first token arrives
in the window; output token i (i >= 1)
counts one trunk pass attending P + i positions and one head when it
arrives in the window.  Recomputation after a preemption is not counted.
Denominator: the summed durations of the window's engine ticks x the peak
(harness/peaks.json) — the time the engine spent stepping."""

from harness import cells, flops


def read(run, params):
    w, cfg = run["window"], run["cfg"]
    counts = cells.family(cfg).counts
    ticks = w["ticks"]
    if not ticks or w["ring_full"]:
        return None
    lo, hi = w["t_open"], w["t_close"]
    head = counts.head_flops(cfg)
    total = 0.0
    for r in w["records"]:
        plen = len(r["prompt"])
        for i, t in enumerate(r["token_times"]):
            if lo <= t < hi:
                total += head + (counts.span_flops(cfg, 0, plen) if i == 0
                                 else counts.token_flops(cfg, plen + i))
    step_s = sum(t["dur_ms"] for t in ticks) / 1e3
    peak = flops.peak(run["device"]["kind"])["flops_per_s_bf16"]
    return 100.0 * total / (step_s * peak)
