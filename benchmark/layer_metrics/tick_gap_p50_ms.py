"""Median time the host takes between two engine ticks: from the end of one
``engine.step`` (flight ring ``ts`` + ``dur_ms``) to the start of the next.
The pump loop, admission and the fan-out of the tick's tokens to the broker
and the streaming responses happen there, with the device idle; every token
gap a client sees is one tick plus one of these."""

from statistics import median


def read(run, params):
    w = run["window"]
    ticks = sorted(w["ticks"], key=lambda t: t["ts"])
    if len(ticks) < 2 or w["ring_full"]:
        return None
    return median((b["ts"] - a["ts"]) * 1e3 - a["dur_ms"]
                  for a, b in zip(ticks, ticks[1:]))
