"""Median duration of the window's engine ticks (flight ring ``dur_ms``).
A tick is one ``engine.step``: plan, one device call, ``device_get``, token
bookkeeping — a completed step on the host clock."""

from statistics import median


def read(run, params):
    ticks = run["window"]["ticks"]
    if not ticks or run["window"]["ring_full"]:
        return None
    return median(t["dur_ms"] for t in ticks)
