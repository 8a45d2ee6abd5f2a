"""Fan-out thread wake-ups per event written to a client: sum of
``fanout_wakes`` / sum of ``fanout_events`` over the window's ticks (flight
ring, ``telemetry.FanoutClock``).  A wake is a return from a blocking wait
that brought work: a broker ``recv`` that returned bytes, a blocked
``XREAD`` woken by an ``XADD``, a front-door stream read that returned
entries (docs/observability.md).  None when the ring wrapped, no tick
carries the fields (a program from before them) or no event was written."""


def read(run, params):
    w = run["window"]
    ticks = [t for t in w["ticks"] if "fanout_wakes" in t]
    events = sum(t["fanout_events"] for t in ticks)
    if not events or w["ring_full"]:
        return None
    return sum(t["fanout_wakes"] for t in ticks) / events
