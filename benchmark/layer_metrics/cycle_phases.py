"""Time per engine cycle spent in named phases of the pump thread's lap
clock (flight ring ``phases``: ``[name, wall_ms, cpu_ms]`` laps that cover
the cycle from the end of one ``engine.step`` to the end of the next with
no remainder; docs/observability.md has the catalog).

params: ``phases`` the names to sum, ``clock`` ``wall`` (wall ms) or
``offcpu`` (wall - CPU ms: the thread was blocked on a socket or waited for
the GIL), ``stat`` ``mean`` or ``p50`` over the window's ticks.  None when
the ring wrapped or no tick carries ``phases`` (a program without the
clock)."""

from statistics import mean, median

STATS = {"mean": mean, "p50": median}
CLOCKS = {"wall": lambda wall, cpu: wall,
          "offcpu": lambda wall, cpu: wall - cpu}


def read(run, params):
    w = run["window"]
    ticks = [t for t in w["ticks"] if t.get("phases")]
    if not ticks or w["ring_full"]:
        return None
    names, took = set(params["phases"]), CLOCKS[params["clock"]]
    return STATS[params["stat"]](
        sum(took(wall, cpu) for name, wall, cpu in t["phases"]
            if name in names)
        for t in ticks)
