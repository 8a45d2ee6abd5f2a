"""CPU ms a cycle that the serving process's fan-out threads ran: mean over
the window's ticks of ``fanout_broker_cpu_ms + fanout_door_cpu_ms`` (flight
ring; the broker's connection threads and the front door's stream writers,
``telemetry.FanoutClock``, docs/observability.md).  It is thread CPU, not
time the threads held the GIL: the kernel's socket work counts too, and the
broker's count takes in its connections that carry no stream (the pump's
own commands, the claims).  None when the ring wrapped or no tick carries
the fields (a program from before them)."""

from statistics import mean


def read(run, params):
    w = run["window"]
    ticks = [t for t in w["ticks"] if "fanout_broker_cpu_ms" in t]
    if not ticks or w["ring_full"]:
        return None
    return mean(t["fanout_broker_cpu_ms"] + t["fanout_door_cpu_ms"]
                for t in ticks)
