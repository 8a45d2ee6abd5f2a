"""Share of the token-stream events that left the pump while a device call
was in flight: 100 x sum of ``flush_events_overlapped`` / sum of
``flush_events`` over the window's ticks (flight ring; the pump's token
flush counts both, docs/observability.md).  Near 100 when a tick's tokens
are written to the broker under the next step's device call
(``engine.after_dispatch``), 0 when they are flushed between two steps.
None where no tick carries the counters (a program from before them, an
engine no pump drives), where the window sent no event, or where the ring
wrapped."""


def read(run, params):
    ticks = [t for t in run["window"]["ticks"] if "flush_events" in t]
    sent = sum(t["flush_events"] for t in ticks)
    if not sent or run["window"]["ring_full"]:
        return None
    return 100.0 * sum(t["flush_events_overlapped"] for t in ticks) / sent
