"""The fan-out's mean delay on a token, ms: sum of ``fanout_lag_ms_sum`` /
sum of ``fanout_lag_n`` over the window's ticks (flight ring,
``telemetry.FanoutClock``): from the pump's flush stamp, read once a flush
as it hands the tokens to the broker, to the front door's write of the
token on the client's socket, both on ``time.monotonic()``.  None when the
ring wrapped, no tick carries the fields (a program from before them) or no
stamped token was written."""


def read(run, params):
    w = run["window"]
    ticks = [t for t in w["ticks"] if "fanout_lag_n" in t]
    n = sum(t["fanout_lag_n"] for t in ticks)
    if not n or w["ring_full"]:
        return None
    return sum(t["fanout_lag_ms_sum"] for t in ticks) / n
