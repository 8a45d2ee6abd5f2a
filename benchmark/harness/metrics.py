"""Metric arithmetic on the load generator's records.  Pure Python."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of the
    samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def in_window(records, t_open: float, t_close: float) -> list:
    """The requests that were due inside the window: what the tails and
    ``attempted`` are taken over."""
    return [r for r in records if t_open <= r["due"] < t_close]


def request_failed(r: dict) -> bool:
    """Refused, errored, cut short or never answered."""
    return (r.get("status") != 200 or r.get("error") is not None
            or r.get("done") is None
            or len(r.get("tokens", ())) != r["max_new"])


def ttft_ms(records, drain_limit_s: float) -> list[float]:
    """First token event at the client minus the time the request was DUE,
    one per request; a failed or unanswered one counts as the drain limit."""
    out = []
    for r in records:
        if request_failed(r) or not r["token_times"]:
            out.append(drain_limit_s * 1e3)
        else:
            out.append((r["token_times"][0] - r["due"]) * 1e3)
    return out


def inter_token_ms(records) -> list[float]:
    """All gaps between consecutive token events, pooled over requests."""
    out = []
    for r in records:
        tt = r["token_times"]
        out.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:]))
    return out


def tokens_in_window(records, t_open: float, t_close: float) -> int:
    """Token events received inside the window, whichever request they
    belong to (one started in the ramp still counts while it streams)."""
    return sum(1 for r in records for t in r["token_times"]
               if t_open <= t < t_close)


def in_flight(records, t: float) -> int:
    """Requests sent and not yet done at time ``t``."""
    return sum(1 for r in records if r["sent"] is not None
               and r["sent"] <= t and (r["done"] is None or r["done"] > t))


def lateness_ms(records) -> dict:
    """How late the generator ran: sent - due."""
    late = [(r["sent"] - r["due"]) * 1e3 for r in records
            if r.get("sent") is not None]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50_ms": percentile(late, 50),
            "max_ms": max(late)}
