"""One general traffic generator: a mix is a data file, a schedule is that
mix drawn from a seed.

Every seed gets the SAME set of request sizes and (open loop) the same set
of arrival gaps, in another order: sizes are the mix's distribution taken at
evenly spaced quantiles, not sampled, so the amount of work in a run does
not change with the seed.  An open loop draws its ramp and its window apart,
so that holds for the requests due in the window, not only for both together.  Only the order, the pairing of a prompt with its
place in time, and the token ids are the seed's.

Stdlib + numpy (token ids only); the load generator child reads the file
this writes and never imports this module.
"""

from __future__ import annotations

import json
import math
import random
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of the distribution."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    inv = NormalDist().inv_cdf
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * inv((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def quantile_gaps(rate: float, n: int) -> list[float]:
    """``n`` exponential inter-arrival gaps at evenly spaced quantiles."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def _sizes(traffic: dict, n: int) -> list[tuple[int, int]]:
    """The mix's (prompt length, max_new) pairs — the same list for every
    seed: the pairing is shuffled by a constant, not by the seed."""
    plens = quantile_lengths(traffic["prompt_len"], n)
    news = quantile_lengths(traffic["max_new"], n)
    random.Random(f"pairing-{n}").shuffle(news)
    return list(zip(plens, news))


def make(traffic: dict, *, seed: int, seconds: float, vocab: int,
         rate_rps: float | None = None) -> list[dict]:
    """The schedule: one dict per request with ``id``, ``tokens``,
    ``max_new`` and either ``due`` (open loop: seconds after the start of
    the ramp) or ``client`` + ``seq`` (closed loop)."""
    rng = random.Random(int(seed))
    ramp = float(traffic["ramp_s"])
    if traffic["loop"] == "open":
        if not rate_rps:
            raise ValueError("an open-loop mix needs the cell's rate_rps")
        # the ramp and the window are drawn apart, so that the requests
        # DUE IN THE WINDOW are the same set of sizes and gaps for every
        # seed (a shuffle over both would let the seed choose which long
        # prompts the window gets, and a tail follows them)
        rows = []
        for start, span in ((0.0, ramp), (ramp, float(seconds))):
            n = int(rate_rps * span)
            sizes = _sizes(traffic, n)
            rng.shuffle(sizes)
            gaps = quantile_gaps(rate_rps, n)
            rng.shuffle(gaps)
            t = start       # sum(gaps) < n / rate <= span: all inside
            for (plen, mn), g in zip(sizes, gaps):
                t += g
                rows.append({"due": round(t, 6), "plen": plen,
                             "max_new": mn})
    elif traffic["loop"] == "closed":
        clients, per = int(traffic["clients"]), int(traffic["per_client"])
        sizes = _sizes(traffic, clients * per)
        rng.shuffle(sizes)
        rows = [{"client": i % clients, "seq": i // clients, "plen": plen,
                 "max_new": mn} for i, (plen, mn) in enumerate(sizes)]
    else:
        raise ValueError(f"unknown loop kind {traffic['loop']!r}")
    ids = np.random.default_rng(int(seed)).integers(
        1, vocab, size=sum(r["plen"] for r in rows), dtype=np.int64)
    at = 0
    for i, r in enumerate(rows):
        plen = r.pop("plen")
        r["id"] = f"s{int(seed)}r{i:05d}"
        r["tokens"] = ids[at:at + plen].tolist()
        at += plen
    return rows


def write(rows: list[dict], path: str) -> None:
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r, separators=(",", ":")) + "\n")
