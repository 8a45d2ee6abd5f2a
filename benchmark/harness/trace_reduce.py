"""From a profiler trace to device busy/idle time, the heaviest device
operations and the longest idle gaps.

Two stages, so the arithmetic can be checked on a small recorded trace
without the profiler:

1. ``read_xplane(path)`` — the profiler's ``.xplane.pb`` to plain lists,
   with nothing but JAX: ``{"devices": {plane: [(name, start_ns, dur_ns),
   ...]}, "marks": {name: start_ns}}``.  A device plane is one named
   ``/device:TPU:<n>``; its operations are the events of its ``XLA Ops``
   line (the other lines — steps, modules, framework scopes — cover the
   same time again and would double it).  ``marks`` are the host-side
   ``TraceAnnotation`` events whose name starts with ``bench_``: the
   harness emits one at a known ``time.monotonic()`` so that device time
   can be laid beside the flight ring's ticks.
2. ``reduce(...)`` — pure Python on those lists.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
MARK_PREFIX = "bench_"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """The trace names a device operation by its whole HLO line
    (``%copy.552 = bf16[16,2257,...] copy(...)``): keep the name before
    the ``=``, without the ``%``."""
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, marks, layout = {}, {}, {}
    for plane in data.planes:
        lines = list(plane.lines)
        layout[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith(DEVICE_PREFIX):
            for ln in lines:
                if ln.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)) for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(MARK_PREFIX):
                        marks.setdefault(ev.name, int(ev.start_ns))
    return {"devices": devices, "marks": marks, "layout": layout}


def host_as_device(path: str) -> dict:
    """CPU dry run only: the busiest host line stands in for a device
    plane, so the rest of a traced run can be rehearsed."""
    from jax.profiler import ProfileData

    best = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in ln.events
                   if not ev.name.startswith(MARK_PREFIX)]
            if len(evs) > len(best):
                best = evs
    return {"cpu-dry-run": best} if best else {}


def busy_ns(events) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start >= end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def idle_gaps(events, lo: int, hi: int) -> list:
    """(start_ns, dur_ns) of every stretch of [lo, hi) with no operation."""
    gaps, end = [], lo
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            gaps.append((end, min(start, hi) - end))
        end = max(end, start + dur)
        if end >= hi:
            break
    if end < hi:
        gaps.append((end, hi - end))
    return [g for g in gaps if g[1] > 0]


def clip(events, lo: int, hi: int) -> list:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce(trace: dict, *, lo_ns: int | None = None,
           hi_ns: int | None = None, ticks: list | None = None,
           mark_monotonic: dict | None = None, top: int = 10) -> dict:
    """``busy_s`` and ``window_s`` averaged over the device planes, the
    ``top`` operations by total time and the longest idle gaps by what the
    host was doing.

    The window is [lo_ns, hi_ns) in the trace's own clock; left out, it is
    first operation start to last operation end.  ``ticks`` are flight-ring
    records (``ts`` seconds on ``time.monotonic()``, ``dur_ms``);
    ``mark_monotonic`` maps a mark's name to the monotonic second at which
    the harness emitted it, which ties the two clocks.  A gap that lies
    inside a tick is the host's work inside ``engine.step`` (plan, H2D,
    ``device_get``, token bookkeeping); one between ticks is the pump loop
    and admission; without ticks or a mark it is ``unattributed``."""
    planes = trace["devices"]
    if not planes:
        raise ValueError("the trace holds no device operations "
                         f"(planes: {trace.get('layout')})")
    every = [e for evs in planes.values() for e in evs]
    lo = min(e[1] for e in every) if lo_ns is None else lo_ns
    hi = max(e[1] + e[2] for e in every) if hi_ns is None else hi_ns
    busy, by_name, gaps = [], {}, []
    for evs in planes.values():
        evs = clip(evs, lo, hi)
        busy.append(busy_ns(evs))
        for name, _, dur in evs:
            by_name[name] = by_name.get(name, 0) + dur
        gaps.extend(idle_gaps(evs, lo, hi))
    n = len(planes)
    offset = None       # trace ns = monotonic s * 1e9 + offset
    for name, mono in (mark_monotonic or {}).items():
        if name in trace.get("marks", {}):
            offset = trace["marks"][name] - int(mono * 1e9)
            break
    spans = []
    if ticks and offset is not None:
        for t in ticks:
            a = int(t["ts"] * 1e9) + offset
            spans.append((a, a + int(t["dur_ms"] * 1e6),
                          "chunk" if t.get("chunks") else "decode"))
        spans.sort()
    by_what = {}
    for start, dur in gaps:
        what = "unattributed"
        if spans:
            mid = start + dur // 2
            what = "between ticks: pump loop and admission"
            for a, b, kind in spans:
                if a <= mid < b:
                    what = f"inside a {kind} tick: host work in step"
                    break
                if a > mid:
                    break
        by_what[what] = by_what.get(what, 0) + dur
    rank = lambda d: [[k, v / 1e9 / n] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": rank(by_name), "idle_gaps": rank(by_what),
            "longest_gap_s": max((g[1] for g in gaps), default=0) / 1e9,
            "by_name_s": {k: v / 1e9 / n for k, v in by_name.items()},
            "clock_tied": offset is not None}
