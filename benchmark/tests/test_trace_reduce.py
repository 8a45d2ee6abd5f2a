"""The reduction from a trace to busy/idle time, top operations and gaps:
by hand on a made-up trace, and on a small trace recorded on the chip."""

import json
import os

import pytest

from harness import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))


def test_busy_is_the_union_of_intervals():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2)]
    assert T.busy_ns(evs) == 15 + 5
    assert T.idle_gaps(evs, 0, 40) == [(15, 15), (35, 5)]
    assert T.idle_gaps(evs, 10, 33) == [(15, 15)]
    assert T.clip(evs, 8, 31) == [("a", 8, 2), ("b", 8, 7), ("c", 30, 1)]


def test_reduce_by_hand_with_ticks_on_the_host_clock():
    # device ops in trace ns; the mark ties trace 1000 ns to monotonic 5.0 s
    trace = {"devices": {"/device:TPU:0": [
        ("fusion.1", 1000, 400), ("custom-call.7", 1500, 300),
        ("fusion.1", 2600, 400)]},
        "marks": {"bench_mark_open": 1000, "bench_mark_close": 3000}}
    ticks = [{"ts": 5.0, "dur_ms": 0.0009, "chunks": 0},      # 1000..1900
             {"ts": 5.0000015, "dur_ms": 0.0006, "chunks": 1}]  # 2500..3100
    r = T.reduce(trace, lo_ns=1000, hi_ns=3000, ticks=ticks,
                 mark_monotonic={"bench_mark_open": 5.0})
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx(1100e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(800e-9)]
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 1400..1500 inside the decode tick, 1800..2600 mostly between ticks
    assert gaps["inside a decode tick: host work in step"] == \
        pytest.approx(100e-9)
    assert gaps["between ticks: pump loop and admission"] == \
        pytest.approx(800e-9)
    assert r["clock_tied"]
    # without a mark nothing is attributed
    r = T.reduce(trace, lo_ns=1000, hi_ns=3000, ticks=ticks)
    assert [g[0] for g in r["idle_gaps"]] == ["unattributed"]


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        T.reduce({"devices": {}, "marks": {}})


def test_recorded_chip_trace():
    """400 consecutive device operations of a traced run on the TPU v5e
    (this PR's chip run), kept as plain lists."""
    path = os.path.join(HERE, "data", "trace_v5e_head.json")
    with open(path) as f:
        rec = json.load(f)
    (plane, evs), = rec["devices"].items()
    assert plane.startswith(T.DEVICE_PREFIX)
    trace = {"devices": {plane: [tuple(e) for e in evs]}, "marks": {}}
    r = T.reduce(trace)
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(rec["expect"]["window_s"],
                                          rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"][0][0] == rec["expect"]["top_op"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
