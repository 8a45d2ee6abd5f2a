"""The reader of the lap clock's phases on hand-built ticks, and the ten
metrics it feeds in the last line of a dry run of each cell."""

import json
import os
import subprocess
import sys

import pytest

from harness import cells

B = cells.benchmark()
_, read = cells.layer_metric("step_host_ms.chat")


def tick(**phases):
    """phases: name=(wall_ms, cpu_ms); ``admit`` twice, as a step has it."""
    laps = [[n, w, c] for n, (w, c) in phases.items()]
    return {"ts": 0.0, "dur_ms": 1.0, "phases": laps + [["admit", 1.0, 1.0]]}


TICKS = [tick(claim=(1.5, 0.1), admit=(0.5, 0.5), plan=(2.0, 2.0),
              dispatch=(3.0, 3.0), device_wait=(50.0, 0.1),
              book=(1.0, 0.9), publish=(4.0, 1.0)),
         tick(claim=(2.5, 0.1), admit=(0.5, 0.5), plan=(4.0, 4.0),
              dispatch=(3.0, 3.0), device_wait=(70.0, 0.1),
              book=(1.0, 0.9)),
         tick(idle_wait=(900.0, 0.2), admit=(0.5, 0.5), plan=(12.0, 3.0),
              dispatch=(4.0, 3.0), device_wait=(51.0, 0.1),
              book=(1.0, 0.9))]


def run_of(ticks, ring_full=False):
    return {"window": {"ticks": ticks, "ring_full": ring_full}}


@pytest.mark.parametrize("params, want", [
    # admit counts both of its laps: 1.5 + plan + book
    ({"phases": ["admit", "plan", "book"], "stat": "mean", "clock": "wall"},
     (4.5 + 6.5 + 14.5) / 3),
    ({"phases": ["admit", "plan", "book"], "stat": "p50", "clock": "wall"},
     6.5),
    ({"phases": ["dispatch", "device_wait"], "stat": "p50",
      "clock": "wall"}, 55.0),
    # wall - CPU: the socket's block in claim, the GIL's in plan
    ({"phases": ["claim", "plan", "publish"], "stat": "mean",
      "clock": "offcpu"}, (1.4 + 3.0 + 2.4 + 9.0) / 3),
    # a phase no tick has reads 0, and idle_wait only where it is asked for
    ({"phases": ["flush"], "stat": "mean", "clock": "wall"}, 0.0),
    ({"phases": ["idle_wait"], "stat": "mean", "clock": "wall"}, 300.0),
])
def test_reader_on_hand_built_ticks(params, want):
    assert read(run_of(TICKS), params) == pytest.approx(want)


def test_none_without_phases_and_when_the_ring_wrapped():
    params = {"phases": ["plan"], "stat": "mean", "clock": "wall"}
    parent = [{"ts": 0.0, "dur_ms": 1.0}, {"ts": 1.0, "dur_ms": 1.0}]
    assert read(run_of(parent), params) is None
    assert read(run_of([]), params) is None
    assert read(run_of(TICKS, ring_full=True), params) is None
    # ticks without the field (the ring's older part) are left out
    assert read(run_of(parent + TICKS[:1]), params) == pytest.approx(2.0)


def test_the_ten_metrics_name_documented_phases():
    from analytics_zoo_tpu.serving.telemetry import PHASES

    mine = [m for m in B["per_layer"]
            if cells.layer_metric(m["name"])[0]["reader"] == "cycle_phases"]
    assert len(mine) == 10
    for m in mine:
        spec, _ = cells.layer_metric(m["name"])
        assert set(spec["params"]) == {"phases", "stat", "clock"}
        assert set(spec["params"]["phases"]) <= set(PHASES), m["name"]
        assert m["unit"] == "ms" and m["source"] == "program_span"
    offcpu = cells.layer_metric("pump_offcpu_ms.chat")[0]["params"]
    assert set(PHASES) - set(offcpu["phases"]) == {
        "dispatch", "device_wait", "idle_wait"}


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_a_dry_run_names_the_cells_metrics_in_its_last_line(cell):
    """The whole path on the CPU at toy sizes: the engine's flight records
    carry ``phases``, the harness hands them to the reader, and the last
    line (no result: a CPU run reports no time) names each metric."""
    out = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", cell, "--tiny", "--trace", "1", "--seed",
         str(2 ** 31 + 26), "--seconds", "6"],     # the toy mix is short
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    prefix = "[CPU dry run, not a result] "
    assert last.startswith(prefix)
    named = set(json.loads(last[len(prefix):])["metrics"])
    want = {m["name"] for m in B["per_layer"]
            if cell in m["workloads"]
            and cells.layer_metric(m["name"])[0]["reader"] == "cycle_phases"}
    assert len(want) == 5 and want <= named, want - named
