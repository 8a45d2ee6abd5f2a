"""The benchmark's own contract tests, inside tier-1: breaking the family
contract (``benchmark/README.md``: what a family's four modules give the
harness), a file that ``BENCHMARK.json`` names, or the table of counts
fails here and not first on the chip.  Each file of ``benchmark/tests`` runs
in a process of its own, as ``python -m pytest benchmark/tests`` runs them:
they import ``harness`` from ``benchmark/``, which is not on this suite's
path."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name", ["test_files.py", "test_flops.py",
                                  "test_family.py"])
def test_benchmark_contract(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         os.path.join("benchmark", "tests", name)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# flush_overlap_pct.*: the reader of the pump's two flush counters
# ---------------------------------------------------------------------------

def _flush_overlap_read():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "flush_overlap_pct", os.path.join(
            ROOT, "benchmark", "layer_metrics", "flush_overlap_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run_of(ticks, ring_full=False):
    return {"window": {"ticks": ticks, "ring_full": ring_full}}


@pytest.mark.parametrize("ticks, ring_full, want", [
    # 30 of 32, 31 of 31, and a tick whose events left at the end of a pass
    ([{"flush_events": 32, "flush_events_overlapped": 30},
      {"flush_events": 31, "flush_events_overlapped": 31},
      {"flush_events": 2, "flush_events_overlapped": 0}], False,
     100.0 * 61 / 65),
    # ticks of a program from before the counters are passed over
    ([{"dur_ms": 1.0}, {"flush_events": 4, "flush_events_overlapped": 1}],
     False, 25.0),
    # the parent: no tick carries them
    ([{"dur_ms": 1.0}, {"dur_ms": 2.0}], False, None),
    # a window in which nothing streamed; a ring that wrapped
    ([{"flush_events": 0, "flush_events_overlapped": 0}], False, None),
    ([{"flush_events": 8, "flush_events_overlapped": 8}], True, None),
    ([], False, None),
])
def test_flush_overlap_reader(ticks, ring_full, want):
    got = _flush_overlap_read()(_run_of(ticks, ring_full), {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_flush_overlap_metric_sits_where_pump_broker_ms_does():
    """One metric per mix, beside ``pump_broker_ms.*``: same layer, same
    end-to-end metric moved, same cells."""
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for mix in ("chat", "batch", "longdoc"):
        m, ref = (per_layer[f"flush_overlap_pct.{mix}"],
                  per_layer[f"pump_broker_ms.{mix}"])
        assert (m["unit"], m["better"], m["source"]) == \
            ("%", "higher", "program_counter")
        assert [m[k] for k in ("layer", "moves", "workloads")] == \
            [ref[k] for k in ("layer", "moves", "workloads")]
