"""The door is open: a second model family lands as NEW files and entries.

In a temporary copy of ``benchmark/`` and ``BENCHMARK.json`` the test adds
the toy family kept under ``tests/data/door/`` (its builder, leaves,
reference and counts; a configuration with a list-valued key read by layer
index; a traffic mix; a cell; two per-layer metrics) and the entries of
``tests/data/door/entries.json``, asserts by hash that no file that was
there changed, and drives ``run.py --tiny`` on the new cell on the CPU: once
to ``correct`` true, once with ``--control`` to false.  Both runs go side by
side (each compiles ~60 small programs, about a minute).
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import cells

DOOR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "door")
SKIP = shutil.ignore_patterns("__pycache__", ".pytest_cache", "tests")


def _hashes(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """(root of the copy, the files the door added)."""
    root = str(tmp_path_factory.mktemp("door"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(cells.BENCH_DIR, bench, ignore=SKIP)
    before = _hashes(bench)
    added = []
    for d, _, files in os.walk(DOOR):
        for f in files:
            if f == "entries.json" or f.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(d, f), DOOR)
            dst = os.path.join(bench, rel)
            assert not os.path.exists(dst), f"{rel} is already there"
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(os.path.join(d, f), dst)
            added.append(rel)
    after = _hashes(bench)
    assert {k: after[k] for k in before} == before, \
        "the door changed a file that was there"
    assert sorted(set(after) - set(before)) == sorted(added)
    # entries only: nothing that BENCHMARK.json had is changed or removed,
    # but for the new cell's name in the list of a metric it reports
    b = cells.benchmark()
    entries = cells.load_json(os.path.join(DOOR, "entries.json"))
    for group in ("configs", "workloads", "per_layer"):
        b[group] = b[group] + entries[group]
    for m in b["end_to_end"]:
        if m["name"] in entries["end_to_end_joins"]:
            m["workloads"] = m["workloads"] + [
                entries["end_to_end_joins"][m["name"]]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f, indent=1)
    return root, added


def _run(root: str, *more: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    return subprocess.Popen(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "toygpt.trickle", "--tiny", "--seconds", "3",
         "--seed", str(2 ** 31 + 5), *more],
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def _line(proc: subprocess.Popen) -> tuple:
    """(the result line of a dry run, its standard error)."""
    out, err = proc.communicate(timeout=900)
    assert proc.returncode == 0, err[-3000:]
    last = out.strip().splitlines()[-1]
    prefix = "[CPU dry run, not a result] "
    assert last.startswith(prefix), last[:200]
    return json.loads(last[len(prefix):]), err


def test_a_second_family_lands_as_new_files_and_entries(copy):
    root, added = copy
    assert any(p.startswith("families/toygpt/") for p in added)
    procs = [_run(root, "--trace", "1"), _run(root, "--control")]
    try:
        (sound, err), (control, _) = (_line(p) for p in procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    limit = sound["compared"]["served_gap_max"]["limit"]
    assert sound["correct"], sound["compared"]
    assert sound["compared"]["served_gap_max"]["value"] <= limit
    assert sound["info"]["served_tokens_compared"] >= 20
    # the traced run read the new cell's per-layer metrics through the new
    # family: one is named, the other got through the family's counts as
    # far as the table of peaks, which has no CPU
    assert "tick_p50_ms.trickle" in sound["metrics"]
    assert 'step_mfu_pct.trickle: not read in the dry run ("no peak' in err
    assert not control["correct"], control["compared"]
    assert control["compared"]["served_gap_max"]["value"] > limit
    assert control["info"]["program_correct"]
