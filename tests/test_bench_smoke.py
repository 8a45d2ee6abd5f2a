"""Import-level smoke for the files the driver runs by name: a syntax
error or a broken import in ``__graft_entry__.py`` or in the benchmark's
entry point (``BENCHMARK.json`` ``command``: ``benchmark/run.py``) would
otherwise surface only in the driver's run after the session, as an
opaque error artifact."""

import importlib
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def test_driver_entry_modules_import_and_expose_entries():
    ge = importlib.import_module("__graft_entry__")
    assert callable(ge.entry) and callable(ge.dryrun_multichip)

    # read-only: run.py puts benchmark/ on sys.path as it is imported (its
    # harness is not a package of this suite), so put the path back
    spec = importlib.util.spec_from_file_location(
        "_benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(run)
    finally:
        sys.path[:] = path
    assert callable(run.main)
