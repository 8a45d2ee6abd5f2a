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
