"""Find a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own:

    configs/<configuration>.json       sizes, engine knobs (path from BENCHMARK.json)
    traffic/<traffic>.json             the mix's parameters
    workloads/<cell>.json              the cell's own numbers (fixed rate, limits)
    layer_metrics/<metric>.json        which reader, its parameters
    layer_metrics/<reader>.py          ``read(run, params)`` -> number or None
    families/<family>/                 what knows the architecture that the
                                       configuration's ``family`` names
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or benchmark()
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in bench['workloads']]})")
        self.row = rows[0]
        self.name = name
        self.chips = int(self.row["chips"])
        cfg_row = next(c for c in bench["configs"]
                       if c["name"] == self.row["config"])
        self.config_name = cfg_row["name"]
        self.config = load_json(os.path.join(ROOT, cfg_row["file"]))
        self.traffic_name = self.row["traffic"]
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", f"{self.traffic_name}.json"))
        self.workload = load_json(os.path.join(
            BENCH_DIR, "workloads", f"{name}.json"))
        self.run_seconds = int(bench["run_seconds"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def layer_metric(name: str):
    """(spec, read) of one per-layer metric: its json and its reader."""
    spec = load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                  f"{name}.json"))
    path = os.path.join(BENCH_DIR, "layer_metrics",
                        f"{spec['reader']}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"layer_metric_{spec['reader'].replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return spec, mod.read


def families() -> list:
    """The families present: the directories of ``families/``."""
    root = os.path.join(BENCH_DIR, "families")
    return sorted(d for d in os.listdir(root)
                  if re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_]*", d)
                  and os.path.isdir(os.path.join(root, d)))


class Family:
    """``families/<name>/``: its ``leaves``, ``reference``, ``counts`` and
    ``model`` modules, each imported when asked for (``model`` alone
    imports the package, and only harness/server.py asks for it)."""

    def __init__(self, name: str):
        self.name = name

    def __getattr__(self, part: str):
        if part not in ("leaves", "reference", "counts", "model"):
            raise AttributeError(part)
        return importlib.import_module(f"families.{self.name}.{part}")


def family(cfg: dict) -> Family:
    """The family a configuration names.  There is no default: a file
    without the key, or naming a family that is absent, is an error."""
    name, present = cfg.get("family"), families()
    if name not in present:
        raise SystemExit(
            f"configuration {cfg.get('name')!r} "
            + (f"names the family {name!r}, which is not under "
               f"benchmark/families/" if name else "has no 'family' key")
            + f" (present: {present})")
    return Family(name)
