"""BENCHMARK.json against its contract and against the files it names."""

import glob
import json
import os
import re

from harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = cells.benchmark()


def test_keys_and_names():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in B[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in B["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_every_named_file_exists_and_loads():
    for w in B["workloads"]:
        cell = cells.Cell(w["name"], B)
        assert cell.config["name"] == w["config"]
        assert cell.workload["config"] == w["config"]
        assert cell.workload["traffic"] == w["traffic"]
        assert cell.workload["chips"] == w["chips"]
    for c in B["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in B["paths"]))
        cfg = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for m in B["per_layer"]:
        spec, read = cells.layer_metric(m["name"])
        assert callable(read)
        # BENCHMARK.json alone says what a metric is; its file says only
        # how it is read, so a later cell joins a metric without an edit
        assert set(spec) == {"reader", "params"}, m["name"]


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in B["workloads"]:
        cell = cells.Cell(w["name"], B)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {x["name"] for x in cell.end_to_end}
    layers = {}
    for m in B["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_file_names_use_the_allowed_characters():
    for path in glob.glob(os.path.join(cells.BENCH_DIR, "**", "*"),
                          recursive=True):
        if "__pycache__" in path or ".pytest_cache" in path:
            continue
        rel = os.path.relpath(path, cells.ROOT)
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_no_code_branches_on_a_cell_or_configuration_name():
    names = [x["name"] for x in B["configs"] + B["workloads"]]
    for path in glob.glob(os.path.join(cells.BENCH_DIR, "**", "*.py"),
                          recursive=True):
        if os.sep + "tests" + os.sep in path:
            continue
        text = open(path).read()
        for n in names:
            for line in text.splitlines():
                if n in line and re.search(r"\b(if|elif)\b", line):
                    raise AssertionError(f"{path}: branches on {n!r}")


def test_configurations_differ_only_in_their_files():
    """Every pair of one family: the same engine and generator knobs, the
    same source keys, other values."""
    import itertools

    cfgs = [cells.load_json(os.path.join(cells.ROOT, c["file"]))
            for c in B["configs"]]
    pairs = [(a, b) for a, b in itertools.combinations(cfgs, 2)
             if a["family"] == b["family"]]
    assert pairs
    for a, b in pairs:
        assert set(a["engine"]) == set(b["engine"])
        assert set(a["generator"]) == set(b["generator"])
        assert json.dumps(a) != json.dumps(b)
