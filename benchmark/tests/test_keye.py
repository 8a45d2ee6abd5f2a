"""The keye family and its cell, on the CPU: ``run.py --tiny`` on the new
cell to ``correct`` true and, with ``--control``, false; the family's
counts against hand counts at the published sizes; and no file that the
benchmark had at the parent commit changed (by hash, as test_door.py
holds it for its copy)."""

import hashlib
import json
import os
import subprocess
import sys

from harness import cells

CELL = "keye-vl-2.0-30b-a3b-l6.longdoc"

# sha256 of every file benchmark/ had at PR 30's parent (cb29c04), tests
# and their data apart: git ls-tree -r cb29c04 -- benchmark
PARENT_FILES = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "parent_files_pr30.json")))


def _run(*more: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    return subprocess.Popen(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", CELL, "--tiny", "--seconds", "4",
         "--seed", str(2 ** 31 + 30), *more],
        cwd=cells.ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def _line(proc: subprocess.Popen) -> tuple:
    out, err = proc.communicate(timeout=900)
    assert proc.returncode == 0, err[-3000:]
    last = out.strip().splitlines()[-1]
    prefix = "[CPU dry run, not a result] "
    assert last.startswith(prefix), last[:200]
    return json.loads(last[len(prefix):]), err


def test_the_cell_runs_to_correct_and_the_control_to_false():
    procs = [_run("--trace", "1"), _run("--control")]
    try:
        (sound, err), (control, _) = (_line(p) for p in procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    limit = sound["compared"]["served_gap_max"]["limit"]
    assert sound["correct"], sound["compared"]
    assert sound["info"]["served_tokens_compared"] >= 20
    # the traced run names the two metrics that read the new counters
    for name in ("dsa_kv_read_share_pct.longdoc",
                 "moe_load_max_over_mean.longdoc",
                 "kv_peak_occupancy_pct.longdoc", "tick_p50_ms.longdoc"):
        assert name in sound["metrics"], (name, err[-2000:])
    assert not control["correct"], control["compared"]
    assert control["compared"]["served_gap_max"]["value"] > limit
    assert control["info"]["program_correct"]


def test_new_readers_return_nothing_where_the_program_has_no_counter():
    """The parent's ticks have no ``dsa_*`` / ``moe_*`` fields: the readers
    leave the metric out and do not raise."""
    run = {"cfg": {"num_experts": 128},
           "window": {"ring_full": False,
                      "ticks": [{"dur_ms": 1.0, "used_blocks": 1}]}}
    for name in ("dsa_kv_read_share_pct.longdoc",
                 "moe_load_max_over_mean.longdoc"):
        spec, read = cells.layer_metric(name)
        assert read(run, spec["params"]) is None
    run["window"]["ticks"] = [
        {"dsa_ctx_tokens": 8000, "dsa_read_tokens": 2048,
         "moe_assignments": 1280, "moe_max_load": 20},
        {"dsa_ctx_tokens": 0, "dsa_read_tokens": 0,
         "moe_assignments": 4096, "moe_max_load": 64}]
    spec, read = cells.layer_metric("dsa_kv_read_share_pct.longdoc")
    assert read(run, spec["params"]) == 25.6
    spec, read = cells.layer_metric("moe_load_max_over_mean.longdoc")
    assert read(run, spec["params"]) == 2.0


def test_counts_against_hand_counts_at_the_published_sizes():
    cfg = cells.Cell(CELL).config
    counts = cells.family(cfg).counts
    # a layer outside its experts: q 2048 x 4096 + o 4096 x 2048, k + v
    # 2 x 2048 x 512, the indexer 2048 x (1024 + 64 + 16), the router
    # 2048 x 128 (+ the norms' 4096 + 2 x 128 + 2 x 64 scales and biases)
    dense = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 1104 + 2048 * 128
    assert counts.dense_params(cfg) == dense == 21_397_504
    assert round((dense + 4096 + 256 + 128) / 1e6, 2) == 21.40
    assert 128 * counts.expert_params(cfg) == 128 * 3 * 2048 * 768
    assert round(128 * counts.expert_params(cfg) / 1e6, 2) == 603.98
    layer = dense + 4096 + 256 + 128 + 603_979_776
    assert counts.n_params(cfg) == 6 * layer + 2 * 151936 * 2048 + 2048
    assert round(counts.n_params(cfg) / 1e9, 3) == 4.375
    assert counts.weight_bytes(cfg) == 2 * counts.n_params(cfg)
    # cache: (2 x 4 x 128 + 64) x 2 B = 2176 B a token a layer
    assert counts.kv_bytes(cfg, 1) == 6 * 2176 == 13_056
    assert counts.kv_bytes(cfg, 1000) == 13_056_000
    # a decode step at 16 k reads 16384 index keys and 2048 K/V rows
    assert counts.dsa_decode_bytes(cfg, 16384) == 6 * 2 * (
        16384 * 64 + 2048 * 1024)
    assert counts.dsa_decode_bytes(cfg, 1000) == counts.kv_bytes(cfg, 1000)
    assert counts.paged_attn_bytes(cfg, 1000, 256) == 6 * 2 * (
        1024 * 64 + 1000 * 1024)
    # a token at context c: projections, 8 experts, 2*16*64*c of scores
    # and 4*32*128*min(c, 2048) of attention, per layer
    per = 2 * (dense + 8 * 3 * 2048 * 768)
    assert counts.token_flops(cfg, 5000) == 6 * (
        per + 2 * 16 * 64 * 5000 + 4 * 32 * 128 * 2048)
    assert counts.token_flops(cfg, 100) == 6 * (
        per + 2 * 16 * 64 * 100 + 4 * 32 * 128 * 100)
    assert counts.span_flops(cfg, 2040, 20) == sum(
        counts.token_flops(cfg, c) for c in range(2041, 2061))
    assert counts.head_flops(cfg) == 2 * 2048 * 151936
    # 16 decode rows make 128 assignments: 81 of 128 experts hit, 4.7 M each
    hit = 128 * (1 - (127 / 128) ** 128)
    assert counts.moe_weight_bytes(cfg, 128) == int(
        6 * hit * 3 * 2048 * 768 * 2)
    assert counts.moe_weight_bytes(cfg, 10 ** 6) == 6 * 603_979_776 * 2


def test_no_file_the_benchmark_had_changed():
    for rel, want in PARENT_FILES.items():
        with open(os.path.join(cells.ROOT, rel), "rb") as f:
            got = hashlib.sha256(f.read()).hexdigest()
        assert got == want, f"{rel} was in the benchmark and changed"


def test_the_configuration_keeps_the_catalogs_widths():
    cfg = cells.Cell(CELL).config
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4,
                                                             128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["vocab_size"]) == (
        128, 8, 768, 151936)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert 4 <= cfg["num_hidden_layers"] <= 6
