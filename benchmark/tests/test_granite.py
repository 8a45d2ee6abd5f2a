"""The granite_hybrid family and its cell, on the CPU: ``run.py --tiny`` on
the new cell to ``correct`` true and, with ``--control``, false; the new
readers on ticks with and without the counters; the family's counts against
hand counts at the published sizes; the configuration against the catalog's
widths; and no file that the benchmark had at the parent commit changed."""

import hashlib
import json
import os
import subprocess
import sys

from harness import cells

CELL = "granite-4.0-h-small-l10-ep2.longgen"

# sha256 of every file benchmark/ had at PR 34's parent (29921a9), tests
# and their data apart: git ls-tree -r 29921a9 -- benchmark
PARENT_FILES = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "parent_files_pr34.json")))


def _run(*more: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    return subprocess.Popen(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", CELL, "--tiny", "--seconds", "4",
         "--seed", str(2 ** 31 + 34), *more],
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
    # the traced run names the metrics that read the new counters
    for name in ("ssm_state_bytes_share_pct.longgen",
                 "moe_held_pick_share_pct.longgen",
                 "moe_load_max_over_mean.longgen",
                 "paged_attn_live_block_pct.longgen",
                 "kv_peak_occupancy_pct.longgen", "tick_p50_ms.longgen"):
        assert name in sound["metrics"], (name, err[-2000:])
    assert not control["correct"], control["compared"]
    assert control["compared"]["served_gap_max"]["value"] > limit
    assert control["info"]["program_correct"]


def test_new_readers_return_nothing_where_the_program_has_no_counter():
    """The parent's ticks, and another model's, have no ``ssm_*`` /
    ``moe_held_*`` fields: the readers leave the metric out and do not
    raise."""
    cfg = cells.Cell(CELL).config
    run = {"cfg": cfg,
           "window": {"ring_full": False,
                      "ticks": [{"dur_ms": 1.0, "used_blocks": 1,
                                 "moe_assignments": 1024,
                                 "moe_max_load": 16}]}}
    names = ("ssm_state_bytes_share_pct.longgen",
             "moe_held_pick_share_pct.longgen",
             "moe_load_max_over_mean.longgen")
    for name in names:
        spec, read = cells.layer_metric(name)
        assert read(run, spec["params"]) is None
    counts = cells.family(cfg).counts
    row = counts.ssm_state_bytes(cfg)
    run["window"]["ticks"] = [
        # a decode-only tick of 64 rows: 640 picks a layer, 3190 held
        {"ssm_rows": 64, "ssm_state_bytes": 2 * 64 * row, "chunks": 0,
         "ssm_passes": 1, "moe_assignments": 6400,
         "moe_held_assignments": 3190, "moe_max_load": 18,
         "attn_live_blocks": 250},
        # a chunk of 448 tokens in one row beside 63 decode rows
        {"ssm_rows": 64, "ssm_state_bytes": 2 * 64 * row, "chunks": 1,
         "ssm_passes": 2, "moe_assignments": 51100,
         "moe_held_assignments": 25810, "moe_max_load": 160,
         "attn_live_blocks": 250},
        # a step of 8 tokens over 64 rows
        {"ssm_rows": 512, "ssm_state_bytes": 2 * 512 * row, "chunks": 0,
         "ssm_passes": 8, "moe_assignments": 51200,
         "moe_held_assignments": 25560, "moe_max_load": 20,
         "attn_live_blocks": 250}]
    spec, read = cells.layer_metric("moe_held_pick_share_pct.longgen")
    assert read(run, spec["params"]) == 100.0 * 54560 / 108700
    spec, read = cells.layer_metric("moe_load_max_over_mean.longgen")
    assert read(run, spec["params"]) == (
        18 / (3190 / 360) + 160 / (25810 / 360 / 2)
        + 20 / (25560 / 360 / 8)) / 3
    spec, read = cells.layer_metric("ssm_state_bytes_share_pct.longgen")
    state = 2 * (2 * 64 + 512) * row
    other = 9 * counts.pass_weight_bytes(cfg, 640) \
        + 2 * counts.pass_weight_bytes(cfg, 2555) \
        + (1 + 1 + 8) * 250 * counts.kv_bytes(cfg, 256)
    assert read(run, spec["params"]) == 100.0 * state / (state + other)
    assert 25 < read(run, spec["params"]) < 35


def test_counts_against_hand_counts_at_the_published_sizes():
    cfg = cells.Cell(CELL).config
    counts = cells.family(cfg).counts
    # a Mamba-2 mixer: in 4096 x (8192 + 8448 + 128), out 8192 x 4096, the
    # convolution 4 x 8448 + 8448, dt_bias, A_log, D 3 x 128, the norm 8192
    mamba = 4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192
    assert round(mamba / 1e6, 2) == 102.29
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert round(attn / 1e6, 2) == 41.94
    expert = 3 * 4096 * 768
    assert counts.expert_params(cfg) == expert == 9_437_184
    ffn = 36 * expert + 3 * 4096 * 1536 + 4096 * 72     # + shared + router
    layer_norms = 2 * 4096
    assert counts.n_params(cfg) == 9 * mamba + attn + 10 * (
        ffn + layer_norms) + 50176 * 4096 + 4096
    assert round(counts.n_params(cfg) / 1e9, 3) == 4.757
    assert round(counts.weight_bytes(cfg) / 1e9, 2) == 9.51
    assert counts.held_expert_slots(cfg) == 360
    # K/V: ONE attention layer, 2 x 8 x 128 x 2 B = 4 KiB a token
    assert counts.kv_bytes(cfg, 1) == 4096
    assert counts.paged_attn_bytes(cfg, 1000, 256) == 1024 * 4096
    # state: 128 x 64 x 128 float32 + 3 x 8448 bf16 a layer, nine layers
    assert counts.ssm_state_bytes(cfg) == 9 * (4_194_304 + 50_688)
    assert round(64 * counts.ssm_state_bytes(cfg) / 1e9, 2) == 2.45
    # a token: per state-space layer its projections, 4 taps, 5 operations
    # an element of the state; per layer the router, the shared expert and
    # 10 x 36 / 72 = 5 held experts; attention 4 x 32 x 128 x ctx
    ssm = 2 * (4096 * 16768 + 8192 * 4096) + 2 * 4 * 8448 \
        + 5 * 128 * 64 * 128
    every = 2 * (4096 * 72 + 3 * 4096 * 1536 + 5 * expert)
    assert counts.token_flops(cfg, 1000) == 9 * ssm + 2 * attn \
        + 10 * every + 4 * 32 * 128 * 1000
    assert counts.span_flops(cfg, 40, 20) == sum(
        counts.token_flops(cfg, c) for c in range(41, 61))
    assert counts.head_flops(cfg) == 2 * 4096 * 50176
    # a decode tick of 64 rows: 640 picks a layer, 320 of them here, over
    # 36 experts: every one is hit (a miss is (35/36)^320 = 1e-4)
    assert 0.999 < counts.moe_weight_bytes(cfg, 640) / (
        10 * 36 * expert * 2) <= 1.0
    assert round(counts.moe_weight_bytes(cfg, 640) / 1e9, 1) == 6.8
    assert round(counts.pass_weight_bytes(cfg, 640) / 1e9, 1) == 9.5


def test_no_file_the_benchmark_had_changed():
    for rel, want in PARENT_FILES.items():
        with open(os.path.join(cells.ROOT, rel), "rb") as f:
            got = hashlib.sha256(f.read()).hexdigest()
        assert got == want, f"{rel} was in the benchmark and changed"


def test_the_configuration_keeps_the_catalogs_widths():
    cfg = cells.Cell(CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_local_experts", "vocab_size"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_local_experts"],
            pub["vocab_size"], len(pub["layer_types"])) == (
        40, 72, 100352, 40)
    assert cfg["layer_types"] == pub["layer_types"][:10] == \
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["num_experts_per_tok"]) \
        == (4096, 32, 8, 768, 1536, 10)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_expand"], cfg["mamba_n_groups"],
            cfg["mamba_chunk_size"]) == (128, 64, 128, 4, 2, 1, 256)
    assert (cfg["attention_multiplier"], cfg["embedding_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"]) == (
        0.0078125, 12, 0.22, 16)
    # the floors: a whole period, 8 routed experts, an eighth of the rows
    assert cfg["num_hidden_layers"] == 10 and cfg["num_local_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["engine"]["engine_prefix_cache"] is False
