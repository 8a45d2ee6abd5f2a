"""The bailing_hybrid family and its cell, on the CPU: ``run.py --tiny`` on
the new cell to ``correct`` true and, with ``--control``, false; the new
reader on ticks with and without the counter, and the shared readers on
the family's counts; the family's counts against hand counts at the
published sizes; the configuration against the catalog's widths; and no
file that the benchmark had at the parent commit changed."""

import hashlib
import json
import os
import subprocess
import sys

from harness import cells

CELL = "ling-3.0-flash-l8-ep4.longgen"

# sha256 of every file benchmark/ had at the parent commit (3bba012), tests
# and their data apart: git ls-tree -r 3bba012 -- benchmark
PARENT_FILES = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "parent_files_3bba012.json")))


def _run(*more: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    return subprocess.Popen(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", CELL, "--tiny", "--seconds", "4",
         "--seed", str(2 ** 31 + 36), *more],
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
    for name in ("latent_kv_bytes_share_pct.longgen",
                 "ssm_state_bytes_share_pct.longgen",
                 "moe_held_pick_share_pct.longgen",
                 "moe_load_max_over_mean.longgen",
                 "paged_attn_live_block_pct.longgen",
                 "kv_peak_occupancy_pct.longgen", "tick_p50_ms.longgen"):
        assert name in sound["metrics"], (name, err[-2000:])
    assert not control["correct"], control["compared"]
    assert control["compared"]["served_gap_max"]["value"] > limit
    assert control["info"]["program_correct"]


def _ticks(row):
    """A decode-only tick of 64 rows, a chunk of 448 tokens in one row
    beside 63 decode rows, and a step of 8 tokens over 64 rows, with the
    counters the program books (6 expert layers of 8, 8 picks a token)."""
    return [
        {"ssm_rows": 64, "ssm_state_bytes": 2 * 64 * row, "chunks": 0,
         "ssm_passes": 1, "moe_assignments": 6 * 512,
         "moe_held_assignments": 6 * 128, "moe_max_load": 5,
         "latent_kv_rows": 64 * 12 * 256,
         "latent_kv_bytes": 1152 * 64 * 12 * 256, "attn_live_blocks": 250},
        {"ssm_rows": 64, "ssm_state_bytes": 2 * 64 * row, "chunks": 1,
         "ssm_passes": 2, "moe_assignments": 6 * 8 * (63 + 448),
         "moe_held_assignments": 6 * 2 * (63 + 448), "moe_max_load": 40,
         "latent_kv_rows": 64 * 12 * 256 + 2048,
         "latent_kv_bytes": 1152 * (64 * 12 * 256 + 2048),
         "attn_live_blocks": 250},
        {"ssm_rows": 512, "ssm_state_bytes": 2 * 512 * row, "chunks": 0,
         "ssm_passes": 8, "moe_assignments": 8 * 6 * 512,
         "moe_held_assignments": 8 * 6 * 128, "moe_max_load": 6,
         "latent_kv_rows": 8 * 64 * 12 * 256,
         "latent_kv_bytes": 8 * 1152 * 64 * 12 * 256,
         "attn_live_blocks": 250}]


def test_the_latent_reader_returns_nothing_where_the_program_has_no_counter():
    """The parent's ticks, and another model's, have no
    ``latent_kv_bytes``: the reader leaves the metric out and does not
    raise; on a program's ticks it is the latent share of the bytes."""
    cfg = cells.Cell(CELL).config
    run = {"cfg": cfg,
           "window": {"ring_full": False,
                      "ticks": [{"dur_ms": 1.0, "used_blocks": 1,
                                 "ssm_rows": 64, "ssm_passes": 1,
                                 "ssm_state_bytes": 10 ** 9,
                                 "moe_assignments": 1024,
                                 "moe_max_load": 16}]}}
    spec, read = cells.layer_metric("latent_kv_bytes_share_pct.longgen")
    assert read(run, spec["params"]) is None
    counts = cells.family(cfg).counts
    row = counts.ssm_state_bytes(cfg)
    run["window"]["ticks"] = ticks = _ticks(row)
    latent = sum(t["latent_kv_bytes"] for t in ticks)
    other = sum(t["ssm_state_bytes"] for t in ticks) + sum(
        t["ssm_passes"] * counts.pass_weight_bytes(
            cfg, t["moe_assignments"] / 8 / t["ssm_passes"])
        for t in ticks)
    got = read(run, spec["params"])
    assert got == 100.0 * latent / (latent + other)
    # a decode pass: 226 MB of latent against 7.2 GB of weights and 1.95
    # GB of state: a few percent
    assert 1.5 < got < 4
    run["window"]["ring_full"] = True
    assert read(run, spec["params"]) is None


def test_the_shared_readers_read_the_familys_counts():
    """``ssm_state_bytes_share_pct`` divides a tick's picks by all 8 layers
    (dense ones included), and the family's ``pass_weight_bytes`` takes its
    argument in that sense: a decode pass of 64 rows is 512 picks in each
    of the 6 expert layers."""
    cfg = cells.Cell(CELL).config
    counts = cells.family(cfg).counts
    row = counts.ssm_state_bytes(cfg)
    run = {"cfg": cfg, "window": {"ring_full": False,
                                  "ticks": _ticks(row)}}
    assert counts.moe_weight_bytes(cfg, 6 * 512 / 8) == int(
        6 * 128 * (1 - (1 - 1 / 128) ** 128) * 5_898_240 * 2)
    spec, read = cells.layer_metric("ssm_state_bytes_share_pct.longgen")
    assert 15 < read(run, spec["params"]) < 25
    spec, read = cells.layer_metric("moe_held_pick_share_pct.longgen")
    assert read(run, spec["params"]) == 25.0
    spec, read = cells.layer_metric("moe_load_max_over_mean.longgen")
    assert read(run, spec["params"]) == (
        5 / (768 / 768) + 40 / (6132 / 768 / 2)
        + 6 / (6144 / 768 / 8)) / 3


def test_counts_against_hand_counts_at_the_published_sizes():
    cfg = cells.Cell(CELL).config
    counts = cells.family(cfg).counts
    E, P = 2560, 4096
    # a KDA mixer: q, k, v, f, gate 5 x 2560 x 4096, beta 2560 x 32, out
    # 4096 x 2560; conv 4 x 12288, dt_bias 4096, A_log 32, the norm 128
    kda = 5 * E * P + E * 32 + P * E + 4 * 3 * P + P + 32 + 128
    assert round(kda / 1e6, 2) == 63.05
    # an MLA mixer: q 2560 x 32 x 192, kv_a 2560 x 576, the latent norm
    # 512, kv_b 512 x 32 x 256, the gate 2560 x 32, out 32 x 128 x 2560
    mla = E * 32 * 192 + E * 576 + 512 + 512 * 32 * 256 + E * 32 \
        + 32 * 128 * E
    assert round(mla / 1e6, 2) == 31.97
    expert = 3 * E * 768
    assert counts.expert_params(cfg) == expert == 5_898_240
    dense = 3 * E * 6144
    moe = 128 * expert + 3 * E * 768 + E * 512 + 512     # + shared, router
    norms = 2 * E
    assert counts.n_params(cfg) == 7 * kda + mla + 2 * dense \
        + 6 * moe + 8 * norms + 2 * 39296 * E + E
    assert round(counts.n_params(cfg) / 1e9, 2) == 5.34
    assert round(counts.weight_bytes(cfg) / 1e9, 2) == 10.68
    assert counts.held_expert_slots(cfg) == 6 * 128
    # the latent: ONE MLA layer, (512 + 64) x 2 B = 1152 B a token
    assert counts.kv_bytes(cfg, 1) == 1152
    assert counts.paged_attn_bytes(cfg, 1000, 256) == 1024 * 1152
    # state: 32 x 128 x 128 float32 + 3 x 12288 bf16 a layer, seven layers
    assert counts.ssm_state_bytes(cfg) == 7 * (2_097_152 + 73_728)
    assert round(64 * counts.ssm_state_bytes(cfg) / 1e9, 2) == 0.97
    # a token: a KDA layer its projections, 4 taps, 7 operations an element
    # of the state; an MLA layer its projections and 2 x 32 x (2 x 512 +
    # 64) a position in context; 2 dense SwiGLUs; 6 expert layers of the
    # router, the shared expert and 8 x 128 / 512 = 2 held experts
    kda_f = 2 * (5 * E * P + E * 32 + P * E) + 2 * 4 * 3 * P \
        + 7 * 32 * 128 * 128
    mla_f = 2 * (E * 32 * 192 + E * 576 + 512 * 32 * 256 + E * 32
                 + 32 * 128 * E)
    ffn = 2 * 2 * dense + 6 * 2 * (E * 512 + 3 * E * 768 + 2 * expert)
    assert counts.token_flops(cfg, 1000) == 7 * kda_f + mla_f + ffn \
        + 2 * 32 * (2 * 512 + 64) * 1000
    assert counts.span_flops(cfg, 40, 20) == sum(
        counts.token_flops(cfg, c) for c in range(41, 61))
    assert counts.head_flops(cfg) == 2 * E * 39296
    # a decode pass of 64 rows: 512 picks an expert layer, 128 of them
    # here, over 128 experts: 81 hit in the mean, 5.7 GB
    assert round(counts.moe_weight_bytes(cfg, 6 * 512 / 8) / 1e9, 1) == 5.7
    assert round(counts.pass_weight_bytes(cfg, 6 * 512 / 8) / 1e9, 1) == 7.2


def test_no_file_the_benchmark_had_changed():
    for rel, want in PARENT_FILES.items():
        with open(os.path.join(cells.ROOT, rel), "rb") as f:
            got = hashlib.sha256(f.read()).hexdigest()
        assert got == want, f"{rel} was in the benchmark and changed"


def test_the_configuration_keeps_the_catalogs_widths():
    cfg = cells.Cell(CELL).config
    assert cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
        "num_nextn_predict_layers"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["num_nextn_predict_layers"]) == (42, 512, 157184, 1)
    assert len(pub["expert_swiglu_limit_list"]) == 42
    assert cfg["expert_swiglu_limit_list"] \
        == pub["expert_swiglu_limit_list"][:8] == [0] * 8
    assert cfg["share_expert_swiglu_limit_list"] \
        == pub["share_expert_swiglu_limit_list"][:8] == [0] * 8
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["short_conv_kernel_size"]) == (
        2560, 32, 128, 6144, 768, 768, 8, 512, 128, 64, 128, 4)
    assert (cfg["n_group"], cfg["topk_group"], cfg["routed_scaling_factor"],
            cfg["layer_group_size"], cfg["first_k_dense_replace"],
            cfg["kda_lower_bound"], cfg["rope_theta"]) == (
        8, 4, 2.5, 6, 2, -5, 6000000)
    kinds = cells.family(cfg).leaves
    assert [kinds.kind(cfg, i) for i in range(8)] == [
        "kda_dense", "kda_dense", "kda", "kda", "kda", "mla", "kda", "kda"]
    # the floors: a whole period of expert layers, two of the router's
    # eight groups, a quarter of the rows
    assert cfg["num_experts"] * 4 == pub["num_experts"]
    assert cfg["vocab_size"] * 4 == pub["vocab_size"]
    assert cfg["engine"]["engine_prefix_cache"] is False
