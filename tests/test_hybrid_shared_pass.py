"""One weight pass a fused tick for the hybrid decoders: the decode rows and
a grid of prompt-chunk rows go through every row-by-row matrix together
(``HybridLM.fused_step_paged_ssm``), on both hybrid configurations' ``tiny``
sections (granite: Mamba-2 + NoPE attention; Ling: KDA + MLA, a dense first
layer), with the benchmark's seeded leaves in float32 on the CPU."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
FAMILIES = {"granite-4.0-h-small-l10-ep2": "granite_hybrid",
            "ling-3.0-flash-l8-ep4": "bailing_hybrid"}
SEED = 5


def _tiny_cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    out = dict(cfg)
    for k, v in cfg["tiny"].items():
        out[k] = {**cfg[k], **v} if isinstance(v, dict) and isinstance(
            cfg.get(k), dict) else v
    return out


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def fam(request):
    """One hybrid configuration: the model, its seeded float32 variables
    and the number of its layers that hold experts."""
    import importlib

    cfg = _tiny_cfg(request.param)
    sys.path.insert(0, BENCH)
    try:
        family = importlib.import_module(
            f"families.{FAMILIES[request.param]}.model")
        from harness import weights
    finally:
        sys.path.remove(BENCH)
    lm = family.build(cfg)
    top = weights.top(cfg, SEED)
    params = family.place(cfg, top, lambda i: weights.layer(cfg, SEED, i))
    return dict(cfg=cfg, lm=lm, expert_layers=lm.num_layers - lm.dense_layers,
                variables={"params": jax.tree.map(
                    lambda a: a.astype(jnp.float32), params)})


def _engine(fam, **kw):
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    args = dict(max_new_tokens=8, max_slots=5, prompt_buckets=(16, 104),
                paged=True, block_size=8, chunked=True,
                tick_token_budget=20, enable_prefix_cache=False)
    args.update(kw)
    return ContinuousEngine(fam["lm"], fam["variables"], **args)


def _tokens(n, key):
    return np.asarray(jax.random.randint(jax.random.key(key), (n,), 1, 512))


# ---- the shared pass against the two passes it replaces -------------------

def _two_kinds(fam, case):
    """Caches holding five slots' contexts, and one fused tick's operands:
    decode rows (two live; a frozen PREFILLING row at its fill frontier; a
    slot whose old tenant left its state behind; a second PREFILLING row)
    and a chunk grid of ``case``: ``frozen`` the PREFILLING row's next
    chunk, ``padding`` that and a padding row (out-of-range slot, all-sink
    table), ``two_rows`` that and the second PREFILLING row's chunk,
    ``fresh`` a new prompt at position 0 in the slot with the stale state,
    ``completes`` the PREFILLING row's last chunk, shorter than the grid."""
    from analytics_zoo_tpu.models.hybrid_lm import HybridLM

    lm, variables = fam["lm"], fam["variables"]
    eng = _engine(fam, n_blocks=40)
    pk, pv = eng._pk, eng._pv
    M, n = 12, 5
    tables = np.zeros((n, M), np.int32)
    tables[0, :6] = np.arange(1, 7)         # 40 in context
    tables[1, :4] = np.arange(7, 11)        # 25
    tables[2, :6] = np.arange(11, 17)       # PREFILLING: 24 filled of 34
    tables[3, :2] = np.arange(17, 19)       # a finished tenant's 16
    tables[4, :6] = np.arange(19, 25)       # PREFILLING: 8 filled of 40
    ctx = np.array([40, 25, 24, 16, 8], np.int32)
    prompts = [_tokens(48, 20 + i) for i in range(n)]
    fill = np.zeros((n, 40), np.int32)
    for i, c in enumerate(ctx):
        fill[i, :c] = prompts[i][:c]
    _, pk, pv, _ = jax.jit(lambda *a: lm.apply(
        variables, *a, method=HybridLM.prefill_chunk_paged_ssm))(
        fill, pk, pv, tables, np.zeros(n, np.int32), ctx,
        np.arange(n, dtype=np.int32))
    tok = np.array([p[c] for p, c in zip(prompts, ctx)], np.int32)
    live = np.array([True, True, False, False, False])
    new = _tokens(16, 40)
    grid = {"frozen": [(2, 16)], "padding": [(2, 16), (None, 1)],
            "two_rows": [(2, 16), (4, 16)], "fresh": [(3, 16)],
            "completes": [(2, 10)]}[case]
    S = 16
    ctoks = np.zeros((len(grid), S), np.int32)
    ctabs = np.zeros((len(grid), M), np.int32)
    cpos = np.zeros(len(grid), np.int32)
    clens = np.ones(len(grid), np.int32)
    cslots = np.full(len(grid), n, np.int32)
    pos = ctx.copy()            # a frozen row sits at its fill frontier
    for j, (i, c) in enumerate(grid):
        if i is None:
            continue
        ctabs[j], clens[j], cslots[j] = tables[i], c, i
        if case == "fresh":
            ctoks[j, :c] = new[:c]
            pos[i] = 0
        else:
            ctoks[j, :c] = prompts[i][ctx[i]:ctx[i] + c]
            cpos[j] = ctx[i]
    return pk, pv, (tok, tables, pos, live), (ctoks, ctabs, cpos, clens,
                                             cslots)


@pytest.mark.parametrize("case", ["frozen", "padding", "two_rows", "fresh",
                                  "completes"])
def test_shared_pass_equals_the_two_passes(fam, case):
    """``fused_step_paged_ssm`` against the decode step, then the chunk on
    the caches it left.  A row's output does not depend on its batchmates
    in any row-by-row matrix or in ``HeldExperts``, so the logits, every
    state arena, window and pool leaf and the counters are the two
    passes', but for the largest load of one expert, which now counts both
    kinds of row at once: at least either pass's, at most their sum.  The
    logits of a frozen decode row are left out: the engine takes no token
    from them, and the shared pass runs a recurrent layer's chunk rows
    before its decode rows, so a frozen row whose chunk rides in the tick
    reads its state after that chunk."""
    from analytics_zoo_tpu.models.hybrid_lm import HybridLM

    lm, variables = fam["lm"], fam["variables"]
    pk, pv, (tok, tables, pos, live), c = _two_kinds(fam, case)
    ctoks, ctabs, cpos, clens, cslots = c

    @jax.jit
    def two(pk, pv):
        logits, pk, pv, st = lm.apply(
            variables, tok, pk, pv, tables, pos, live,
            method=HybridLM.decode_step_paged_ssm)
        clog, pk, pv, cst = lm.apply(
            variables, ctoks, pk, pv, ctabs, cpos, clens, cslots,
            method=HybridLM.prefill_chunk_paged_ssm)
        return logits, clog, pk, pv, st, cst

    @jax.jit
    def one(pk, pv):
        return lm.apply(variables, tok, pk, pv, tables, pos, live, ctoks,
                        ctabs, cpos, clens, cslots,
                        method=HybridLM.fused_step_paged_ssm)

    want, got = two(pk, pv), one(pk, pv)
    for name, w, g in (("logits", want[0][live], got[0][live]),
                       ("clog", want[1], got[1])):
        w, g = np.asarray(w), np.asarray(g)
        assert np.abs(w - g).max() <= 1e-6, name
        assert (w.argmax(-1) == g.argmax(-1)).all(), name
    leaves_w = jax.tree.leaves(want[2:4])
    leaves_g = jax.tree.leaves(got[2:4])
    assert len(leaves_w) == len(leaves_g)
    for w, g in zip(leaves_w, leaves_g):
        assert np.abs(np.asarray(w) - np.asarray(g)).max() <= 1e-6
    st, cst, fused = (np.asarray(a) for a in (*want[4:], got[4]))
    np.testing.assert_array_equal(fused[:-1], st[:-1] + cst[:-1])
    assert max(st[-1], cst[-1]) <= fused[-1] <= st[-1] + cst[-1]
    # the expert load counts the live decode rows and the real columns
    real = cslots < len(live)
    names = lm.counter_names
    assert fused[names.index("moe_assignments")] \
        == lm.experts_per_token * fam["expert_layers"] * (
            int(live.sum()) + int(np.sum(real * clens)))
    assert fused[names.index("ssm_rows")] == int(live.sum() + real.sum())


def test_a_fresh_row_ignores_its_slots_stale_state(fam):
    """The ``fresh`` case reads as a new prompt served alone: the chunk
    row at position 0 gives the logits that the same tokens give through
    the uncached forward, whatever its slot held."""
    from analytics_zoo_tpu.models.hybrid_lm import HybridLM

    lm, variables = fam["lm"], fam["variables"]
    pk, pv, (tok, tables, pos, live), c = _two_kinds(fam, "fresh")
    ctoks, ctabs, cpos, clens, cslots = c
    _, clog, _, _, _ = jax.jit(lambda pk, pv: lm.apply(
        variables, tok, pk, pv, tables, pos, live, ctoks, ctabs, cpos,
        clens, cslots, method=HybridLM.fused_step_paged_ssm))(pk, pv)
    whole = lm.apply(variables, ctoks[:, :clens[0]])[:, -1]
    np.testing.assert_allclose(np.asarray(clog), np.asarray(whole),
                               atol=1e-5)


# ---- the programs --------------------------------------------------------

def _tiny_engine(fam):
    """The engine the benchmark's CPU dry run builds for the config."""
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    cfg = fam["cfg"]
    eng, gen = cfg["engine"], cfg["generator"]
    return ContinuousEngine(
        fam["lm"], fam["variables"], max_new_tokens=gen["max_new_tokens"],
        max_slots=eng["engine_slots"],
        prompt_buckets=tuple(gen["prompt_buckets"]),
        kernel=eng["engine_kernel"], paged=True,
        block_size=eng["engine_block_size"], chunked=True,
        enable_prefix_cache=False, ticks_per_step=eng["engine_ticks"],
        tick_token_budget=eng["engine_tick_token_budget"])


def _lowered_ragged_dots(eng, program, kb):
    """``lax.ragged_dot`` operations in one step program lowered for the
    TPU (on the CPU it lowers to a loop that names none)."""
    spec = jax.ShapeDtypeStruct
    S, M, Cb = eng._S, eng._M, eng._chunk_buckets[-1]
    like = lambda a: spec(a.shape, a.dtype)
    pk = jax.tree_util.tree_map(like, eng._pk)
    pv = jax.tree_util.tree_map(like, eng._pv)
    rows = (spec((S,), jnp.int32), spec((S,), jnp.int32),
            spec((S,), jnp.bool_), spec((S, M), jnp.int32),
            spec((S,), jnp.float32), spec((S,), jnp.uint32),
            spec((S,), jnp.float32))
    if program in ("decode", "step"):
        n = 1 if program == "decode" else eng.ticks_per_step
        w, args = eng._get_step(n, False), (pk, pv, *rows)
    else:
        w = eng._get_fused(program == "fused", False, False)
        args = (pk, pv, *rows, spec((kb, Cb), jnp.int32),
                spec((kb,), jnp.int32), spec((kb,), jnp.int32),
                spec((kb, M), jnp.int32), spec((kb,), jnp.float32),
                spec((kb,), jnp.uint32), spec((kb,), jnp.float32),
                spec((kb,), jnp.int32))
    text = w._jit.trace(*w._weights, *args).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count('"chlo.ragged_dot"')


@pytest.mark.parametrize("program,kb", [
    ("decode", 0), ("step", 0), ("chunk", 1), ("chunk", 2), ("fused", 1),
    ("fused", 2)])
def test_fused_tick_reads_each_expert_once(fam, program, kb):
    """Two grouped matmuls an expert layer (gate and up, down) in every
    program, the fused tick with decode rows included: both kinds of row
    go through one call, where the two passes held four.  (The step of
    several tokens holds its one-token body once, inside a scan.)"""
    eng = _tiny_engine(fam)
    assert _lowered_ragged_dots(eng, program, kb) \
        == 2 * fam["expert_layers"]


def test_shared_pass_updates_caches_in_place(fam):
    """The fused program with decode rows aliases the pools and the state
    arenas, and its temporaries stay under the chunk-only program's plus a
    quarter of one attention layer's share of the pool: the chunk's write
    to a layer follows the decode rows' read of it by a value, so no copy
    of the pool is kept."""
    eng = _engine(fam, n_blocks=512)
    chunk = eng.paged_step_memory("chunk")
    fused = eng.paged_step_memory("fused")
    pools = fused["pool_bytes"] + eng._pv.nbytes
    assert fused["alias_bytes"] >= pools
    layer = eng._pk.k.nbytes // fam["lm"].kv_layers
    assert fused["temp_bytes"] < chunk["temp_bytes"] + layer // 4, (
        chunk, fused, layer)


def test_fused_tick_books_its_shared_pass(fam):
    """Every tick carries ``moe_shared_assignments``: all of its
    ``moe_assignments`` where decode rows rode beside a chunk (one pass,
    ``ssm_passes`` 1), else 0; a chunk tick without decode rows is one
    pass too."""
    eng = _engine(fam, max_slots=3)
    out = {}
    prompts = [_tokens(n, 30 + n) for n in (40, 60, 30, 70, 12)]
    for i, p in enumerate(prompts):
        eng.submit(f"r{i}", p, max_new=8,
                   on_done=lambda uri, toks: out.__setitem__(uri, toks))
    eng.drain()
    assert len(out) == len(prompts)
    recs = eng.flight.snapshot()
    assert recs and all("moe_shared_assignments" in r for r in recs)
    both = [r for r in recs if r["chunks"] and r["ssm_rows"] > r["chunks"]]
    alone = [r for r in recs if r["chunks"] and r["ssm_rows"] == r["chunks"]]
    assert both and alone
    assert all(r["moe_shared_assignments"] == r["moe_assignments"] > 0
               and r["ssm_passes"] == 1 for r in both)
    assert all(r["ssm_passes"] == 1 for r in alone)
    assert all(r["moe_shared_assignments"] == 0 for r in recs
               if r not in both)
    assert any(r["moe_assignments"] for r in recs if r not in both)
