"""A llama configuration traces what it traced.

The step programs of the benchmark's two llama configurations (their
``tiny`` sections, on the CPU) lower to the text they lowered to on the
commit named below, and ``precompile_chunked`` visits as many variants.  A
new architecture is reached by branches on static configuration that a
llama model never takes: it may add nothing to the operands, the pytrees or
the outputs of these programs (PR 29 did, and every llama cell paid 28 s of
set-up for it).

A PR that MEANS to change the llama programs runs
``ZOO_PRINT_FROZEN=1 python -m pytest tests/test_llama_programs_frozen.py -s``,
records the values anew with its parent's commit, and says so in CHANGES.md.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

# recorded on the untouched tree of this commit (PR 30's parent)
PARENT = "cb29c0420cb65563c188c20cb2607b2b3fe3adcd"
FROZEN = {
    "qwen2.5-1.5b": {
        "decode": "dfa3939d4bae80739e93cfb146213028"
                  "d4e5a3cb45df068a5ccd767e2f96390e",
        "chunk": "4f2fc75de98aa78dfcd833efbf612f61"
                 "4b64fc520866a888208498a90c042ebb",
        "fused": "d7b76b291d070d0ee33631c578bbc8ce"
                 "d0e6b2b35f31ebb829c9d1fbbfbcfa6a",
        "variants": 24},
    "mistral-7b-v0.3-l16": {
        "decode": "901c82f732885dd00b07011b6715269e"
                  "bb3212f27beda0469da8d466fd8d21b7",
        "chunk": "3f06f1d73ba0616c1b25b8babbab7f02"
                 "1889b470912a00468bcc7cfd113187b8",
        "fused": "f0b21c563e87ea4c36f3721a79562327"
                 "138a73ba4176dc689aea7b56023b4701",
        "variants": 24},
}


def _tiny_cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    tiny = cfg["tiny"]
    out = dict(cfg)
    for k, v in tiny.items():
        out[k] = {**cfg[k], **v} if isinstance(v, dict) and isinstance(
            cfg.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def engines():
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    sys.path.insert(0, BENCH)
    try:
        from families.llama import model as llama
    finally:
        sys.path.remove(BENCH)
    made = {}
    for name in FROZEN:
        cfg = _tiny_cfg(name)
        model = llama.build(cfg)
        variables = jax.tree.map(
            lambda s: jnp.zeros(s.shape, jnp.bfloat16),
            jax.eval_shape(model.init, jax.random.key(0),
                           np.zeros((1, 8), np.int32)))
        eng, gen = cfg["engine"], cfg["generator"]
        made[name] = (cfg, ContinuousEngine(
            model, variables, max_new_tokens=gen["max_new_tokens"],
            max_slots=eng["engine_slots"],
            prompt_buckets=tuple(gen["prompt_buckets"]),
            kernel=eng["engine_kernel"], kv_dtype=eng["engine_kv_dtype"],
            paged=eng["engine_paged"], block_size=eng["engine_block_size"],
            chunked=eng["engine_chunked"],
            tick_token_budget=eng["engine_tick_token_budget"]))
    return made


def _lowered_sha(eng, program):
    """The lowered text of one step program on abstract operands, the way
    ``paged_step_memory`` lowers it."""
    spec = jax.ShapeDtypeStruct
    S, kb, Cb = eng._S, 1, eng._chunk_buckets[-1]
    like = lambda a: spec(a.shape, a.dtype)
    pk = jax.tree_util.tree_map(like, eng._pk)
    pv = jax.tree_util.tree_map(like, eng._pv)
    rows = (spec((S,), jnp.int32), spec((S,), jnp.int32),
            spec((S,), jnp.bool_), spec((S, eng._M), jnp.int32),
            spec((S,), jnp.float32), spec((S,), jnp.uint32),
            spec((S,), jnp.float32))
    if program == "decode":
        lowered = eng._get_step(1, False).lower(pk, pv, *rows)
    else:
        lowered = eng._get_fused(program == "fused", False, False).lower(
            pk, pv, *rows, spec((kb, Cb), jnp.int32),
            spec((kb,), jnp.int32), spec((kb,), jnp.int32),
            spec((kb, eng._M), jnp.int32), spec((kb,), jnp.float32),
            spec((kb,), jnp.uint32), spec((kb,), jnp.float32))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def _variants(eng, cfg):
    """How many (program, shape) variants ``precompile_chunked`` visits,
    with the programs themselves replaced by a stub: nothing compiles."""
    real = eng._get_fused
    eng._get_fused = lambda *a, **k: (lambda *args: 0)
    try:
        return eng.precompile_chunked(
            max_chunk_rows=(cfg.get("warm") or {}).get("max_chunk_rows"))
    finally:
        eng._get_fused = real


@pytest.mark.parametrize("program", ["decode", "chunk", "fused"])
@pytest.mark.parametrize("name", sorted(FROZEN))
def test_step_program_lowers_to_the_parents_text(engines, name, program):
    _, eng = engines[name]
    got = _lowered_sha(eng, program)
    if os.environ.get("ZOO_PRINT_FROZEN"):
        print(f"FROZEN {name} {program} {got}")
    assert got == FROZEN[name][program], (
        f"the {program} step program of the tiny {name} configuration no "
        f"longer lowers to the text it had at {PARENT[:7]}: something was "
        f"added to a path every llama model takes")


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_precompile_grid_is_the_parents(engines, name):
    cfg, eng = engines[name]
    got = _variants(eng, cfg)
    if os.environ.get("ZOO_PRINT_FROZEN"):
        print(f"FROZEN {name} variants {got}")
    assert got == FROZEN[name]["variants"]


def test_llama_kernels_file_is_untouched_by_the_sparse_path():
    """A Mosaic kernel's serialized body carries the file and line of every
    operation, and JAX's compile-cache key keeps them: a line inserted above
    a kernel in ops/flash_attention.py is another program to the cache,
    though not to the chip.  So the sparse-attention path lives in a module
    of its own, and the llama kernels' module does not import it."""
    import importlib

    fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")
    sa = importlib.import_module("analytics_zoo_tpu.ops.sparse_attention")
    for name in ("IndexedKeys", "index_scores", "topk_mask",
                 "paged_sparse_attention", "paged_index_update"):
        assert hasattr(sa, name) and not hasattr(fa, name)
    with open(fa.__file__) as f:
        assert "sparse_attention" not in f.read()
