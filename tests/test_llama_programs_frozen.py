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

# recorded on this commit's own tree; the parent named is the commit it
# was built on (PR 33 re-recorded them: the fused paged-attention kernel
# changed its grid, so every program that calls it lowers anew)
PARENT = "de952d8c295dcee0174301ac47fe5bb640547c69"
FROZEN = {
    "qwen2.5-1.5b": {
        "decode": "25204adf1ca2881dc9e97f3f3440d56c"
                  "394b1a63e4127ec6de0b98e413f9c798",
        "chunk": "d507eb9d216cdced7b498e61b7ee9137"
                 "676cbe9209ed3454b50ce6ca4c490ce1",
        "fused": "7f91d0b4efdb2a70e9680b5e59db9839"
                 "ec0dc5463e8d0b4298a056423c40db33",
        "variants": 24},
    "mistral-7b-v0.3-l16": {
        "decode": "2e484bd700ce99ab0ca5e3ca53a26771"
                  "55a60e8a58b1abc899d39b7099dd0fef",
        "chunk": "f6ea7c6a155a1d28f0a460a4c416007b"
                 "1ea1e3466cf5f9d17941f07039299935",
        "fused": "c0fa6b8057323a808d0df19ee86f9870"
                 "bc8ab764a0dec559024881bdb9d17bc5",
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
