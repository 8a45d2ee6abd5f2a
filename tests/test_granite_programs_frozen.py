"""A granite configuration traces what it traced.

The step programs of the benchmark's hybrid state-space configuration
(``granite-4.0-h-small-l10-ep2``, its ``tiny`` section, on the CPU) lower to
the text they lowered to on the commit named below, and
``precompile_chunked`` visits as many variants.  Other recurrent or latent
layer kinds reach ``models/hybrid_lm.py`` and the engine's state arena by
branches on static configuration that a granite model never takes: they may
add nothing to the operands, the pytrees or the outputs of these programs.

The form is ``tests/test_llama_programs_frozen.py``'s.  A PR that MEANS to
change granite's programs runs
``ZOO_PRINT_FROZEN=1 python -m pytest tests/test_granite_programs_frozen.py -s``,
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
NAME = "granite-4.0-h-small-l10-ep2"

# "decode", "step", "chunk" and "variants" recorded on 3bba012, before the
# second recurrence and the latent pool were added beside the granite path,
# and read again on the commit named; "fused" recorded on top of it, where
# a fused tick with decode rows became ONE pass over the weights
# (``HybridLM.fused_step_paged_ssm``)
PARENT = "09b3bc9951a4511dedd8c81190037ff65db0b2e0"
FROZEN = {
    "decode": "d2cc6d0c636f7f25f975310d80a5ca67"
              "15f5047fa21dad30b92f0d6c06d453b0",
    "step": "75daa654188a0fecc179cb5709a4c16e"
            "77bff3a521c03519743c5a994e00f4ae",
    "chunk": "fdc3c6834e2f63023f8d3eb5c5ec79ec"
             "138c9c802359c2b51b7ac42d106a0641",
    "fused": "5fc24a15f831b03174359a059f6298f8"
             "bfbd54add6e65af3037579f31ff798d6",
    "variants": 31,
}


def _tiny_cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    out = dict(cfg)
    for k, v in cfg["tiny"].items():
        out[k] = {**cfg[k], **v} if isinstance(v, dict) and isinstance(
            cfg.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def engine():
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    sys.path.insert(0, BENCH)
    try:
        from families.granite_hybrid import model as granite
    finally:
        sys.path.remove(BENCH)
    cfg = _tiny_cfg(NAME)
    model = granite.build(cfg)
    variables = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.key(0),
                       np.zeros((1, 8), np.int32)))
    eng, gen = cfg["engine"], cfg["generator"]
    return cfg, ContinuousEngine(
        model, variables, max_new_tokens=gen["max_new_tokens"],
        max_slots=eng["engine_slots"],
        prompt_buckets=tuple(gen["prompt_buckets"]),
        kernel=eng["engine_kernel"], kv_dtype=eng["engine_kv_dtype"],
        paged=eng["engine_paged"], block_size=eng["engine_block_size"],
        chunked=eng["engine_chunked"],
        enable_prefix_cache=eng["engine_prefix_cache"],
        ticks_per_step=eng["engine_ticks"],
        tick_token_budget=eng["engine_tick_token_budget"])


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
    if program in ("decode", "step"):
        n = 1 if program == "decode" else eng.ticks_per_step
        lowered = eng._get_step(n, False).lower(pk, pv, *rows)
    else:
        lowered = eng._get_fused(program == "fused", False, False).lower(
            pk, pv, *rows, spec((kb, Cb), jnp.int32),
            spec((kb,), jnp.int32), spec((kb,), jnp.int32),
            spec((kb, eng._M), jnp.int32), spec((kb,), jnp.float32),
            spec((kb,), jnp.uint32), spec((kb,), jnp.float32),
            spec((kb,), jnp.int32))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def _variants(eng, cfg):
    """How many (program, shape) variants ``precompile_chunked`` visits,
    with the programs replaced by a stub that hands the caches back:
    nothing compiles."""
    real = eng._get_fused, eng._get_step
    stub = lambda *a, **k: (lambda pk, pv, *rest: (pk, pv))
    eng._get_fused = eng._get_step = stub
    try:
        return eng.precompile_chunked(
            max_chunk_rows=(cfg.get("warm") or {}).get("max_chunk_rows"))
    finally:
        eng._get_fused, eng._get_step = real


@pytest.mark.parametrize("program", ["decode", "step", "chunk", "fused"])
def test_step_program_lowers_to_the_parents_text(engine, program):
    _, eng = engine
    got = _lowered_sha(eng, program)
    if os.environ.get("ZOO_PRINT_FROZEN"):
        print(f"FROZEN {NAME} {program} {got}")
    assert got == FROZEN[program], (
        f"the {program} step program of the tiny {NAME} configuration no "
        f"longer lowers to the text it had at {PARENT[:7]}: something was "
        f"added to a path every granite model takes")


def test_precompile_grid_is_the_parents(engine):
    cfg, eng = engine
    got = _variants(eng, cfg)
    if os.environ.get("ZOO_PRINT_FROZEN"):
        print(f"FROZEN {NAME} variants {got}")
    assert got == FROZEN["variants"]
