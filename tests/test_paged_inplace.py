"""The paged step writes the KV pool where it lies.

Every paged step program (decode step, chunk, fused decode + chunk)
takes the donated pool ``[layers, N, KH, bs, D]``, views it once as ONE
block arena ``[layers*N, KH, bs, D]`` (models/lm.py:_flat_pools), lets
layer ``i`` read and write through ``tables + i*N``, and returns the
same buffer.  Two kinds of test hold that:

- STRUCTURAL (``memory_analysis()`` of the compiled program, CPU,
  float32 because the CPU compiler upcasts bf16 around a scatter): both
  pools aliased to the outputs and temporaries far under ONE layer's
  slice of one pool.  A slice or a stack of layers inside the program,
  or a write the compiler cannot perform in place, shows here as
  pool-sized temporaries (before the flat view: about two pools).
- HAZARDS of the flat view: a write dropped by ``limit``, a write past
  the table clamped to the sink and a padding row with an all-sink
  table, each in layer ``i``, leave layers ``i-1`` and ``i+1`` alone
  (block 0 of the next layer above all), and the pools after one step
  equal bit for bit those of a plain per-layer reference written here
  (slice a layer out, ``pool.at[phys, :, off].set``, stack the layers):
  the semantics the step had before, independent of the flat view and
  of the write's new form.
"""

import importlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.models.lm import TransformerLM, _apply_rope
from analytics_zoo_tpu.serving.continuous import ContinuousEngine

# the ops package re-exports the flash_attention *function*, which
# shadows the submodule attribute
fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")

LAYERS = 3


def _toy(dtype=jnp.float32, pos_encoding="learned"):
    model = TransformerLM(vocab_size=32, hidden_size=32,
                          num_layers=LAYERS, num_heads=2,
                          num_kv_heads=2, intermediate_size=64,
                          max_position=64, dropout=0.0, dtype=dtype,
                          pos_encoding=pos_encoding)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


# ---------------------------------------------------------------------------
# structural: no pool-sized temporary in a step program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def big_pool_engine():
    """The pool much larger than everything else in the program: 512
    blocks of 8 positions against 2 slots of at most 24 tokens."""
    model, variables = _toy()
    return ContinuousEngine(model, variables, max_new_tokens=8,
                            max_slots=2, prompt_buckets=(8, 16),
                            paged=True, block_size=8, chunked=True,
                            tick_token_budget=10, n_blocks=512)


@pytest.mark.parametrize("program", ["decode", "chunk", "fused"])
def test_step_program_updates_pool_in_place(big_pool_engine, program):
    mem = big_pool_engine.paged_step_memory(program)
    pool = mem["pool_bytes"]
    assert pool == LAYERS * 512 * 2 * 8 * 16 * 4
    # both donated pools come back as the same buffers
    assert mem["alias_bytes"] >= 2 * pool
    # before the flat view: ~2 pools (decode, chunk) and more (fused)
    assert mem["temp_bytes"] < pool // LAYERS // 4, mem


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_quantkv_step_updates_pool_in_place(program):
    """int8 data + per-row scales scatter in place too.  The scales are
    float32 here (the engine's are bf16, which the CPU compiler upcasts
    around a scatter, temporaries and all)."""
    model, variables = _toy()
    N, KH, bs, D, B, M, S = 512, 2, 8, 16, 2, 3, 8

    def pool():
        return fa.QuantKV(
            jax.ShapeDtypeStruct((LAYERS, N, KH, bs, D), jnp.int8),
            jax.ShapeDtypeStruct((LAYERS, N, KH, bs), jnp.float32))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if program == "decode":
        def fn(v, pk, pv, tok, pos, tables):
            return model.apply(v, tok, pk, pv, tables, pos,
                               method=TransformerLM.decode_step_paged)
        args = (i32(B), i32(B), i32(B, M))
    else:
        def fn(v, pk, pv, toks, pos, lens, tables):
            return model.apply(v, toks, pk, pv, tables, pos, lens,
                               method=TransformerLM.prefill_chunk_paged)
        args = (i32(B, S), i32(B), i32(B), i32(B, M))
    mem = jax.jit(fn, donate_argnums=(1, 2)).lower(
        variables, pool(), pool(), *args).compile().memory_analysis()
    pool_bytes = LAYERS * N * KH * bs * (D + 4)
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // LAYERS // 2, mem


# ---------------------------------------------------------------------------
# hazards of the flat view, against a plain per-layer reference
# ---------------------------------------------------------------------------

def _ref_write(pool_k, pool_v, tables, pos, new_k, new_v, limit):
    """One layer's write as the step did it before: the layer's own
    ``[N, KH, bs, D]`` pool, (block, offset) indexed around the kv-heads
    slice, the layer's own N as the drop id."""
    quant = isinstance(pool_k, fa.QuantKV)
    N, KH, bs, D = (pool_k.data if quant else pool_k).shape
    B, S = new_k.shape[:2]
    p = pos[:, None] + jnp.arange(S)[None, :]
    blk = jnp.minimum(p // bs, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables, blk, axis=1)
    if limit is not None:
        phys = jnp.where(p < limit[:, None], phys, N)
    off = p % bs

    def put(pool, rows):
        return pool.at[phys, :, off].set(rows.astype(pool.dtype),
                                         mode="drop")

    if quant:
        qk, sk = fa.quantize_kv(new_k, pool_k.scale.dtype)
        qv, sv = fa.quantize_kv(new_v, pool_v.scale.dtype)
        return (fa.QuantKV(put(pool_k.data, qk), put(pool_k.scale, sk)),
                fa.QuantKV(put(pool_v.data, qv), put(pool_v.scale, sv)))
    return put(pool_k, new_k), put(pool_v, new_v)


def _ref_step(model, variables, toks, pools_k, pools_v, tables, pos,
              limit):
    """``verify_hidden_paged`` layer by layer: slice the layer's pool
    out, write, read (the gather reference), stack the layers back."""

    def fn(m):
        S = toks.shape[1]
        x = m.embed(toks)
        if m.pos_embed is not None:
            x = x + m.pos_embed(pos[:, None] + jnp.arange(S)[None, :])
        x = x.astype(m.dtype)
        ks, vs = [], []
        for i, layer in enumerate(m.layers):
            att = layer.attention
            h = layer.ln_attn(x).astype(layer.dtype)
            q, k, v = att.query(h), att.key(h), att.value(h)
            if att.pos_encoding == "rope":
                p = pos[:, None] + jnp.arange(S)[None, :]
                q = _apply_rope(q, p, att.rope_base)
                k = _apply_rope(k, p, att.rope_base)
            pk, pv = _ref_write(pools_k[i], pools_v[i], tables, pos,
                                k, v, limit)
            o = fa.paged_attention(q, pk, pv, tables, pos,
                                   kernel="gather")
            x = x + att.attn_out(o.astype(att.dtype))
            x = x + layer._mlp(layer.ln_ffn(x).astype(layer.dtype),
                               False)
            ks.append(pk)
            vs.append(pv)

        def stack(xs):
            return jax.tree_util.tree_map(lambda *l: jnp.stack(l), *xs)

        return m.ln_f(x), stack(ks), stack(vs)

    return nn.apply(fn, model)(variables)


N_BLOCKS, KH, BS, D = 6, 2, 8, 16


def _filled_pools(int8, dtype):
    """Random pools, so that any stray write shows, the sinks too."""
    shape = (LAYERS, N_BLOCKS, KH, BS, D)
    kk, kv = jax.random.split(jax.random.key(3))
    pk = jax.random.normal(kk, shape, jnp.float32)
    pv = jax.random.normal(kv, shape, jnp.float32)
    if int8:
        return (fa.QuantKV(*fa.quantize_kv(pk)),
                fa.QuantKV(*fa.quantize_kv(pv)))
    return pk.astype(dtype), pv.astype(dtype)


# each case: tables [B, M], pos, S, lens (None = no limit) and a live
# block that row 0 writes
HAZARDS = {
    # row 0's chunk is 3 real tokens of 6: positions 7, 8, 9 dropped by
    # the limit.  With this narrow table 8 and 9 would clamp to column
    # 0 = live block 2; with a layer's own N for the drop id in the
    # flat view all three would land in block 0 of the NEXT layer.
    "limit_drop": dict(tables=[[2], [4]], pos=[4, 0], S=6,
                       lens=[3, 6], live=2),
    # no limit (the speculative verify): row 0 writes positions 13..18,
    # its blocks end at 16, so 16..18 go through the last column, which
    # the allocator keeps at the sink (block 0 of THIS layer)
    "clamp_to_sink": dict(tables=[[2, 3, 0], [4, 5, 0]], pos=[13, 2],
                          S=6, lens=None, live=3),
    # row 1 is a padding row: an all-sink table, its one write lands in
    # this layer's sink
    "padding_row": dict(tables=[[2, 3], [0, 0]], pos=[5, 0], S=6,
                        lens=[6, 1], live=2),
}


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("hazard", sorted(HAZARDS))
def test_flat_view_hazards_match_per_layer_reference(hazard, kv):
    case = HAZARDS[hazard]
    int8 = kv == "int8"
    model, variables = _toy(jnp.bfloat16, pos_encoding="rope")
    pk0, pv0 = _filled_pools(int8, jnp.bfloat16)
    tables = jnp.asarray(case["tables"], jnp.int32)
    pos = jnp.asarray(case["pos"], jnp.int32)
    B, S = len(case["pos"]), case["S"]
    toks = jnp.asarray(
        np.random.default_rng(1).integers(1, 32, (B, S)), jnp.int32)
    limit = (None if case["lens"] is None
             else pos + jnp.asarray(case["lens"], jnp.int32))

    h, pk, pv = model.apply(variables, toks, pk0, pv0, tables, pos,
                            limit=limit,
                            method=TransformerLM.verify_hidden_paged)
    rh, rk, rv = _ref_step(model, variables, toks, pk0, pv0, tables,
                           pos, limit)

    def leaves(pool):
        return [np.asarray(a.astype(jnp.float32))
                for a in jax.tree_util.tree_leaves(pool)]

    for got, want in zip(leaves(pk) + leaves(pv),
                         leaves(rk) + leaves(rv)):
        assert got.shape[:2] == (LAYERS, N_BLOCKS)
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(h.astype(jnp.float32)),
                                  np.asarray(rh.astype(jnp.float32)))

    # and, said directly: in EVERY layer only the blocks the tables name
    # changed; every other block, the neighbours' block 0 above all,
    # holds what it held
    named = set(np.asarray(tables).ravel().tolist())
    if hazard == "limit_drop":
        assert 0 not in named       # nothing may reach any sink
    for before, after in zip(leaves(pk0) + leaves(pv0),
                             leaves(pk) + leaves(pv)):
        for layer in range(LAYERS):
            for blk in range(N_BLOCKS):
                same = np.array_equal(before[layer, blk],
                                      after[layer, blk])
                if blk not in named:
                    assert same, (hazard, layer, blk)
    # the step did write: every layer's live block of row 0 changed
    before, after = leaves(pk0)[0], leaves(pk)[0]
    for layer in range(LAYERS):
        assert not np.array_equal(before[layer, case["live"]],
                                  after[layer, case["live"]])


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_step_matches_per_layer_reference(kv):
    """S = 1, no limit: the decode step, one row a padding row."""
    int8 = kv == "int8"
    model, variables = _toy(jnp.bfloat16, pos_encoding="rope")
    pk0, pv0 = _filled_pools(int8, jnp.bfloat16)
    tables = jnp.asarray([[2, 3], [0, 0]], jnp.int32)
    pos = jnp.asarray([9, 0], jnp.int32)
    tok = jnp.asarray([5, 7], jnp.int32)
    _, pk, pv = model.apply(variables, tok, pk0, pv0, tables, pos,
                            method=TransformerLM.decode_step_paged)
    _, rk, rv = _ref_step(model, variables, tok[:, None], pk0, pv0,
                          tables, pos, None)
    for got, want in zip(jax.tree_util.tree_leaves((pk, pv)),
                         jax.tree_util.tree_leaves((rk, rv))):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
