"""Fused Pallas paged-attention kernel + int8 KV blocks: parity suite.

The decode hot path now has two implementations of ``paged_attention``
(ops/flash_attention.py) — the materialising ``jnp.take`` gather
(CPU/reference) and the fused Pallas kernel streaming KV blocks
HBM→VMEM behind block-table indirection — plus an int8 storage mode
(``QuantKV``: per-row scales, quantize-on-write / dequantize-on-read).
Contracts pinned here:

- op-level: fused (Pallas interpret mode on this CPU host) matches
  gather on the same pool for MHA, GQA, multi-token queries, ragged
  positions, and int8 pools;
- quantization: round-trip error is bounded by the per-row scale
  (amax/127), all-zero rows are exact, and the stored (data, scale)
  pair reads back identically on both kernels;
- engine-level: greedy decode is TOKEN-IDENTICAL between
  ``kernel="gather"`` and ``kernel="fused"`` for every {paged,
  chunked, speculative} combination, and int8 storage preserves the
  f32 argmax (token-identical on this peaked-free tiny model);
- accounting: ``block_bytes`` gives int8 >= 1.9x the blocks of bf16
  at equal HBM for D=64, and the knobs validate eagerly.

Compile-heavy engine sweeps (the speculative combinations) ride the
``slow`` lane like test_spec_composed.py; `make serve-smoke` runs this
file unfiltered.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# the ops package re-exports the flash_attention *function*, which
# shadows the submodule attribute — fetch the module from sys.modules
import importlib

fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")
from analytics_zoo_tpu.models.lm import TransformerLM
from analytics_zoo_tpu.serving.continuous import ContinuousEngine
from analytics_zoo_tpu.serving.paged_cache import (BlockPool,
                                                   block_bytes)


# ---------------------------------------------------------------------------
# op-level: fused kernel vs gather reference
# ---------------------------------------------------------------------------

def _pool_case(B=2, S=1, H=4, KH=2, D=16, bs=4, M=5, seed=0,
               int8=False, pos=None, empty=()):
    """A filled pool + valid tables/pos: every row owns M distinct
    physical blocks (ids 1..B*M — block 0 stays the garbage sink),
    pos is ragged so masking frontiers differ per row (or is the
    ``pos`` given).  A row in ``empty`` is a slot that holds nothing,
    as the engine leaves it: table all sink, pos 0."""
    rng = np.random.default_rng(seed)
    N = B * M + 1
    ks = jax.random.split(jax.random.key(seed), 3)
    pk = jax.random.normal(ks[0], (N, KH, bs, D), jnp.float32)
    pv = jax.random.normal(ks[1], (N, KH, bs, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    tables = 1 + np.arange(B * M).reshape(B, M)
    maxp = M * bs - S
    if pos is None:
        pos = rng.integers(0, maxp + 1, B)
    pos = np.asarray(pos, np.int32)
    for b in empty:
        tables[b], pos[b] = 0, 0
    if int8:
        pk = fa.QuantKV(*fa.quantize_kv(pk))
        pv = fa.QuantKV(*fa.quantize_kv(pv))
    return q, pk, pv, jnp.asarray(tables, jnp.int32), jnp.asarray(pos)


def _assert_fused_matches_gather(case, **fused_kw):
    q, pk, pv, tables, pos = case
    ref = fa.paged_attention(q, pk, pv, tables, pos, kernel="gather")
    if fused_kw:    # the private entry: the walk chosen by hand
        out = fa._paged_attention_fused(q, pk, pv, tables, pos,
                                        interpret=True, **fused_kw)
    else:
        out = fa.paged_attention(q, pk, pv, tables, pos, kernel="fused",
                                 interpret=True)
    assert out.dtype == ref.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# rows at the first position, the last of block 0, the first of block 1
# and the table's very last position, in ONE call (bs = 4, M = 5)
_RAGGED = dict(B=4, pos=[0, 3, 4, 19])

_SHAPES = {
    "mha": dict(H=4, KH=4, S=1),
    "gqa2": dict(H=4, KH=2, S=1),
    "mqa": dict(H=4, KH=1, S=1),
    "gqa2-s5": dict(H=4, KH=2, S=5),
    "ragged-frontiers": dict(H=4, KH=2, S=1, **_RAGGED),
    "ragged-frontiers-s3": dict(H=4, KH=2, S=3, B=4, pos=[0, 1, 2, 17]),
    "empty-slot-beside-full": dict(H=4, KH=2, S=1, B=3, pos=[19, 0, 7],
                                   empty=(1,)),
    "all-slots-empty": dict(H=4, KH=2, S=1, B=2, empty=(0, 1)),
    "kh1-g4": dict(H=4, KH=1, S=1, **_RAGGED),
    "kh1-g6-s3": dict(H=6, KH=1, S=3),
    "kh2-g6": dict(H=12, KH=2, S=1, **_RAGGED),
    "kh2-g1-s3": dict(H=2, KH=2, S=3),
    "kh8-g1": dict(H=8, KH=8, S=1, **_RAGGED),
    "kh8-g4": dict(H=32, KH=8, S=1, **_RAGGED),
    "kh8-g4-s3": dict(H=32, KH=8, S=3),
    # a prefill chunk on a narrow table: one row mid-table, one at 0
    "chunk-narrow-table": dict(H=12, KH=2, S=16, bs=8, M=4, B=2,
                               pos=[9, 0]),
    "one-column-table": dict(H=4, KH=2, S=1, M=1, B=2, pos=[0, 3]),
}


@pytest.mark.parametrize("shape", list(_SHAPES))
def test_fused_matches_gather(shape):
    _assert_fused_matches_gather(_pool_case(**_SHAPES[shape]))


@pytest.mark.parametrize("shape", ["gqa2", "ragged-frontiers",
                                   "empty-slot-beside-full", "kh8-g4-s3",
                                   "chunk-narrow-table"])
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_fused_narrow_heads_walk_matches_gather(shape, int8):
    """The walk by BlockSpecs that a compiled kernel takes for heads
    under a lane tile wide (interpreted here, where the choice has to be
    made by hand): the same results, a dead column skipped."""
    _assert_fused_matches_gather(_pool_case(int8=int8, **_SHAPES[shape]),
                                 looped=False)


@pytest.mark.parametrize("case", [
    dict(S=1), dict(S=3),
    dict(S=1, **_RAGGED),
    dict(S=3, B=4, pos=[0, 1, 2, 17]),
    dict(S=1, B=3, pos=[19, 0, 7], empty=(1,)),
    dict(S=1, H=32, KH=8, **_RAGGED),
], ids=["s1", "s3", "ragged-frontiers", "ragged-frontiers-s3",
        "empty-slot-beside-full", "kh8-g4-ragged"])
def test_fused_matches_gather_int8(case):
    """Both kernels read the SAME stored (int8, scale) pairs, so their
    outputs agree to float tolerance — and argmax over a vocab-sized
    projection agrees exactly with the f32 pool's (the greedy-decode
    criterion, checked end-to-end below)."""
    _assert_fused_matches_gather(_pool_case(int8=True, **case))


@pytest.mark.parametrize("name,S,H,KH,want", [
    ("qwen-decode", 1, 12, 2, 2), ("mistral-decode", 1, 32, 8, 8),
    ("mistral-verify", 5, 32, 8, 8), ("mha-32-decode", 1, 32, 32, 16),
    ("qwen-chunk", 256, 12, 2, 1), ("mistral-chunk", 256, 32, 8, 1),
])
def test_heads_per_step_rule(name, S, H, KH, want):
    """All KV heads of a block in one grid step at decode and verify
    widths, one head a step at the cells' chunk width (bs 256, D 128,
    bf16): a pure function of the static shapes."""
    SGp = -(-S * (H // KH) // 8) * 8
    hb = fa._paged_heads_per_step(SGp, KH, 256, 128, 2, 2)
    assert hb == want and KH % hb == 0


def test_fused_under_jit_decode_shape():
    """The S=1 decode signature under jit — the shape the engine's
    step program traces."""
    q, pk, pv, tables, pos = _pool_case(S=1)
    f = jax.jit(lambda *a: fa.paged_attention(
        *a, kernel="fused", interpret=True))
    out = f(q, pk, pv, tables, pos)
    ref = fa.paged_attention(q, pk, pv, tables, pos, kernel="gather")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_rejects_unknown_kernel():
    q, pk, pv, tables, pos = _pool_case()
    with pytest.raises(ValueError, match="kernel"):
        fa.paged_attention(q, pk, pv, tables, pos, kernel="mkl")


# ---------------------------------------------------------------------------
# op-level under a tensor-parallel mesh: the fused kernel reads a
# tp-SHARDED pool per-chip via shard_map (kv-heads grid dim shrinks
# tp-fold), int8 scales sharded on the same kv-heads axis
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tp2_mesh():
    from analytics_zoo_tpu.parallel.mesh import make_mesh
    return make_mesh(axes={"dp": -1, "tp": 2})


def _shard_pool(pool, mesh):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if isinstance(pool, fa.QuantKV):
        return fa.QuantKV(
            jax.device_put(pool.data,
                           NamedSharding(mesh, P(None, "tp", None,
                                                 None))),
            jax.device_put(pool.scale,
                           NamedSharding(mesh, P(None, "tp", None))))
    return jax.device_put(pool,
                          NamedSharding(mesh, P(None, "tp", None,
                                                None)))


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_fused_tp_sharded_pool_matches(tp2_mesh, int8):
    """Fused on a tp-sharded pool: BITWISE-equal to the single-chip
    fused kernel (each chip computes its own kv heads' fold with the
    identical per-head program) and gather-close like the solo path."""
    q, pk, pv, tables, pos = _pool_case(S=3, int8=int8)
    solo = fa.paged_attention(q, pk, pv, tables, pos, kernel="fused",
                              interpret=True)
    ref = fa.paged_attention(q, pk, pv, tables, pos, kernel="gather")
    out = fa.paged_attention(q, _shard_pool(pk, tp2_mesh),
                             _shard_pool(pv, tp2_mesh), tables, pos,
                             kernel="fused", interpret=True,
                             mesh=tp2_mesh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(solo))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fused_tp_replicated_hatch_and_divisibility(tp2_mesh):
    """KH % tp != 0 (MQA, KH=1 under tp=2): kv_sharded=True is a loud
    error (the pool CANNOT shard that way), and kv_sharded=False — the
    replicated-pool hatch the engine takes — computes the full
    attention redundantly per chip, bitwise-equal to one chip."""
    q, pk, pv, tables, pos = _pool_case(H=4, KH=1)
    solo = fa.paged_attention(q, pk, pv, tables, pos, kernel="fused",
                              interpret=True)
    with pytest.raises(ValueError, match="divisible"):
        fa.paged_attention(q, pk, pv, tables, pos, kernel="fused",
                           interpret=True, mesh=tp2_mesh)
    out = fa.paged_attention(q, pk, pv, tables, pos, kernel="fused",
                             interpret=True, mesh=tp2_mesh,
                             kv_sharded=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(solo))


# ---------------------------------------------------------------------------
# quantization: round-trip bounds + pytree behavior + write path
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.key(3), (5, 7, 16), jnp.float32)
    qd, sc = fa.quantize_kv(x)
    assert qd.dtype == jnp.int8 and sc.dtype == fa.KV_SCALE_DTYPE
    deq = fa.dequantize_kv(qd, sc)
    # symmetric rounding: error per element <= half a quantization
    # step (the bf16-stored scale), plus bf16 slop on the scale itself
    step = np.asarray(sc, np.float32)[..., None]
    err = np.abs(np.asarray(deq) - np.asarray(x))
    assert (err <= 0.5 * step + 1e-6).all(), err.max()


def test_quantize_zero_rows_exact():
    x = jnp.zeros((3, 4, 8), jnp.float32)
    qd, sc = fa.quantize_kv(x)
    assert (np.asarray(qd) == 0).all()
    assert (np.asarray(sc, np.float32) == 1.0).all()
    assert (np.asarray(fa.dequantize_kv(qd, sc)) == 0.0).all()


def test_quantkv_is_a_pytree():
    pool = fa.QuantKV(jnp.zeros((4, 2, 4, 8), jnp.int8),
                      jnp.ones((4, 2, 4), fa.KV_SCALE_DTYPE))
    leaves, treedef = jax.tree_util.tree_flatten(pool)
    assert len(leaves) == 2
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, fa.QuantKV)
    out = jax.jit(lambda p: p)(pool)        # threads through jit whole
    assert isinstance(out, fa.QuantKV)
    assert out.shape == pool.shape and out.dtype == jnp.int8
    layer = pool[1]                          # per-layer indexing
    assert isinstance(layer, fa.QuantKV)
    assert layer.data.shape == (2, 4, 8)


def test_paged_kv_update_int8_roundtrip_and_limit():
    """Quantize-on-write: rows land as (int8, scale) pairs whose
    dequantization equals quantize∘dequantize of the input; positions
    >= limit are dropped outright (the chunked-prefill guard)."""
    N, KH, bs, D, B, S = 7, 2, 4, 8, 2, 3
    pool = fa.QuantKV(jnp.zeros((N, KH, bs, D), jnp.int8),
                      jnp.ones((N, KH, bs), fa.KV_SCALE_DTYPE))
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([0, 5], jnp.int32)
    new_k = jax.random.normal(jax.random.key(0), (B, S, KH, D),
                              jnp.float32)
    new_v = jax.random.normal(jax.random.key(1), (B, S, KH, D),
                              jnp.float32)
    limit = jnp.asarray([2, 99], jnp.int32)   # row 0: drop its 3rd row
    pk, pv = fa.paged_kv_update(pool, pool, tables, pos, new_k, new_v,
                                limit=limit)
    assert isinstance(pk, fa.QuantKV)

    def stored(pool_q, b, p):
        blk = int(tables[b, p // bs])
        return fa.dequantize_kv(pool_q.data[blk, :, p % bs],
                                pool_q.scale[blk, :, p % bs])

    exp_k = fa.dequantize_kv(*fa.quantize_kv(new_k))
    np.testing.assert_array_equal(np.asarray(stored(pk, 0, 0)),
                                  np.asarray(exp_k[0, 0]))
    np.testing.assert_array_equal(np.asarray(stored(pk, 1, 6)),
                                  np.asarray(exp_k[1, 1]))
    # row 0 position 2 >= limit 2: dropped — still the zero-init pool
    assert (np.asarray(pk.data[int(tables[0, 0]), :, 2]) == 0).all()
    exp_v = fa.dequantize_kv(*fa.quantize_kv(new_v))
    np.testing.assert_array_equal(np.asarray(stored(pv, 0, 1)),
                                  np.asarray(exp_v[0, 1]))


def test_block_bytes_accounting():
    # the headline ratio at D=64: (2*64)/(64+2) = 1.94x blocks/HBM
    bf16 = block_bytes(4, 16, 2, 64, "bf16")
    int8 = block_bytes(4, 16, 2, 64, "int8")
    assert bf16 / int8 >= 1.9
    assert bf16 == 2 * 4 * 16 * 2 * 128
    assert int8 == 2 * 4 * 16 * 2 * 66
    with pytest.raises(ValueError, match="kv_dtype"):
        block_bytes(4, 16, 2, 64, "fp8")
    pool = BlockPool(4, 2, kv_dtype="int8", bytes_per_block=int8)
    m = pool.metrics()
    assert m["kv_dtype"] == "int8" and m["bytes_per_block"] == int8
    with pytest.raises(ValueError, match="kv_dtype"):
        BlockPool(4, 2, kv_dtype="fp8")


# ---------------------------------------------------------------------------
# engine-level: greedy token parity across composed modes
# ---------------------------------------------------------------------------

def _tiny_lm(**kw):
    cfg = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
               intermediate_size=64, max_position=64,
               num_kv_heads=2, dtype=jnp.float32)
    cfg.update(kw)
    return TransformerLM(**cfg)


@pytest.fixture(scope="module")
def lm():
    model = _tiny_lm()
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


@pytest.fixture(scope="module")
def draft():
    model = _tiny_lm(hidden_size=16, num_heads=2, num_kv_heads=1,
                     num_layers=1, intermediate_size=32)
    variables = model.init(jax.random.key(9),
                           np.zeros((1, 8), np.int32))
    return model, variables


MODES = {
    "paged": dict(paged=True, block_size=4),
    "paged-chunked": dict(paged=True, block_size=4, chunked=True,
                          tick_token_budget=16),
    "spec-paged": dict(paged=True, block_size=4, _spec=True),
    "spec-paged-chunked": dict(paged=True, block_size=4, chunked=True,
                               tick_token_budget=16, _spec=True),
}

_PROMPTS = {
    "a": np.asarray([3, 7, 2, 9, 11], np.int32),
    "b": np.asarray([5, 1, 8], np.int32),
    "c": np.asarray([4, 4, 6, 2, 9, 13, 1, 7, 2, 30, 21, 17],
                    np.int32),
}


def _run_engine(lm, draft, mode, **knobs):
    model, variables = lm
    kw = dict(MODES[mode])
    if kw.pop("_spec", False):
        dm, dvv = draft
        kw.update(draft_model=dm, draft_variables=dvv, speculation_k=2)
    eng = ContinuousEngine(model, variables, max_new_tokens=5,
                           max_slots=2, prompt_buckets=(8, 16),
                           **kw, **knobs)
    out = {}
    for uri, p in _PROMPTS.items():
        eng.submit(uri, p,
                   on_done=lambda u, t: out.__setitem__(u, t))
    eng.drain()
    return {u: [int(t) for t in toks] for u, toks in out.items()}, eng


@pytest.mark.parametrize("mode", [
    # the speculative compositions are compile-heavy (draft + verify
    # program families x2 engines) — slow lane, like test_spec_composed
    pytest.param(m, marks=pytest.mark.slow) if m.startswith("spec")
    else m
    for m in MODES])
def test_fused_gather_token_parity(lm, draft, mode):
    """The acceptance bar: greedy decode bitwise-identical between
    engine_kernel=gather and engine_kernel=fused (interpret mode on
    this host) for every composed mode."""
    ref, _ = _run_engine(lm, draft, mode, kernel="gather")
    out, _ = _run_engine(lm, draft, mode, kernel="fused")
    assert out == ref, (mode, out, ref)


@pytest.mark.parametrize("mode", ["paged",
                                  pytest.param(
                                      "spec-paged-chunked",
                                      marks=pytest.mark.slow)])
def test_int8_fused_gather_token_parity(lm, draft, mode):
    """int8 pools: both kernels read identical stored (data, scale)
    pairs, so greedy tokens match exactly between them too."""
    ref, _ = _run_engine(lm, draft, mode, kernel="gather",
                         kv_dtype="int8")
    out, _ = _run_engine(lm, draft, mode, kernel="fused",
                         kv_dtype="int8")
    assert out == ref, (mode, out, ref)


def test_int8_argmax_parity_vs_f32(lm, draft):
    """f32-argmax-equality for int8 storage: on this peaked-free tiny
    model the quantization error never flips the greedy pick, so the
    int8 engine emits the f32 engine's exact tokens."""
    ref, _ = _run_engine(lm, draft, "paged")
    out, _ = _run_engine(lm, draft, "paged", kv_dtype="int8")
    assert out == ref, (out, ref)


def test_engine_knob_validation(lm):
    model, variables = lm
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         kernel="fused")
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         kv_dtype="int8")
    with pytest.raises(ValueError, match="kernel"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         paged=True, kernel="mkl")
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         paged=True, kv_dtype="fp8")


def test_int8_engine_accounting_and_flight(lm, draft):
    """The billing surface: capacity_report carries the storage mode
    and per-token cost, int8 fits ~(2D)/(D+2) more blocks in the same
    bytes, and every flight tick records which kernel/kv-dtype it ran
    (the diagnostic-bundle field a regression bisect reads first)."""
    _, e16 = _run_engine(lm, draft, "paged", kv_dtype="bf16")
    _, e8 = _run_engine(lm, draft, "paged", kv_dtype="int8",
                        kernel="fused")
    r16, r8 = e16.capacity_report(), e8.capacity_report()
    assert r16["kv_dtype"] == "bf16" and r8["kv_dtype"] == "int8"
    assert r8["kernel"] == "fused"
    D = 32 // 4                              # head_dim of _tiny_lm
    ratio = r16["bytes_per_block"] / r8["bytes_per_block"]
    assert abs(ratio - 2 * D / (D + 2)) < 1e-6
    assert r8["kv_bytes_per_token"] < r16["kv_bytes_per_token"]
    ticks = e8.flight.snapshot()
    assert ticks, "flight ring empty"
    assert ticks[-1]["kernel"] == "fused"
    assert ticks[-1]["kv_dtype"] == "int8"
    assert ticks[-1]["kv_bytes_per_token"] == r8["kv_bytes_per_token"]
