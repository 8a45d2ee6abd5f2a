"""The sparse-expert, sparse-attention decoder (Keye-VL-2.0's language
model) against its plain reference, at a small size on the CPU: hidden 64,
4 layers, 8 query / 2 KV heads of 16, 8 experts 2 a token of width 32, an
indexer of 4 heads of 8, ``topk`` 16, contexts to 96, blocks of 8.

The reference is ``benchmark/families/keye/reference.py`` (float32
``jax.numpy``, imports nothing of the package); the weights are the
benchmark's seeded bf16 leaves, so both sides hold the same values."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

CFG = dict(
    name="keye-test", family="keye", vocab_size=256, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True,
    tie_word_embeddings=False, rms_norm_eps=1e-6, rope_theta=1e7,
    rope_scaling={"rope_type": "default"}, max_position_embeddings=512,
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                   indexer_num_kv_heads=1, topk=16))
TOPK, SEED, L = 16, 3, 4


@pytest.fixture(scope="module")
def fam():
    """The keye family's modules and the seeded leaves."""
    sys.path.insert(0, BENCH)
    try:
        from families.keye import model, reference
        from harness import weights
    finally:
        sys.path.remove(BENCH)
    top = weights.top(CFG, SEED)
    layers = [weights.layer(CFG, SEED, i) for i in range(L)]
    return dict(model=model, reference=reference, top=top, layers=layers)


def _variables(fam, dtype):
    params = fam["model"].place(CFG, fam["top"], lambda i: fam["layers"][i])
    return {"params": jax.tree.map(lambda a: a.astype(dtype), params)}


def _model(fam, dtype):
    return fam["model"].build(CFG).clone(dtype=dtype)


def _ref_logits(fam, toks, rows=None, cfg=CFG):
    """The reference's logits [T or len(rows), V] of one sequence."""
    R = fam["reference"]
    off = bool(cfg.get("_selection_off"))
    if off not in fam:      # one compile a sequence length, not one an op
        fam[off] = jax.jit(lambda w, x: R.layer(cfg, "sparse", None, w, x))
    x = R.embed(cfg, fam["top"], jnp.asarray(toks))
    for w in fam["layers"]:
        x = fam[off](w, x)
    rows = jnp.arange(len(toks)) if rows is None else jnp.asarray(rows)
    return np.asarray(R.logits(cfg, None, fam["top"], x, rows))


def _tokens(n, key=0):
    return np.asarray(jax.random.randint(jax.random.key(key), (n,), 1,
                                         CFG["vocab_size"]))


# ---- (a) the full forward -------------------------------------------------

def test_full_forward_equals_reference_float32(fam):
    toks = _tokens(96)
    got = _model(fam, jnp.float32).apply(_variables(fam, jnp.float32),
                                         toks[None])[0]
    # float32 on both sides: the order of rounding only (reads 4e-6)
    assert np.abs(np.asarray(got) - _ref_logits(fam, toks)).max() <= 1e-4


def test_full_forward_near_reference_bfloat16(fam):
    """bf16 activations against the float32 reference.  Before position
    topk the attention is plain causal: bf16 rounds a residual stream of
    unit elements at 2^-8 through 4 layers, 0.19 read, 0.4 allowed.  Past
    it, a query whose 16th and 17th index scores lie within bf16's rounding
    selects another position, and at this toy topk ONE position carries
    1/16 of a query's attention (1/2048 at the published size): single
    logits move by up to 0.68, so what is held is the mean |difference|
    over all logits, 0.035 read, 0.08 allowed — the dense-attention variant
    reads 0.17 (test e), a wrong cast or a missing norm more."""
    toks = _tokens(96)
    got = _model(fam, jnp.bfloat16).apply(_variables(fam, jnp.bfloat16),
                                          toks[None])[0]
    diff = np.abs(np.asarray(got, np.float32) - _ref_logits(fam, toks))
    assert diff[:TOPK].max() <= 0.4
    assert diff.mean() <= 0.08


# ---- (b) prefill in chunks, decode through the paged engine ---------------

def _engine(fam, **kw):
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    args = dict(max_new_tokens=8, max_slots=4, prompt_buckets=(16, 96),
                paged=True, block_size=8, chunked=True,
                tick_token_budget=20)
    args.update(kw)
    return ContinuousEngine(_model(fam, jnp.float32),
                            _variables(fam, jnp.float32), **args)


def _serve(eng, prompts, max_new=8):
    out = {}
    for i, p in enumerate(prompts):
        eng.submit(f"r{i}", np.asarray(p, np.int32), max_new=max_new,
                   on_done=lambda uri, toks: out.__setitem__(uri, toks))
    eng.drain()
    return [list(map(int, out[f"r{i}"])) for i in range(len(prompts))]


def _served_gap(fam, prompt, served):
    """How far each served token's logit lies under the reference's best
    at its position, over the whole prompt + served sequence."""
    seq = list(prompt) + list(served)
    rows = len(prompt) - 1 + np.arange(len(served))
    ref = _ref_logits(fam, np.asarray(seq), rows)
    return (ref.max(-1) - ref[np.arange(len(served)), served]).max()


def test_paged_chunked_engine_equals_reference(fam):
    """Contexts on both sides of topk, a preemption and a prefix-cache hit
    on the way: the index keys came back with the block."""
    shared = _tokens(40, key=7)
    prompts = [_tokens(10, 1), _tokens(40, 2), _tokens(90, 3),
               np.concatenate([shared, _tokens(30, 4)]),
               np.concatenate([shared, _tokens(20, 5)]),
               _tokens(88, 6)]
    # 30 blocks of 8 hold 240 positions: six prompts of 338 tokens and
    # their outputs do not fit at once, so rows are preempted and redone
    eng = _engine(fam, n_blocks=30)
    served = _serve(eng, prompts)
    m = eng.cache_metrics()
    assert m["preemptions"] > 0 and m["prefix_hits"] > 0, m
    for p, s in zip(prompts, served):
        assert len(s) == 8
        assert _served_gap(fam, p, s) <= 1e-4
    recs = eng.flight.snapshot()
    assert recs and all(
        {"dsa_ctx_tokens", "dsa_read_tokens", "moe_assignments",
         "moe_max_load"} <= set(r) for r in recs)
    dec = [r for r in recs if r["dsa_ctx_tokens"]]
    assert dec and all(r["dsa_read_tokens"] <= r["dsa_ctx_tokens"]
                       for r in dec)
    # past topk the decode rows read topk positions each, not the context
    assert any(r["dsa_read_tokens"] < r["dsa_ctx_tokens"] for r in dec)
    assert all(r["moe_max_load"] <= r["moe_assignments"] for r in recs)


def test_counters_count_what_was_read(fam):
    """One row decoding alone at contexts past topk: every decode tick
    reads exactly topk positions of its context, and its one token makes
    experts_per_token assignments."""
    eng = _engine(fam, chunked=False)
    prompt = _tokens(50, 9)
    _serve(eng, [prompt], max_new=5)
    dec = [r for r in eng.flight.snapshot() if r["dsa_ctx_tokens"]]
    assert [r["dsa_ctx_tokens"] for r in dec] == [51, 52, 53, 54]
    assert all(r["dsa_read_tokens"] == TOPK for r in dec)
    assert all(r["moe_assignments"] == 2 and r["moe_max_load"] == 1
               for r in dec)


def test_llama_flight_record_gains_nothing():
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving.continuous import (DSA_COUNTERS,
                                                      ContinuousEngine)

    model = TransformerLM(vocab_size=32, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64,
                          max_position=64, pos_encoding="rope")
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    eng = ContinuousEngine(model, variables, max_new_tokens=4, max_slots=2,
                           prompt_buckets=(8, 16), paged=True, block_size=8)
    eng.submit("a", np.arange(1, 7, dtype=np.int32))
    eng.drain()
    recs = eng.flight.snapshot()
    assert recs and not any(set(DSA_COUNTERS) & set(r) for r in recs)


# ---- (c) the selected sets ------------------------------------------------

def test_selected_sets_equal_the_references(fam):
    from analytics_zoo_tpu.ops.sparse_attention import (index_scores,
                                                        topk_mask)

    T = 96
    toks = _tokens(T, 11)
    model, variables = _model(fam, jnp.float32), _variables(fam, jnp.float32)
    R = fam["reference"]
    x = R.embed(CFG, fam["top"], jnp.asarray(toks))
    w0 = fam["layers"][0]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * w0["ln_attn"].astype(jnp.float32)
    keep, score = R.selection(CFG, None, w0, h)
    qi, ki, w = model.apply(
        variables, h[None], jnp.arange(T)[None],
        method=lambda m, h, p: m.layers[0].attention._index_qkw(h, p))
    got_score = index_scores(qi, w, ki, jnp.arange(T)[None])[0]
    got = np.asarray(topk_mask(got_score, TOPK))
    keep = np.asarray(keep)
    assert (got.sum(-1) == np.minimum(np.arange(T) + 1, TOPK)).all()
    agree = (got & keep).sum() / keep.sum()
    assert agree >= 0.99
    # an entry that differs lies within rounding of the k-th score
    kth = np.sort(np.where(keep, np.asarray(score), np.inf), -1)[:, :1]
    diff = got ^ keep
    assert (np.abs(np.asarray(score) - kth)[diff] <= 1e-5).all()
    # the decode path's top_k indices name the same sets
    _, sel = jax.lax.top_k(got_score[-1], TOPK)
    assert set(map(int, sel)) == set(np.flatnonzero(got[-1]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_scores_all_heads_at_once_as_the_chunk_scores_them(dtype):
    """A decode row (S = 1) scores its index heads in one matmul, a chunk
    (S > 1) one head at a time: the same scores, so the same sets."""
    from analytics_zoo_tpu.ops.sparse_attention import index_scores

    rng = np.random.default_rng(5)
    B, S, IH, ID, L = 3, 4, 4, 8, 40
    qi = jnp.asarray(rng.normal(size=(B, S, IH, ID)), dtype)
    w = jnp.asarray(rng.normal(size=(B, S, IH)), dtype)
    ki = jnp.asarray(rng.normal(size=(B, L, ID)), dtype)
    qpos = jnp.asarray(rng.integers(S, L, size=(B, 1))
                       + np.arange(S)[None], jnp.int32)
    chunk = np.asarray(index_scores(qi, w, ki, qpos))
    for s in range(S):
        one = np.asarray(index_scores(qi[:, s:s + 1], w[:, s:s + 1], ki,
                                      qpos[:, s:s + 1]))[:, 0]
        assert np.array_equal(np.isinf(one), np.isinf(chunk[:, s]))
        fin = np.isfinite(one)
        np.testing.assert_allclose(one[fin], chunk[:, s][fin], rtol=1e-5,
                                   atol=1e-6)


def test_topk_mask_is_exact_with_ties():
    from analytics_zoo_tpu.ops.sparse_attention import topk_mask

    s = jnp.asarray([[3.0, 0.0, 0.0, 1.0, 0.0, -jnp.inf, -2.0, 0.0],
                     [1.0, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf,
                      -jnp.inf, -jnp.inf, -jnp.inf]])
    got = np.asarray(topk_mask(s, 4))
    # ties at 0 go to the lower positions; -inf is never selected
    assert got.tolist() == [[1, 1, 1, 1, 0, 0, 0, 0],
                            [1, 0, 0, 0, 0, 0, 0, 0]]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 200)).astype(np.float32))
    want = np.zeros((5, 200), bool)
    np.put_along_axis(want, np.asarray(jax.lax.top_k(x, 17)[1]), True, -1)
    assert (np.asarray(topk_mask(x, 17)) == want).all()


# ---- (d) the dropless expert layer ----------------------------------------

def _moe(dtype, router_bias=None):
    from analytics_zoo_tpu.models.moe import DroplessMoE

    moe = DroplessMoE(8, 32, top_k=2, dtype=dtype)
    x = jax.random.normal(jax.random.key(1), (32, 64)).astype(dtype)
    v = moe.init(jax.random.key(0), x)
    if router_bias is not None:
        v = {"params": {**v["params"], "router":
                        v["params"]["router"] * 0 + router_bias}}
    return moe, v, x


def test_dropless_moe_row_does_not_depend_on_batchmates():
    moe, v, x = _moe(jnp.bfloat16)
    y, load = moe.apply(v, x)
    alone, _ = moe.apply(v, x[5:6])
    assert np.array_equal(np.asarray(y[5:6]), np.asarray(alone))
    assert int(load.sum()) == 32 * 2
    # float32: the same row among two different sets of 31 others
    moe, v, x = _moe(jnp.float32)
    other = jax.random.normal(jax.random.key(2), x.shape).at[5].set(x[5])
    assert np.array_equal(np.asarray(moe.apply(v, x)[0][5]),
                          np.asarray(moe.apply(v, other)[0][5]))
    assert np.abs(np.asarray(moe.apply(v, x)[0][5:6])
                  - np.asarray(moe.apply(v, x[5:6])[0])).max() <= 1e-6


def test_dropless_moe_equals_the_dense_sum_and_drops_nothing():
    moe, v, x = _moe(jnp.float32)
    p = v["params"]

    def dense(x, p):
        pr = jax.nn.softmax(x @ p["router"])
        g, c = jax.lax.top_k(pr, 2)
        g = g / g.sum(-1, keepdims=True)
        gate = jnp.zeros((x.shape[0], 8)).at[
            jnp.arange(x.shape[0])[:, None], c].set(g)
        a = jax.nn.silu(jnp.einsum("ne,xef->nxf", x, p["w_gate"])) \
            * jnp.einsum("ne,xef->nxf", x, p["w_up"])
        return jnp.einsum("nx,nxe->ne", gate,
                          jnp.einsum("nxf,xfe->nxe", a, p["w_down"]))

    y, _ = moe.apply(v, x)
    assert np.abs(np.asarray(y - dense(x, p))).max() <= 1e-5
    # a router skewed so that expert 3 gets every token (first choice):
    # all 32 are served by it, none dropped
    bias = jnp.zeros((64, 8)).at[:, 3].set(1.0)
    moe, v, x = _moe(jnp.float32, router_bias=bias)
    x = jnp.abs(x)
    y, load = moe.apply(v, x)
    assert int(load[3]) == 32 and int(load.sum()) == 64
    assert np.abs(np.asarray(y - dense(x, v["params"]))).max() <= 1e-5
    # padding is computed but not counted
    count = jnp.arange(32) < 10
    assert int(moe.apply(v, x, count)[1].sum()) == 20


# ---- (e) the comparison sees the selection --------------------------------

def test_dense_attention_reference_differs_past_topk(fam):
    toks = _tokens(4 * TOPK, 13)
    sparse = _ref_logits(fam, toks)
    dense = _ref_logits(fam, toks, cfg={**CFG, "_selection_off": True})
    diff = np.abs(sparse - dense)
    assert diff[:TOPK].max() == 0.0         # plain causal GQA
    # 1.39 and 0.17 read: over what bf16 is allowed in test a (0.08 mean)
    assert diff[2 * TOPK:].max() > 0.7 and diff.mean() > 0.12


# ---- (f) what is not supported raises -------------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(paged=False, chunked=False), "paged=False"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(kv_host_store_bytes=1 << 20), "host tier"),
    (dict(draft=True), "draft model"),
    (dict(tp=2), "tp mesh")])
def test_unsupported_engine_combinations_raise(fam, kw, needle):
    kw = dict(kw)
    if kw.pop("draft", False):
        kw.update(draft_model=_model(fam, jnp.float32),
                  draft_variables=_variables(fam, jnp.float32))
    if kw.pop("tp", 0):
        from analytics_zoo_tpu.parallel.mesh import make_mesh
        kw["mesh"] = make_mesh(axes={"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="sparse-attention indexer") as e:
        _engine(fam, **kw)
    assert needle in str(e.value)


def test_arena_decode_of_an_indexer_model_raises(fam):
    from analytics_zoo_tpu.models.lm import generate

    with pytest.raises(NotImplementedError, match="paged"):
        generate(_model(fam, jnp.float32), _variables(fam, jnp.float32),
                 jnp.ones((1, 4), jnp.int32), 2)
