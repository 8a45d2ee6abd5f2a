"""The hybrid state-space / attention decoder (Granite 4.0-H) against its
plain reference, at a small size on the CPU: hidden 64, two periods of
(mamba, mamba, attention), 8 state-space heads of 16 with a state of 16 in
scan blocks of 8, 8 query / 2 KV heads of 8, 8 experts of which 4 are held,
3 a token, beside a shared expert; contexts to 100, K/V blocks of 8.

The reference is ``benchmark/families/granite_hybrid/reference.py`` (float32
``jax.numpy``, the recurrence one position at a time, imports nothing of the
package); the weights are the benchmark's seeded bf16 leaves, so both sides
hold the same values."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

PATTERN = ["mamba", "mamba", "attention"] * 2
CFG = dict(
    name="granite-test", family="granite_hybrid", vocab_size=256,
    hidden_size=64, num_hidden_layers=6, layer_types=PATTERN,
    num_attention_heads=8, num_key_value_heads=2,
    attention_multiplier=0.125, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_n_groups=1, mamba_chunk_size=8, mamba_conv_bias=True,
    mamba_proj_bias=False, num_local_experts=4,
    published={"num_local_experts": 8}, num_experts_per_tok=3,
    intermediate_size=32, shared_intermediate_size=48,
    position_embedding_type="nope", tie_word_embeddings=True,
    rms_norm_eps=1e-5, max_position_embeddings=512, torch_dtype="float32")
SEED, L = 3, 6
COUNTERS = {"ssm_rows", "ssm_state_bytes", "ssm_chunk_tokens", "ssm_passes",
            "moe_assignments", "moe_held_assignments", "moe_max_load"}


@pytest.fixture(scope="module")
def fam():
    """The family's modules, the seeded leaves and the served model."""
    sys.path.insert(0, BENCH)
    try:
        from families.granite_hybrid import leaves, model, reference
        from harness import weights
    finally:
        sys.path.remove(BENCH)
    top = weights.top(CFG, SEED)
    layers = [weights.layer(CFG, SEED, i) for i in range(L)]
    params = model.place(CFG, top, lambda i: layers[i])
    return dict(
        model=model, reference=reference, leaves=leaves, top=top,
        layers=layers, lm=model.build(CFG),
        variables={"params": jax.tree.map(
            lambda a: a.astype(jnp.float32), params)})


def _ref_logits(fam, toks, rows=None, cfg=CFG):
    """The reference's logits [T or len(rows), V] of one sequence."""
    R = fam["reference"]
    key = ("ref", cfg.get("_state_drop"))
    if key not in fam:
        fam[key] = {k: jax.jit(lambda w, x, k=k: R.layer(cfg, k, None, w, x))
                    for k in ("mamba", "attention")}
    x = R.embed(cfg, fam["top"], jnp.asarray(toks))
    for kind, w in zip(PATTERN, fam["layers"]):
        x = fam[key][kind](w, x)
    rows = jnp.arange(len(toks)) if rows is None else jnp.asarray(rows)
    return np.asarray(R.logits(cfg, None, fam["top"], x, rows))


def _tokens(n, key=0):
    return np.asarray(jax.random.randint(jax.random.key(key), (n,), 1,
                                         CFG["vocab_size"]))


# ---- (a) the full forward -------------------------------------------------

def test_full_forward_equals_reference_float32(fam):
    """100 positions: off the scan's block of 8, so the last block is
    padded.  float32 on both sides, the order of rounding only (reads
    2e-7 at logits of 0.02)."""
    toks = _tokens(100)
    got = fam["lm"].apply(fam["variables"], toks[None])[0]
    ref = _ref_logits(fam, toks)
    assert np.abs(np.asarray(got) - ref).max() <= 2e-6
    # and the tied head does not answer every token with itself (at this
    # toy vocabulary the token's own row is often the largest of 256; at
    # the published 50176 rows it is one candidate: leaves.py)
    assert (ref.argmax(-1) == toks).mean() < 0.9


def test_the_reference_sees_a_dropped_state(fam):
    """The comparison can see the mechanism: with the carried state zeroed
    every 32 positions the reference moves by a hundred times what float32
    rounding does, after the first boundary and not before it."""
    toks = _tokens(96, 1)
    ref = _ref_logits(fam, toks)
    cut = _ref_logits(fam, toks, cfg=dict(CFG, _state_drop=32))
    d = np.abs(ref - cut).max(-1)
    assert d[:32].max() == 0.0
    assert d[32:].max() >= 1e-3


# ---- (b) the three forms of the recurrence --------------------------------

def test_step_equals_chunk_scan_equals_sequential_scan():
    from analytics_zoo_tpu.ops import ssm

    R, T, H, P, N = 2, 24, 4, 8, 16
    ks = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(ks[0], (R, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (R, T, H)) - 2.0)
    # row 1 holds 19 real positions: padding advances nothing
    dt = dt.at[1, 19:].set(0.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B, C = (jax.random.normal(k, (R, T, N)) for k in ks[3:5])
    D = jnp.ones((H,))
    h0 = jax.random.normal(ks[5], (R, H, P, N))
    y_chunk, h_chunk = ssm.ssm_chunk_scan(h0, x, dt, A, B, C, D, block=8)
    y_seq, h_seq = jax.vmap(
        lambda h, x, dt, B, C: ssm.ssm_scan_reference(h, x, dt, A, B, C, D)
    )(h0, x, dt, B, C)
    h, ys = h0, []
    for t in range(T):
        y, h = ssm.ssm_step(h, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        ys.append(y)
    np.testing.assert_allclose(y_chunk, y_seq, atol=2e-5)
    np.testing.assert_allclose(h_chunk, h_seq, atol=2e-5)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_seq, atol=1e-6)
    np.testing.assert_allclose(h, h_seq, atol=1e-6)
    # the padded row's state is the state after its 19th position
    _, h19 = ssm.ssm_scan_reference(h0[1], x[1, :19], dt[1, :19], A,
                                    B[1, :19], C[1, :19], D)
    np.testing.assert_allclose(h_chunk[1], h19, atol=2e-5)


def test_convolution_window_is_carried_and_padding_does_not_move_it():
    from analytics_zoo_tpu.ops import ssm

    K, C, T = 4, 6, 10
    ks = jax.random.split(jax.random.key(1), 3)
    u = jax.random.normal(ks[0], (1, T, C))
    w, b = jax.random.normal(ks[1], (K, C)), jax.random.normal(ks[2], (C,))
    zero = jnp.zeros((1, K - 1, C))
    whole, win = ssm.conv_chunk(zero, u, jnp.array([T]), w, b)
    # in two chunks of 7 (5 real, 2 padding) and 5
    first = jnp.concatenate([u[:, :5], jnp.ones((1, 2, C))], 1)
    a, win_a = ssm.conv_chunk(zero, first, jnp.array([5]), w, b)
    c, win_c = ssm.conv_chunk(win_a, u[:, 5:], jnp.array([5]), w, b)
    np.testing.assert_allclose(a[:, :5], whole[:, :5], atol=1e-6)
    np.testing.assert_allclose(c, whole[:, 5:], atol=1e-6)
    np.testing.assert_allclose(win_c, win, atol=0)
    np.testing.assert_allclose(win, u[:, T - (K - 1):], atol=0)
    # one token at a time
    win_s, outs = zero, []
    for t in range(T):
        o, win_s = ssm.conv_step(win_s, u[:, t], w, b)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), whole, atol=1e-6)
    np.testing.assert_allclose(win_s, win, atol=0)
    # a chunk shorter than the window keeps what the window held
    _, win_1 = ssm.conv_chunk(win_a, u[:, 5:7], jnp.array([1]), w, b)
    np.testing.assert_allclose(win_1, u[:, 3:6], atol=0)


# ---- (c) prompt by chunks, decode through the engine's caches -------------

def _engine(fam, **kw):
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    args = dict(max_new_tokens=8, max_slots=3, prompt_buckets=(16, 104),
                paged=True, block_size=8, chunked=True,
                tick_token_budget=20, enable_prefix_cache=False)
    args.update(kw)
    return ContinuousEngine(fam["lm"], fam["variables"], **args)


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


PROMPTS = [(10, 1), (40, 2), (99, 3), (23, 4), (16, 5), (57, 6), (5, 7)]


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_paged_chunked_engine_equals_reference(fam, kernel):
    """Seven requests on three slots, chunks of 16 under a budget of 20:
    chunk boundaries off the scan's block of 8 (a chunk beside two decode
    rows is 18 tokens), rows of different lengths in one tick, prompts that
    end inside a tick, padded chunk rows, and every slot reused by a later
    request whose state must start from zero."""
    prompts = [_tokens(n, k) for n, k in PROMPTS]
    eng = _engine(fam, kernel=kernel)
    served = _serve(eng, prompts)
    for p, s in zip(prompts, served):
        assert len(s) == 8
        # float32: the served token IS the reference's (a tie apart)
        assert _served_gap(fam, p, s) <= 2e-6
    recs = eng.flight.snapshot()
    assert recs and all(COUNTERS <= set(r) for r in recs)
    assert any(r["chunks"] >= 2 for r in recs), "two chunk rows in a tick"
    assert sum(r["ssm_chunk_tokens"] for r in recs) == sum(
        n for n, _ in PROMPTS)
    state_row = 4 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
    assert all(r["ssm_state_bytes"] == 2 * state_row * r["ssm_rows"]
               for r in recs)
    # 3 picks a token and layer; about half of them are held here
    assert all(r["moe_held_assignments"] <= r["moe_assignments"]
               and r["moe_max_load"] <= r["moe_held_assignments"]
               for r in recs)
    picks = sum(r["moe_assignments"] for r in recs)
    assert 0.35 <= sum(r["moe_held_assignments"] for r in recs) / picks \
        <= 0.65


def test_preempt_and_resume_give_the_same_tokens(fam):
    """A pool too small for three rows at once: rows are preempted and
    recomputed from their tokens, state and all."""
    prompts = [_tokens(n, k) for n, k in ((60, 11), (70, 12), (50, 13))]
    roomy = _serve(_engine(fam), prompts)
    eng = _engine(fam, n_blocks=20)
    assert _serve(eng, prompts) == roomy
    assert eng.cache_metrics()["preemptions"] > 0


def test_a_step_of_several_tokens_serves_the_same_tokens(fam):
    """``ticks_per_step`` 3: while no prompt is filling the rows advance
    three tokens a device call (a scan over the one-token step, the state
    carried through it), a row that ends inside a call drops the surplus,
    and the last tokens of a draining engine go one a call: two step
    programs, and the tokens of the one-token engine."""
    prompts = [_tokens(n, k) for n, k in PROMPTS]
    eng = _engine(fam, ticks_per_step=3, max_new_tokens=11)
    assert eng.precompile_chunked(max_chunk_rows=1) \
        == 1 + _engine(fam).precompile_chunked(max_chunk_rows=1)
    served = _serve(eng, prompts, max_new=11)
    assert served == _serve(_engine(fam, max_new_tokens=11), prompts,
                            max_new=11)
    assert {k[0] for k in eng._step_cache} == {1, 3}
    recs = eng.flight.snapshot()
    assert {r["ssm_passes"] for r in recs} == {1, 2, 3}
    assert all(r["ssm_rows"] <= 3 * r["ssm_passes"] for r in recs)


def test_llama_flight_record_gains_nothing():
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    m = TransformerLM(vocab_size=32, hidden_size=32, num_layers=2,
                      num_heads=2, intermediate_size=64, max_position=64)
    v = m.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    eng = ContinuousEngine(m, v, max_new_tokens=4, max_slots=2,
                           prompt_buckets=(8, 16), paged=True, block_size=8,
                           chunked=True)
    eng.submit("a", np.arange(1, 7, dtype=np.int32))
    eng.drain()
    assert not COUNTERS & set().union(*eng.flight.snapshot())


# ---- (d) what does not compose raises, in one message ---------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(paged=False, chunked=False, enable_prefix_cache=False),
     "paged=False"),
    (dict(chunked=False), "chunked=False"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(kv_host_store_bytes=1 << 20), "host tier"),
    (dict(draft=True), "draft model"),
    (dict(tp=2), "tp mesh"),
    (dict(elastic_pool=True), "elastic_pool")])
def test_unsupported_engine_combinations_raise(fam, kw, needle):
    kw = dict(kw)
    if kw.pop("draft", False):
        kw.update(draft_model=fam["lm"], draft_variables=fam["variables"])
    if kw.pop("tp", 0):
        from analytics_zoo_tpu.parallel.mesh import make_mesh
        kw["mesh"] = make_mesh(axes={"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="state-space layers") as e:
        _engine(fam, **kw)
    assert needle in str(e.value)


def test_arena_paths_of_a_state_space_model_raise(fam):
    from analytics_zoo_tpu.models.lm import beam_search, generate

    prompt = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="state-space"):
        generate(fam["lm"], fam["variables"], prompt, 2)
    with pytest.raises(NotImplementedError, match="state-space"):
        beam_search(fam["lm"], fam["variables"], prompt, 2)
    eng = _engine(fam)
    with pytest.raises(ValueError, match="state-space"):
        eng.register_prefix(np.arange(1, 9, dtype=np.int32))
    with pytest.raises(ValueError, match="state-space"):
        eng.submit("h", np.arange(1, 9, dtype=np.int32),
                   handoff_cb=lambda state: None)


# ---- (e) the chip's share, tied to the model ------------------------------

def test_the_two_halves_of_the_experts_add_up_to_the_uncut_layer(fam):
    """Experts 0-3 here, 4-7 on the partner, the shared expert counted
    once: their sum is the uncut reference layer's expert output; and the
    program's layer, told its half, gives that half."""
    from analytics_zoo_tpu.models.hybrid_lm import HeldExperts

    R, leaves = fam["reference"], fam["leaves"]
    whole = dict(CFG, num_local_experts=8)
    w = {k: jax.random.normal(jax.random.key(j), shape, jnp.float32) * std
         for j, (k, (shape, _, std)) in enumerate(sorted(
             leaves.layer_leaves(whole, "attention").items()))}
    h = jax.random.normal(jax.random.key(99), (40, 64), jnp.float32)
    routed, shared = R._experts(whole, None, w, h)
    halves = []
    for first in (0, 4):
        cfg = dict(CFG, first_local_expert=first)
        wh = dict(w, w_in=w["w_in"][first:first + 4],
                  w_out=w["w_out"][first:first + 4])
        part, sh = R._experts(cfg, None, wh, h)
        np.testing.assert_allclose(sh, shared, atol=1e-6)
        halves.append(part)
        mod = HeldExperts(8, 4, first, 32, 3, jnp.float32)
        got, stats = mod.apply({"params": {
            "router": w["router"], "w_in": wh["w_in"],
            "w_out": wh["w_out"]}}, h)
        np.testing.assert_allclose(got, part, atol=2e-6)
        assert int(stats[0]) == 40 * 3
    np.testing.assert_allclose(halves[0] + halves[1], routed, atol=2e-6)
    assert float(jnp.abs(halves[0]).max()) > 0 \
        and float(jnp.abs(halves[1]).max()) > 0


def test_a_held_experts_row_does_not_depend_on_its_batchmates():
    from analytics_zoo_tpu.models.hybrid_lm import HeldExperts

    mod = HeldExperts(8, 4, 2, 16, 3, jnp.float32)
    x = jax.random.normal(jax.random.key(0), (12, 32))
    v = mod.init(jax.random.key(1), x)
    alone, _ = mod.apply(v, x[:1])
    among, stats = mod.apply(v, x, jnp.arange(12) < 6)
    np.testing.assert_allclose(among[:1], alone, atol=1e-6)
    assert int(stats[0]) == 6 * 3       # padding is computed, not counted


def test_the_vocabulary_slices_logits_are_the_whole_heads_rows(fam):
    """Rows 0-127 of a 256-row tied head: the slice's logits are the same
    rows of the whole head's (the embedding's other rows are never
    touched)."""
    R = fam["reference"]
    x = jax.random.normal(jax.random.key(5), (7, 64), jnp.float32)
    rows = jnp.arange(7)
    whole = R.logits(CFG, None, fam["top"], x, rows)
    half = dict(fam["top"], embed=fam["top"]["embed"][:128])
    np.testing.assert_allclose(
        R.logits(dict(CFG, vocab_size=128), None, half, x, rows),
        whole[:, :128], atol=1e-7)
    lm = fam["lm"].clone(vocab_size=128)
    v = jax.tree.map(lambda a: a, fam["variables"])
    v["params"]["embed"] = {
        "embedding": fam["variables"]["params"]["embed"]["embedding"][:128]}
    toks = _tokens(24, 8) % 128
    np.testing.assert_allclose(
        lm.apply(v, toks[None])[0],
        fam["lm"].apply(fam["variables"], toks[None])[0][:, :128],
        atol=1e-6)
