"""The gated delta-rule / latent-attention decoder (Ling 3.0,
``bailing_hybrid``) against its plain reference, at a small size on the
CPU: hidden 64, two periods of (KDA, KDA, MLA) with the first layer dense,
4 KDA heads of 16, MLA with 4 heads of nope 16 + rope 8, value 16 and a
latent of 32, 32 experts in 4 groups (2 kept) of which 8 are held, 3 a
token, beside a shared expert; contexts to 100, latent blocks of 8.

The reference is ``benchmark/families/bailing_hybrid/reference.py`` (float32
``jax.numpy``: the delta rule one position at a time, MLA materialised a
head, the router a loop over groups; it imports nothing of the package); the
weights are the benchmark's seeded bf16 leaves, so both sides hold the same
values.  Logits have unit spread here (an untied head of N(0, 1/hidden)), so
float32 rounding reads some 1e-6 on them."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

CFG = dict(
    name="ling-test", family="bailing_hybrid", vocab_size=256,
    hidden_size=64, num_hidden_layers=6, layer_group_size=3,
    first_k_dense_replace=1, num_attention_heads=4, head_dim=16,
    num_kv_heads_for_linear_attn=0, short_conv_kernel_size=4,
    kda_lower_bound=-5, qk_nope_head_dim=16, qk_rope_head_dim=8,
    rotary_dim=8, qk_head_dim=24, v_head_dim=16, kv_lora_rank=32,
    rope_theta=6000000, num_experts=8, published={"num_experts": 32},
    n_group=4, topk_group=2, num_experts_per_tok=3,
    routed_scaling_factor=2.5, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_shared_experts=1,
    intermediate_size=96, rms_norm_eps=1e-6, max_position_embeddings=512,
    expert_swiglu_limit_list=[0] * 6, share_expert_swiglu_limit_list=[0] * 6,
    torch_dtype="float32")
for _k, _v in (("hidden_act", "silu"), ("use_bias", False),
               ("use_qkv_bias", False), ("q_lora_rank", None),
               ("tie_word_embeddings", False), ("score_function", "sigmoid"),
               ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
               ("moe_router_enable_expert_bias", True),
               ("rope_interleave", True), ("kda_safe_gate", True),
               ("no_kda_lora", True), ("use_kda_lora", False),
               ("linear_silu", True), ("use_mla_nope", False),
               ("use_nGPT", False), ("scale_router_input", False),
               ("value_norm", False), ("up_proj_norm", False),
               ("group_norm_size", 1),
               ("gated_attention_proj_granularity_type", "head_wise"),
               ("num_nextn_predict_layers", 0), ("rope_scaling", None)):
    CFG[_k] = _v
SEED, L = 3, 6
KINDS = ["kda_dense", "kda", "mla", "kda", "kda", "mla"]
COUNTERS = {"ssm_rows", "ssm_state_bytes", "ssm_chunk_tokens", "ssm_passes",
            "latent_kv_rows", "latent_kv_bytes", "moe_assignments",
            "moe_held_assignments", "moe_max_load"}


@pytest.fixture(scope="module")
def fam():
    """The family's modules, the seeded leaves and the served model."""
    sys.path.insert(0, BENCH)
    try:
        from families.bailing_hybrid import leaves, model, reference
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
                    for k in set(KINDS)}
    x = R.embed(cfg, fam["top"], jnp.asarray(toks))
    for kind, w in zip(KINDS, fam["layers"]):
        x = fam[key][kind](w, x)
    rows = jnp.arange(len(toks)) if rows is None else jnp.asarray(rows)
    return np.asarray(R.logits(cfg, None, fam["top"], x, rows))


def _tokens(n, key=0):
    return np.asarray(jax.random.randint(jax.random.key(key), (n,), 1,
                                         CFG["vocab_size"]))


def test_the_pattern_is_read_from_the_periods(fam):
    assert [fam["leaves"].kind(CFG, i) for i in range(L)] == KINDS
    assert fam["lm"].layer_types == ("kda", "kda", "mla") * 2
    assert fam["lm"].state_layers == 4 and fam["lm"].kv_layers == 2


# ---- (a) the full forward -------------------------------------------------

def test_full_forward_equals_reference_float32(fam):
    """100 positions: off the delta rule's block of 32, so the last block
    is padded; the MLA layers absorbed in the program and materialised in
    the reference.  float32 on both sides, the order of rounding only
    (reads 2e-6 at logits of unit spread)."""
    toks = _tokens(100)
    got = fam["lm"].apply(fam["variables"], toks[None])[0]
    ref = _ref_logits(fam, toks)
    assert np.abs(np.asarray(got) - ref).max() <= 1e-5


def test_the_reference_sees_a_dropped_state(fam):
    """The comparison can see the mechanism: with the delta rule's state
    zeroed every 32 positions the reference moves by many times what
    float32 rounding does, after the first boundary and not before it."""
    toks = _tokens(96, 1)
    ref = _ref_logits(fam, toks)
    cut = _ref_logits(fam, toks, cfg=dict(CFG, _state_drop=32))
    d = np.abs(ref - cut).max(-1)
    assert d[:32].max() == 0.0
    assert d[32:].max() >= 1e-2


# ---- (b) the forms of the delta rule --------------------------------------

def _kda_inputs(R, T, H, dk, dv, key=0):
    ks = jax.random.split(jax.random.key(key), 6)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = l2(jax.random.normal(ks[0], (R, T, H, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], (R, T, H, dk)))
    v = jax.random.normal(ks[2], (R, T, H, dv))
    # decays from e^-5 (the floor) to nearly 1 a position
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (R, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (R, T, H)))
    S0 = jax.random.normal(ks[5], (R, H, dk, dv))
    return S0, q, k, v, g, beta


def test_chunk_scan_equals_step_equals_sequential_scan():
    """Blocks of 8 over 40 positions, decays down to the floor of e^-5 a
    position (the pairwise form has no exponent above 0); row 1 holds 29
    real positions and 11 of padding (g = beta = 0), which advance
    nothing."""
    from analytics_zoo_tpu.ops import kda

    S0, q, k, v, g, beta = _kda_inputs(2, 40, 3, 8, 6)
    g, beta = g.at[1, 29:].set(0.0), beta.at[1, 29:].set(0.0)
    o_c, S_c = kda.kda_chunk_scan(S0, q, k, v, g, beta, block=8)
    o_s, S_s = jax.vmap(kda.kda_scan_reference)(S0, q, k, v, g, beta)
    np.testing.assert_allclose(o_c, o_s, atol=2e-6)
    np.testing.assert_allclose(S_c, S_s, atol=2e-6)
    S, outs = S0, []
    for t in range(40):
        o, S = kda.kda_step(S, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_s, atol=1e-6)
    _, S29 = kda.kda_scan_reference(S0[1], *(a[1, :29] for a in
                                             (q, k, v, g, beta)))
    np.testing.assert_allclose(S_c[1], S29, atol=2e-6)


def test_a_row_resumed_across_chunk_ticks_is_the_whole_prompt(fam):
    """The mixer over a prompt of 45 in one chunk, and over three chunk
    ticks of 16 (the last with 13 real positions and 3 of padding) carrying
    the state and the convolution's window from tick to tick."""
    lm, v = fam["lm"], fam["variables"]
    mixer_p = v["params"]["layer_1"]["kda"]
    from analytics_zoo_tpu.models.hybrid_lm import KdaMixer

    mix = KdaMixer(64, 4, 16, 4, -5.0, 1e-6, jnp.float32)
    u = jax.random.normal(jax.random.key(4), (1, 48, 64))
    geo = lm.state_geometry()
    S0 = jnp.zeros((1,) + geo["ssm"])
    w0 = jnp.zeros((1,) + geo["conv"])
    whole, S_w, win_w = mix.apply({"params": mixer_p}, u[:, :45], S0, w0,
                                  jnp.array([45]), method=KdaMixer.chunk)
    S, win, outs = S0, w0, []
    for t0, n in ((0, 16), (16, 16), (32, 13)):
        o, S, win = mix.apply({"params": mixer_p}, u[:, t0:t0 + 16], S,
                              win, jnp.array([n]), method=KdaMixer.chunk)
        outs.append(o[:, :n])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=1e-5)
    np.testing.assert_allclose(S, S_w, atol=1e-5)
    np.testing.assert_allclose(win, win_w, atol=0)
    # and one token at a time from there on equals the longer chunk
    o_step, S_step, _ = mix.apply({"params": mixer_p}, u[:, 45], S, win,
                                  jnp.array([True]), method=KdaMixer.step)
    longer, _, _ = mix.apply({"params": mixer_p}, u[:, :46], S0, w0,
                             jnp.array([46]), method=KdaMixer.chunk)
    np.testing.assert_allclose(o_step, longer[:, 45], atol=1e-5)


# ---- (c) latent attention, absorbed and paged ------------------------------

def test_absorbed_paged_latent_attention_equals_materialised(fam):
    """One MLA layer's mixer over the paged latent pools (blocks of 8, a
    shuffled table), a prompt of 21 in two chunks (13 + 8 of a 16-wide
    chunk: padding columns write nothing) and then two decode positions,
    against the reference's materialised heads over the whole sequence."""
    from analytics_zoo_tpu.models.hybrid_lm import LatentAttention

    R = fam["reference"]
    w = fam["layers"][2]
    p = fam["variables"]["params"]["layer_2"]["attention"]
    mla = LatentAttention(64, 4, 16, 8, 16, 32, 6e6, 1e-6, jnp.float32)
    u = jax.random.normal(jax.random.key(7), (23, 64))
    ref = R._mla(CFG, None, w, u)
    pool_c = jnp.zeros((9, 1, 8, 32))
    pool_pe = jnp.zeros((9, 1, 8, 8))
    table = jnp.array([[5, 2, 7, 1]])
    pad = lambda a, n: jnp.pad(a, ((0, n - a.shape[0]), (0, 0)))
    outs = []
    for t0, n in ((0, 13), (13, 8)):
        o, pool_c, pool_pe = mla.apply(
            {"params": p}, pad(u[t0:t0 + n], 16)[None], pool_c, pool_pe,
            table, jnp.array([t0]), jnp.array([t0 + n]), "gather",
            method=LatentAttention.paged)
        outs.append(o[0, :n])
    for t in (21, 22):
        o, pool_c, pool_pe = mla.apply(
            {"params": p}, u[t][None, None], pool_c, pool_pe, table,
            jnp.array([t]), None, "gather", method=LatentAttention.paged)
        outs.append(o[0])
    np.testing.assert_allclose(jnp.concatenate(outs), ref, atol=2e-5)
    # the sink block (0) and the blocks the table does not name hold
    # nothing: padding wrote nothing
    assert float(jnp.abs(pool_c[jnp.array([0, 3, 4, 6, 8])]).max()) == 0.0


# ---- (d) the router --------------------------------------------------------

def test_the_group_limited_sigmoid_router_equals_a_loop(fam):
    """The program's ``sigmoid_route`` against the reference's loop over
    groups: the same experts, the same gates; every chosen expert lies in
    one of the kept groups, and the bias moves the choice (not the
    gates' formula)."""
    from analytics_zoo_tpu.models.hybrid_lm import sigmoid_route

    R = fam["reference"]
    logits = jax.random.normal(jax.random.key(11), (200, 32))
    bias = 0.05 * jax.random.normal(jax.random.key(12), (32,))
    gates, chosen = sigmoid_route(logits, bias, 3, 4, 2, 2.5)
    dense = np.zeros((200, 32), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(gates), 1)
    ref = np.asarray(R.route(CFG, jax.nn.sigmoid(logits), bias))
    np.testing.assert_allclose(dense, ref, atol=1e-6)
    np.testing.assert_allclose(ref.sum(-1), 2.5, rtol=1e-6)
    groups = np.asarray(chosen) // 8
    assert all(len(set(g)) <= 2 for g in groups)
    unbiased = np.asarray(R.route(CFG, jax.nn.sigmoid(logits),
                                  jnp.zeros(32)))
    assert ((ref > 0) != (unbiased > 0)).any()


def test_the_four_expert_shares_add_up_to_the_uncut_layer(fam):
    """Experts 0-7, 8-15, 16-23 and 24-31 on four chips, the shared expert
    counted once: their sum is the uncut reference layer's expert output;
    and the program's layer, told its quarter, gives that quarter."""
    from analytics_zoo_tpu.models.hybrid_lm import HeldExperts

    R, leaves = fam["reference"], fam["leaves"]
    whole = dict(CFG, num_experts=32)
    w = {k: jax.random.normal(jax.random.key(j), shape, jnp.float32) * std
         for j, (k, (shape, _, std)) in enumerate(sorted(
             leaves.layer_leaves(whole, "kda").items()))}
    w["router"] = w["router"] * 3.0         # spread the choice
    h = jax.random.normal(jax.random.key(99), (40, 64), jnp.float32)
    routed, shared = R._experts(whole, None, w, h)
    parts = []
    for first in (0, 8, 16, 24):
        cfg = dict(CFG, first_local_expert=first)
        wq = dict(w, w_in=w["w_in"][first:first + 8],
                  w_out=w["w_out"][first:first + 8])
        part, sh = R._experts(cfg, None, wq, h)
        np.testing.assert_allclose(sh, shared, atol=1e-6)
        parts.append(part)
        mod = HeldExperts(32, 8, first, 32, 3, jnp.float32, "sigmoid", 4,
                          2, 2.5)
        got, stats = mod.apply({"params": {
            "router": w["router"], "bias": w["router_bias"],
            "w_in": wq["w_in"], "w_out": wq["w_out"]}}, h)
        np.testing.assert_allclose(got, part, atol=2e-6)
        assert int(stats[0]) == 40 * 3
    np.testing.assert_allclose(sum(parts), routed, atol=2e-6)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_the_routing_witness_sees_the_choices_and_forces_them(
        fam, monkeypatch):
    """``flips.py`` (the check by hand of the cell's limit) at this size:
    the float32 program makes the reference's choices (no swap, no gap) and
    the fp8 control reads a gap; with the bias dropped from the reference's
    router the program's own choices differ from its, and the program
    FORCED to them puts the reference's own tokens first again."""
    monkeypatch.syspath_prepend(BENCH)
    from families.bailing_hybrid import flips

    same = flips.measure(CFG, SEED, 1, 40)
    assert same["flip_share"] == 0
    assert same["gap_own"]["max"] == same["gap_forced"]["max"] == 0
    assert same["gap_control"]["max"] > 0
    R = fam["reference"]
    route = R.route
    monkeypatch.setattr(R, "route",
                        lambda cfg, s, bias: route(cfg, s, 0 * bias))
    # another key, so no layer the reference compiled with its bias is reused
    off = flips.measure(dict(CFG, _router="no bias"), SEED, 1, 40)
    assert off["flip_share"] > 0 and off["gap_own"]["max"] > 0
    assert off["gap_forced"]["max"] == 0
    assert off["gap_forced"]["argmax_share"] == 1.0


# ---- (e) prompt by chunks, decode through the engine's caches -------------

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


def test_paged_chunked_engine_equals_reference(fam):
    """Seven requests on three slots, chunks of 16 under a budget of 20:
    chunk boundaries off the delta rule's block, rows of different lengths
    in one tick, prompts that end inside a tick, padded chunk rows, and
    every slot reused by a later request whose state must start from zero.
    The prompt goes in by chunks and the answer one token a step, and the
    served tokens are the reference's own (float32)."""
    prompts = [_tokens(n, k) for n, k in PROMPTS]
    eng = _engine(fam)
    served = _serve(eng, prompts)
    for p, s in zip(prompts, served):
        assert len(s) == 8
        assert _served_gap(fam, p, s) <= 1e-5
    recs = eng.flight.snapshot()
    assert recs and all(COUNTERS <= set(r) for r in recs)
    assert any(r["chunks"] >= 2 for r in recs), "two chunk rows in a tick"
    assert sum(r["ssm_chunk_tokens"] for r in recs) == sum(
        n for n, _ in PROMPTS)
    state_row = 4 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert all(r["ssm_state_bytes"] == 2 * state_row * r["ssm_rows"]
               for r in recs)
    # the latent rows: c 32 + k_pe 8 in float32 a token, of every row's
    # table in both MLA layers a pass
    assert all(r["latent_kv_bytes"] == r["latent_kv_rows"] * 40 * 4
               for r in recs)
    assert all(r["latent_kv_rows"] % (2 * 8) == 0 and r["latent_kv_rows"]
               for r in recs)
    # 3 picks a token and expert layer (four of six), a quarter held
    assert all(r["moe_held_assignments"] <= r["moe_assignments"]
               and r["moe_max_load"] <= r["moe_held_assignments"]
               for r in recs)
    picks = sum(r["moe_assignments"] for r in recs)
    assert 0.1 <= sum(r["moe_held_assignments"] for r in recs) / picks \
        <= 0.4


def test_a_step_of_several_tokens_serves_the_same_tokens(fam):
    """``ticks_per_step`` 3: the rows advance three tokens a device call
    (the state carried through the scan), and the tokens are those of the
    one-token engine."""
    prompts = [_tokens(n, k) for n, k in PROMPTS[:4]]
    eng = _engine(fam, ticks_per_step=3, max_new_tokens=11)
    served = _serve(eng, prompts, max_new=11)
    assert served == _serve(_engine(fam, max_new_tokens=11), prompts,
                            max_new=11)
    assert {k[0] for k in eng._step_cache} == {1, 3}


def test_the_pools_hold_the_latent_and_no_head(fam):
    eng = _engine(fam)
    N = eng._pool.n_blocks
    assert eng._pk.k.shape == (2, N, 1, 8, 32)
    assert eng._pv.shape == (2, N, 1, 8, 8)
    assert [a.shape for a in eng._pk.ssm] == [(3, 4, 16, 16)] * 4
    assert [a.shape for a in eng._pk.conv] == [(3, 3, 192)] * 4
    assert eng._per_block_bytes == 2 * 8 * 40 * 4


# ---- (f) what does not compose raises, in one message ---------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(paged=False, chunked=False, enable_prefix_cache=False),
     "paged=False"),
    (dict(chunked=False), "chunked=False"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(kv_host_store_bytes=1 << 20), "host tier"),
    (dict(draft=True), "draft model"),
    (dict(tp=2), "tp mesh")])
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


def test_generate_of_a_delta_rule_model_raises(fam):
    from analytics_zoo_tpu.models.lm import generate

    with pytest.raises(NotImplementedError, match="state-space"):
        generate(fam["lm"], fam["variables"], jnp.ones((1, 4), jnp.int32), 2)


def test_one_recurrent_and_one_attention_kind_a_model(fam):
    bad = fam["lm"].clone(layer_types=("kda", "mamba", "mla"))
    with pytest.raises(ValueError, match="one recurrent kind"):
        bad.init(jax.random.key(0), np.zeros((1, 4), np.int32))
