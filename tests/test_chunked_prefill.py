"""Chunked-prefill scheduler tests (serving/continuous.py
chunked=True): greedy AND sampled chunked output must be bitwise what
the monolithic prefill path produces (arena + paged, prefix-cached
included), a paged request whose pool dries MID-PROMPT must requeue and
later complete with identical tokens, budget validation must reject
livelock-prone configs eagerly, and the scheduler must be observable
through cache_metrics()."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.lm import TransformerLM
from analytics_zoo_tpu.serving.continuous import ContinuousEngine


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=32, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64,
                          max_position=64, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


def _collect(results):
    return lambda u, t: results.__setitem__(u, np.asarray(t))


def _run(lm, prompts, engine_kw=None, submit_kw=None):
    model, variables = lm
    kw = dict(max_new_tokens=6, max_slots=3, prompt_buckets=(4, 8, 16))
    kw.update(engine_kw or {})
    eng = ContinuousEngine(model, variables, **kw)
    out = {}
    for i, p in enumerate(prompts):
        eng.submit(f"r{i}", p, on_done=_collect(out),
                   **dict(submit_kw or {}))
    eng.drain()
    assert len(out) == len(prompts)
    return out, eng


# ---------------------------------------------------------------------------
# bitwise parity vs monolithic prefill
# ---------------------------------------------------------------------------

# lengths straddle chunk boundaries for budget=8: 12 and 15 need two
# chunks, 9 needs 8+1, the rest fit one chunk (4 under-fills a bucket)
LENGTHS = (4, 12, 7, 9, 15, 5)


@pytest.mark.parametrize("mode", ["arena", "paged"])
def test_chunked_greedy_bitwise_equals_monolithic(lm, mode):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 32, n).astype(np.int32) for n in LENGTHS]
    paged = dict(paged=True, block_size=4) if mode == "paged" else {}
    base, _ = _run(lm, prompts, engine_kw=paged)
    got, eng = _run(lm, prompts, engine_kw=dict(
        chunked=True, tick_token_budget=8, **paged))
    for k in base:
        assert np.array_equal(base[k], got[k]), k
    m = eng.cache_metrics()
    assert m["chunked"] and m["tick_token_budget"] == 8
    assert 0.0 < m["budget_utilization"] <= 1.0


@pytest.mark.parametrize("mode", ["arena", "paged"])
def test_chunked_sampled_bitwise_equals_monolithic(lm, mode):
    """The final chunk's on-device first-token pick must fold the rng
    at plen-1 exactly like monolithic admission's _pick_first."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 32, n).astype(np.int32)
               for n in (12, 7, 15)]
    skw = dict(temperature=0.8, rng_seed=123, top_p=0.9)
    paged = dict(paged=True, block_size=4) if mode == "paged" else {}
    base, _ = _run(lm, prompts, engine_kw=paged, submit_kw=skw)
    got, _ = _run(lm, prompts, engine_kw=dict(
        chunked=True, tick_token_budget=8, **paged), submit_kw=skw)
    for k in base:
        assert np.array_equal(base[k], got[k]), k


def test_chunked_max_new_one(lm):
    """A request finishing on its FIRST token (picked inside the fused
    step the tick its last chunk lands) must complete cleanly."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 32, 12).astype(np.int32)]
    base, _ = _run(lm, prompts, submit_kw=dict(max_new=1))
    got, _ = _run(lm, prompts, submit_kw=dict(max_new=1),
                  engine_kw=dict(chunked=True, tick_token_budget=8))
    assert np.array_equal(base["r0"], got["r0"])


def test_chunked_arena_prefix_bitwise(lm):
    """Chunked admission splices a registered prefix and chunks only
    the suffix — output must equal the full concatenated prompt run
    through a plain engine."""
    model, variables = lm
    rng = np.random.default_rng(11)
    pref = rng.integers(1, 32, 6).astype(np.int32)
    sufs = [rng.integers(1, 32, n).astype(np.int32) for n in (10, 3)]
    base, _ = _run(lm, [np.concatenate([pref, s]) for s in sufs])
    eng = ContinuousEngine(model, variables, max_new_tokens=6,
                           max_slots=3, prompt_buckets=(4, 8, 16),
                           chunked=True, tick_token_budget=8)
    pid = eng.register_prefix(pref)
    out = {}
    for i, s in enumerate(sufs):
        eng.submit(f"r{i}", s, on_done=_collect(out), prefix=pid)
    eng.drain()
    for k in base:
        assert np.array_equal(base[k], out[k]), k


def test_chunked_paged_prefix_sharing(lm):
    """Chunk-landed full blocks are hash-published: a second identical
    prompt must hit the prefix index and still match bitwise."""
    model, variables = lm
    rng = np.random.default_rng(13)
    p = rng.integers(1, 32, 14).astype(np.int32)
    base, _ = _run(lm, [p, p], engine_kw=dict(paged=True, block_size=4))
    eng = ContinuousEngine(model, variables, max_new_tokens=6,
                           max_slots=3, prompt_buckets=(4, 8, 16),
                           paged=True, block_size=4, chunked=True,
                           tick_token_budget=8)
    out = {}
    eng.submit("r0", p, on_done=_collect(out))
    eng.drain()                       # r0's blocks now published
    eng.submit("r1", p, on_done=_collect(out))
    eng.drain()
    assert np.array_equal(out["r0"], out["r1"])
    assert np.array_equal(base["r0"], out["r0"])
    assert eng.cache_metrics()["prefix_hits"] > 0


# ---------------------------------------------------------------------------
# mid-prefill preemption (pool dry between chunks)
# ---------------------------------------------------------------------------

def test_pool_dry_mid_prefill_requeues_and_completes(lm):
    """A PREFILLING request whose pool dries between chunks is the
    preemption victim (decoders are never evicted for a joiner's
    prompt), requeues, and later completes with tokens identical to an
    uncontended run."""
    model, variables = lm
    rng = np.random.default_rng(17)
    shorts = [rng.integers(1, 32, 8).astype(np.int32) for _ in range(2)]
    long = rng.integers(1, 32, 16).astype(np.int32)

    def run(n_blocks):
        eng = ContinuousEngine(model, variables, max_new_tokens=8,
                               max_slots=3, prompt_buckets=(8, 16),
                               paged=True, block_size=4,
                               n_blocks=n_blocks, chunked=True,
                               tick_token_budget=8)
        out = {}
        for i, s in enumerate(shorts):
            eng.submit(f"s{i}", s, on_done=_collect(out))
        for _ in range(2):            # shorts resident and decoding
            eng.step()
        eng.submit("long", long, on_done=_collect(out))
        eng.drain()
        assert len(out) == 3
        return out, eng

    free, _ = run(None)               # arena-equivalent pool: no dry
    tight, eng = run(7)               # 6 usable blocks: dries mid-chunk
    m = eng.cache_metrics()
    assert m["prefill_preemptions"] >= 1
    assert m["preemptions"] >= m["prefill_preemptions"]
    for k in free:
        assert np.array_equal(free[k], tight[k]), k


# ---------------------------------------------------------------------------
# validation + observability
# ---------------------------------------------------------------------------

def test_budget_below_smallest_bucket_rejected(lm):
    model, variables = lm
    with pytest.raises(ValueError, match="smallest chunk bucket"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         prompt_buckets=(8, 16), chunked=True,
                         tick_token_budget=4)


def test_budget_below_block_size_rejected(lm):
    model, variables = lm
    with pytest.raises(ValueError, match="block_size"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         prompt_buckets=(8, 16), paged=True,
                         block_size=16, chunked=True,
                         tick_token_budget=8)


@pytest.mark.slow       # parity compiles; tests/test_spec_composed.py
# carries the tier-1 composed-mode contracts
def test_chunked_draft_composes(lm):
    """chunked+draft is no longer refused: a self-draft chunked engine
    (acceptance rate 1.0 by construction) emits exactly the plain
    chunked engine's greedy tokens."""
    model, variables = lm
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 32, n).astype(np.int32)
               for n in LENGTHS]
    want, _ = _run(lm, prompts,
                   engine_kw=dict(chunked=True, tick_token_budget=16))
    got, eng = _run(lm, prompts,
                    engine_kw=dict(chunked=True, tick_token_budget=16,
                                   draft_model=model,
                                   draft_variables=variables,
                                   speculation_k=2))
    for k in want:
        assert np.array_equal(got[k], want[k]), k
    m = eng.cache_metrics()
    assert m["spec_proposed"] > 0
    assert m["spec_accepted"] > 0


def test_scheduler_metrics_keys(lm):
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 32, 12).astype(np.int32)]
    _, eng = _run(lm, prompts,
                  engine_kw=dict(chunked=True, tick_token_budget=8))
    m = eng.cache_metrics()
    for key in ("chunked", "tick_token_budget", "budget_utilization",
                "prefill_queue_depth", "chunks_in_flight",
                "prefill_stall_ticks", "prefill_preemptions"):
        assert key in m, key
    assert m["chunks_in_flight"] == 0 and m["prefill_queue_depth"] == 0


@pytest.mark.parametrize("mode", ["arena", "paged"])
def test_precompile_covers_fused_grid(lm, mode):
    """After precompile_chunked(), NO arrival pattern may trigger a
    fused compile: a staggered drive that collides decode rows with
    single and paired chunks of every width runs under trace_guard."""
    from analytics_zoo_tpu.lint import trace_guard

    model, variables = lm
    paged = dict(paged=True, block_size=4) if mode == "paged" else {}
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=3, prompt_buckets=(4, 8, 16),
                           chunked=True, tick_token_budget=8, **paged)
    out = {}
    # warm ONLY the shared decode program (also used by non-chunked
    # engines); every fused shape must come from the precompile
    eng.submit("warm", np.arange(1, 5, dtype=np.int32),
               on_done=_collect(out))
    eng.drain()
    assert eng.precompile_chunked() > 0
    rng = np.random.default_rng(29)
    prompts = [rng.integers(1, 32, n).astype(np.int32)
               for n in (15, 12, 4, 9, 7)]
    with trace_guard(eng, name="precompiled-drive"):
        for i, p in enumerate(prompts):
            eng.submit(f"r{i}", p, on_done=_collect(out))
            eng.step()                # stagger: mixes decode + chunks
        eng.drain()
    assert len(out) == 1 + len(prompts)


def test_precompile_requires_chunked(lm):
    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=3, prompt_buckets=(4, 8))
    with pytest.raises(ValueError, match="chunked"):
        eng.precompile_chunked()


def test_config_knobs(tmp_path):
    from analytics_zoo_tpu.serving.server import ServingConfig

    cfg = tmp_path / "c.yaml"
    cfg.write_text(
        "model: {path: /m}\n"
        "params: {continuous_batching: true, engine_chunked: true, "
        "engine_tick_token_budget: 96}\n")
    c = ServingConfig.from_yaml(str(cfg))
    assert c.engine_chunked is True
    assert c.engine_tick_token_budget == 96
    assert ServingConfig().engine_chunked is False
