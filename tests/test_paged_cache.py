"""Paged KV-cache subsystem tests (serving/paged_cache.py + the
engine's paged=True mode): BlockPool lifecycle/invariants, paged-vs-
arena greedy parity, automatic prefix sharing, block-recycling
isolation (including eviction-then-reallocation), preemption-to-queue,
co-residency under equal HBM, config plumbing, and the ClusterServing
paged round trip."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.lm import TransformerLM, generate
from analytics_zoo_tpu.serving.continuous import ContinuousEngine
from analytics_zoo_tpu.serving.paged_cache import (BlockPool, SINK_BLOCK,
                                                   chain_hashes)


def _tiny_lm(**kw):
    cfg = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=2,
               intermediate_size=64, max_position=64, dtype=jnp.float32)
    cfg.update(kw)
    return TransformerLM(**cfg)


@pytest.fixture(scope="module")
def lm():
    model = _tiny_lm()
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


def _collect(results):
    return lambda u, t: results.__setitem__(u, np.asarray(t))


# ---------------------------------------------------------------------------
# BlockPool unit behaviour
# ---------------------------------------------------------------------------

def test_chain_hashes_position_aligned():
    """Equal hash ⇔ equal token history through that block: a shared
    head gives equal hashes, one differing token breaks the CHAIN from
    that block on, and a trailing partial block gets no hash."""
    a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    b = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    assert len(a) == 2 and len(b) == 2      # 9th token: partial, no hash
    assert a == b
    c = chain_hashes([1, 2, 3, 4, 9, 6, 7, 8], 4)
    assert c[0] == a[0] and c[1] != a[1]
    d = chain_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert d[0] != a[0] and d[1] != a[1]    # chain: head diff poisons all


def test_block_pool_lifecycle_and_lru_eviction():
    pool = BlockPool(6, 4)          # 5 usable blocks + sink
    hs = pool.block_hashes(list(range(12)))
    assert pool.lookup(hs) == []
    b = [pool.allocate() for _ in range(3)]
    assert SINK_BLOCK not in b
    for h, blk in zip(hs, b):
        pool.insert(h, blk)
    pool.check()
    assert pool.lookup(hs) == b
    for blk in b:                   # owner finishes: blocks park in LRU
        pool.release(blk)
    pool.check()
    assert pool.num_cached() == 3 and pool.allocatable() == 5
    got = pool.lookup(hs[:2])       # resurrect two from the LRU
    for blk in got:
        pool.acquire(blk)
    pool.check()
    # 2 free + 1 cached are allocatable; the 4th allocation must evict
    # the cached block and UNPUBLISH its hash
    a = [pool.allocate() for _ in range(3)]
    assert None not in a and pool.allocate() is None
    assert pool.evictions == 1
    assert pool.lookup(hs) == b[:2]         # b[2] no longer matchable
    pool.check()


def test_block_pool_refcount_sharing():
    pool = BlockPool(4, 2)
    h = pool.block_hashes([1, 2])
    blk = pool.allocate()
    pool.insert(h[0], blk)
    pool.acquire(blk)               # second sharer
    pool.release(blk)               # first leaves: still referenced
    pool.check()
    assert pool.num_cached() == 0 and pool.num_referenced() == 1
    pool.release(blk)               # last sharer leaves: now cached
    assert pool.num_cached() == 1
    with pytest.raises(ValueError):
        pool.release(blk)           # over-release must be loud
    pool.check()


def test_block_pool_disable_prefix_cache():
    pool = BlockPool(4, 2, enable_prefix_cache=False)
    h = pool.block_hashes([1, 2])
    blk = pool.allocate()
    pool.insert(h[0], blk)          # no-op when disabled
    assert pool.lookup(h) == []
    pool.release(blk)               # straight back to the free list
    assert pool.num_cached() == 0 and pool.allocatable() == 3
    pool.check()


# ---------------------------------------------------------------------------
# engine parity + sharing
# ---------------------------------------------------------------------------

def test_paged_matches_arena_and_solo(lm):
    """THE tentpole contract: paged mode serves the same request stream
    as arena mode with identical greedy tokens — and both equal each
    request's own solo generate() run."""
    model, variables = lm
    rng = np.random.default_rng(0)
    prompts = {f"r{i}": rng.integers(1, 32, rng.integers(2, 14)).astype(
        np.int32) for i in range(8)}

    def run(**kw):
        eng = ContinuousEngine(model, variables, max_new_tokens=5,
                               max_slots=3, prompt_buckets=(8, 16),
                               ticks_per_step=2, **kw)
        results = {}
        for uri, p in prompts.items():
            eng.submit(uri, p, on_done=_collect(results))
        eng.drain()
        return eng, results

    _, arena = run()
    eng, paged = run(paged=True, block_size=4)
    assert set(arena) == set(paged) == set(prompts)
    for uri in prompts:
        np.testing.assert_array_equal(arena[uri], paged[uri], err_msg=uri)
    for uri, p in prompts.items():
        solo = np.asarray(generate(model, variables, jnp.asarray(p[None]),
                                   5))[0]
        np.testing.assert_array_equal(paged[uri], solo, err_msg=uri)
    eng._pool.check()
    m = eng.cache_metrics()
    assert m["mode"] == "paged" and m["referenced_blocks"] == 0


def test_freed_slot_rests_at_zero_and_prefill_at_its_frontier(lm):
    """Host state the fused kernel's frontier stop reads: after EVERY
    step a slot that holds nothing sits at ``pos`` 0 with a table that
    is all sink (every step program advances ``pos`` for all rows and
    the host reads it back whole: unpinned, a freed slot's would creep
    to the table's end), a PREFILLING row sits frozen at its fill
    frontier, and the rows still running decode what they decode alone."""
    model, variables = lm
    rng = np.random.default_rng(3)
    prompts = {"short": rng.integers(1, 32, 3).astype(np.int32),
               "long": rng.integers(1, 32, 5).astype(np.int32),
               "late": rng.integers(1, 32, 14).astype(np.int32)}
    budget = {"short": 2, "long": 12, "late": 6}
    eng = ContinuousEngine(model, variables, max_new_tokens=12,
                           max_slots=3, prompt_buckets=(8, 16),
                           paged=True, block_size=4, chunked=True,
                           tick_token_budget=8, kernel="fused")
    results = {}
    seen = {"freed": 0, "prefilling": 0}

    def step_and_check():
        eng.step()
        for i, st in enumerate(eng._slots):
            if st is None:
                seen["freed"] += 1
                assert eng._pos[i] == 0
                assert (eng._tables[i] == SINK_BLOCK).all()
            elif st.state == "PREFILLING":
                seen["prefilling"] += 1
                assert eng._pos[i] == st.fill_pos and eng._done[i]

    for uri in ("short", "long"):
        eng.submit(uri, prompts[uri], max_new=budget[uri],
                   on_done=_collect(results))
    while "short" not in results:
        step_and_check()
    for _ in range(3):      # the freed slot idles beside a running row
        step_and_check()
    freed_ticks = seen["freed"]
    eng.submit("late", prompts["late"], max_new=budget["late"],
               on_done=_collect(results))     # two chunks at budget 8
    while len(results) < 3:
        step_and_check()
    assert freed_ticks >= 3 and seen["prefilling"] >= 1
    for uri, p in prompts.items():
        solo = np.asarray(generate(model, variables, jnp.asarray(p[None]),
                                   budget[uri]))[0]
        np.testing.assert_array_equal(results[uri][:budget[uri]], solo,
                                      err_msg=uri)
    rec = eng.flight.snapshot()[-1]
    assert rec["attn_table_blocks"] == eng._tables.size
    assert 0 <= rec["attn_live_blocks"] <= rec["attn_table_blocks"]


def test_paged_eos_and_sampling_parity(lm):
    """EOS frozen-tail semantics and seeded sampling both survive the
    paged path: eos output matches generate(eos_id=...), and a sampled
    request reproduces its arena-mode tokens (same position-folded
    rng, same logits)."""
    model, variables = lm
    p = np.asarray([5, 9, 11, 2], np.int32)
    first = int(np.asarray(generate(model, variables,
                                    jnp.asarray(p[None]), 1))[0, 0])

    def run(**kw):
        eng = ContinuousEngine(model, variables, max_new_tokens=6,
                               max_slots=2, prompt_buckets=(8,),
                               eos_id=first, **kw)
        results = {}
        eng.submit("e", p, on_done=_collect(results))
        eng.submit("s", p, temperature=1.3, rng_seed=7,
                   on_done=_collect(results))
        eng.drain()
        return results

    arena, paged = run(), run(paged=True, block_size=4)
    solo = np.asarray(generate(model, variables, jnp.asarray(p[None]),
                               6, eos_id=first))[0]
    np.testing.assert_array_equal(paged["e"], solo)
    assert (paged["e"] == first).all()          # finished on token 1
    np.testing.assert_array_equal(paged["s"], arena["s"])


def test_paged_prefix_sharing_hits(lm):
    """Requests sharing a long system prompt automatically attach to
    the same physical blocks: hit rate > 0, outputs still equal solo
    runs of the full concatenated prompts."""
    model, variables = lm
    rng = np.random.default_rng(2)
    sys_p = rng.integers(1, 32, 20).astype(np.int32)
    eng = ContinuousEngine(model, variables, max_new_tokens=5,
                           max_slots=4, prompt_buckets=(8, 16, 32),
                           paged=True, block_size=4)
    results, fulls = {}, {}
    for i in range(6):
        sfx = rng.integers(1, 32, 4).astype(np.int32)
        fulls[f"s{i}"] = np.concatenate([sys_p, sfx])
        eng.submit(f"s{i}", fulls[f"s{i}"], on_done=_collect(results))
    eng.drain()
    m = eng.cache_metrics()
    assert m["prefix_hits"] > 0 and m["prefix_hit_rate"] > 0.0
    for uri, full in fulls.items():
        solo = np.asarray(generate(model, variables,
                                   jnp.asarray(full[None]), 5))[0]
        np.testing.assert_array_equal(results[uri], solo, err_msg=uri)
    eng._pool.check()


def test_paged_register_prefix_compat(lm):
    """The legacy register_prefix() API on the paged engine: pinned
    blocks are shared by every suffix request (hits > 0), outputs match
    the concatenated solo run, and unregister releases the pin."""
    model, variables = lm
    rng = np.random.default_rng(3)
    sys_p = rng.integers(1, 32, 17).astype(np.int32)
    eng = ContinuousEngine(model, variables, max_new_tokens=5,
                           max_slots=2, prompt_buckets=(8, 16, 32),
                           paged=True, block_size=4)
    pid = eng.register_prefix(sys_p)
    pinned = eng._pool.num_referenced()
    assert pinned == len(sys_p) // 4
    results = {}
    sfx = rng.integers(1, 32, 5).astype(np.int32)
    eng.submit("a", sfx, prefix=pid, on_done=_collect(results))
    eng.drain()
    full = np.concatenate([sys_p, sfx])
    solo = np.asarray(generate(model, variables, jnp.asarray(full[None]),
                               5))[0]
    np.testing.assert_array_equal(results["a"], solo)
    assert eng.cache_metrics()["prefix_hits"] > 0
    eng.unregister_prefix(pid)
    assert eng._pool.num_referenced() == 0      # pin released
    with pytest.raises(ValueError):
        eng.submit("b", sfx, prefix=pid)        # id gone, loud
    eng._pool.check()


# ---------------------------------------------------------------------------
# adversarial recycling isolation
# ---------------------------------------------------------------------------

def test_recycled_block_never_leaks_predecessor_kv(lm):
    """Adversarial recycling: run waves of DIFFERENT requests through a
    minimal pool so every wave decodes in blocks its predecessors just
    vacated (and, with prefix caching on, blocks that went through the
    LRU and were EVICTED then reallocated).  Any K/V leak from a
    predecessor changes attention output ⇒ token mismatch vs solo."""
    model, variables = lm
    rng = np.random.default_rng(4)
    # M = ceil((16+6)/4) = 6; pool of 2 rows' worth forces heavy reuse
    eng = ContinuousEngine(model, variables, max_new_tokens=6,
                           max_slots=2, prompt_buckets=(8, 16),
                           paged=True, block_size=4, n_blocks=13)
    for wave in range(4):
        results, fulls = {}, {}
        for i in range(3):
            uri = f"w{wave}r{i}"
            fulls[uri] = rng.integers(1, 32, rng.integers(5, 15)).astype(
                np.int32)
            eng.submit(uri, fulls[uri], on_done=_collect(results))
        eng.drain()
        for uri, p in fulls.items():
            solo = np.asarray(generate(model, variables,
                                       jnp.asarray(p[None]), 6))[0]
            np.testing.assert_array_equal(results[uri], solo, err_msg=uri)
        eng._pool.check()
    # the pool actually cycled: every usable block was handed out and
    # the LRU evicted cached blocks to serve new prompts
    m = eng.cache_metrics()
    assert m["evictions"] > 0


def test_eviction_then_reallocation_unpublishes_hash(lm):
    """After a cached block is evicted and reallocated to a NEW prompt,
    a request re-sending the OLD prompt must not match stale storage:
    the lookup misses and it recomputes — output still equals solo."""
    model, variables = lm
    rng = np.random.default_rng(5)
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=1, prompt_buckets=(8, 16),
                           paged=True, block_size=4, n_blocks=7)
    old = rng.integers(1, 32, 12).astype(np.int32)
    results = {}
    eng.submit("old1", old, on_done=_collect(results))
    eng.drain()
    cached_before = eng._pool.num_cached()
    assert cached_before > 0            # old1's full blocks parked
    # churn DIFFERENT prompts through the tiny pool until the old
    # prompt's cached blocks have all been evicted + reallocated
    for i in range(4):
        eng.submit(f"churn{i}", rng.integers(1, 32, 12).astype(np.int32),
                   on_done=_collect(results))
        eng.drain()
    assert eng.cache_metrics()["evictions"] > 0
    eng.submit("old2", old, on_done=_collect(results))
    eng.drain()
    solo = np.asarray(generate(model, variables, jnp.asarray(old[None]),
                               4))[0]
    np.testing.assert_array_equal(results["old1"], solo)
    np.testing.assert_array_equal(results["old2"], solo)
    eng._pool.check()


# ---------------------------------------------------------------------------
# preemption + scheduling
# ---------------------------------------------------------------------------

def test_pool_dry_preempts_to_queue_not_oom(lm):
    """More resident demand than blocks: the engine preempts the LATEST
    admission back to the queue front (never OOMs, never deadlocks),
    and every request still finishes with solo-identical tokens."""
    model, variables = lm
    rng = np.random.default_rng(6)
    prompts = {f"p{i}": rng.integers(1, 32, rng.integers(8, 15)).astype(
        np.int32) for i in range(8)}
    # just above the one-full-row minimum: co-residency forces preempts
    eng = ContinuousEngine(model, variables, max_new_tokens=8,
                           max_slots=4, prompt_buckets=(8, 16),
                           paged=True, block_size=4, n_blocks=9,
                           enable_prefix_cache=False)
    results = {}
    for uri, p in prompts.items():
        eng.submit(uri, p, on_done=_collect(results))
    eng.drain()
    assert set(results) == set(prompts)
    assert eng.cache_metrics()["preemptions"] > 0
    for uri, p in prompts.items():
        solo = np.asarray(generate(model, variables, jnp.asarray(p[None]),
                                   8))[0]
        np.testing.assert_array_equal(results[uri], solo, err_msg=uri)
    eng._pool.check()


def test_paged_double_coresidency_for_equal_hbm(lm):
    """The acceptance bar made concrete at engine level: give BOTH
    modes the same cache HBM; short-prompt traffic lets paged hold
    >= 2x the arena's max co-resident requests (the arena pays
    worst-case length per slot, paged pays actual length)."""
    model, variables = lm
    arena = ContinuousEngine(model, variables, max_new_tokens=4,
                             max_slots=2, prompt_buckets=(8, 16))
    arena_bytes = arena.capacity_report()["arena_bytes"]
    # same HBM, paged: arena's L=20 -> 2 slots = 40 token slots = 10
    # blocks of 4 (one of them the sink).  Short prompts (3 tokens + 4
    # new = 2 blocks each) fit >= 4 residents where the arena holds 2.
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=4, prompt_buckets=(8, 16),
                           paged=True, block_size=4, n_blocks=10)
    assert eng.capacity_report()["arena_bytes"] <= arena_bytes
    rng = np.random.default_rng(7)
    results = {}
    for i in range(8):
        eng.submit(f"c{i}", rng.integers(1, 32, 3).astype(np.int32),
                   on_done=_collect(results))
    eng.drain()
    assert len(results) == 8
    m = eng.cache_metrics()
    assert m["peak_resident"] >= 2 * arena.capacity_report()["slots"]
    assert m["preemptions"] == 0    # genuinely co-resident, not thrash


def test_paged_validation_and_cache_dtype_errors(lm):
    """Eager, serving-level errors: bad cache_dtype (any mode), integer
    cache_dtype, undersized pool, draft-pool sizing, and the one
    composition still excluded — the fused kernel under a mesh."""
    model, variables = lm
    with pytest.raises(ValueError, match="cache_dtype"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         cache_dtype="not_a_dtype")
    with pytest.raises(ValueError, match="floating"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         cache_dtype="int8")
    with pytest.raises(ValueError, match="n_blocks"):
        ContinuousEngine(model, variables, max_new_tokens=4,
                         paged=True, block_size=4, n_blocks=3)
    draft = _tiny_lm(num_layers=1)
    dvars = draft.init(jax.random.key(1), np.zeros((1, 8), np.int32))
    with pytest.raises(ValueError, match="draft_n_blocks"):
        # paged+draft now composes, but the draft tenant still needs a
        # table-width's worth of blocks plus the sink
        ContinuousEngine(model, variables, max_new_tokens=4, paged=True,
                         block_size=4, draft_model=draft,
                         draft_variables=dvars, draft_n_blocks=2)
    # paged + mesh composes for BOTH kernels now: the fused Pallas
    # kernel runs per-chip under shard_map (tests/test_mesh_paged.py
    # pins parity), so fused + mesh constructs without complaint
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("dp",))
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           paged=True, kernel="fused", mesh=mesh)
    assert eng.kernel == "fused" and eng.mesh is mesh


def test_paged_gqa_cache_dtype_parity():
    """GQA + narrowed cache_dtype compose with paged mode: the pool
    stores kv_heads bf16 blocks and greedy tokens still match the
    model's own f32 solo generation on this peaked-free tiny model."""
    model = _tiny_lm(num_heads=4, num_kv_heads=1)
    variables = model.init(jax.random.key(2), np.zeros((1, 8), np.int32))
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=2, prompt_buckets=(8,),
                           paged=True, block_size=4,
                           cache_dtype="bfloat16")
    assert eng._pk.dtype == jnp.bfloat16
    assert eng._pk.shape[2] == 1            # kv_heads, not num_heads
    p = np.asarray([3, 7, 2, 9], np.int32)
    results = {}
    eng.submit("g", p, on_done=_collect(results))
    eng.drain()
    solo = np.asarray(generate(model, variables, jnp.asarray(p[None]),
                               4))[0]
    np.testing.assert_array_equal(results["g"], solo)


# ---------------------------------------------------------------------------
# serving-stack plumbing
# ---------------------------------------------------------------------------

def test_serving_config_paged_knobs(tmp_path):
    from analytics_zoo_tpu.serving import ServingConfig

    y = tmp_path / "cfg.yaml"
    y.write_text(
        "model:\n  path: /tmp/m\nparams:\n"
        "  continuous_batching: true\n  engine_paged: true\n"
        "  engine_block_size: 8\n  engine_blocks: 99\n"
        "  engine_hbm_fraction: 0.25\n  engine_prefix_cache: false\n")
    cfg = ServingConfig.from_yaml(str(y))
    assert cfg.engine_paged and cfg.engine_block_size == 8
    assert cfg.engine_blocks == 99
    assert cfg.engine_hbm_fraction == 0.25
    assert cfg.engine_prefix_cache is False
    # defaults stay off so existing configs keep the arena
    assert ServingConfig().engine_paged is False


def test_cluster_serving_paged_round_trip(lm):
    """e2e: a paged-mode ClusterServing serves ragged prompts from the
    queue; results equal solo generations and the published stats carry
    the pool's cache metrics."""
    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           OutputQueue, ServingConfig)

    model, variables = lm
    im = InferenceModel().load_flax_generator(
        model, variables, max_new_tokens=6, prompt_buckets=(8, 16))
    cfg = ServingConfig(prompt_col="prompt", continuous_batching=True,
                        engine_slots=3, engine_paged=True,
                        engine_block_size=4)
    srv = ClusterServing(im, cfg, embedded_broker=True).start()
    try:
        assert srv.engine.paged
        iq = InputQueue(port=srv.port)
        oq = OutputQueue(port=srv.port)
        rng = np.random.default_rng(8)
        prompts = {f"q{i}": rng.integers(1, 32, rng.integers(2, 9)).astype(
            np.int32) for i in range(5)}
        for uri, p in prompts.items():
            iq.enqueue(uri, prompt=p)
        for uri, p in prompts.items():
            got = oq.query(uri, timeout=60)
            solo = np.asarray(generate(model, variables,
                                       jnp.asarray(p[None]), 6))[0]
            np.testing.assert_array_equal(np.asarray(got), solo,
                                          err_msg=uri)
        with srv._stats_lock:
            cache = dict(srv.stats.get("cache") or {})
        assert cache.get("mode") == "paged"
        assert "prefix_hit_rate" in cache and "occupancy" in cache
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# disaggregation: chain export/adopt + elastic pool resize
# ---------------------------------------------------------------------------

def test_block_pool_export_adopt_chain_preserves_hashes():
    """The handoff wire format round-trips: an exported chain carries
    the source's full-block prefix hashes, export is read-only on the
    source, and adoption re-publishes the hashes so the destination's
    prefix index matches them again."""
    src = BlockPool(8, 4)
    hs = src.block_hashes(list(range(10)))      # 2 full blocks + partial
    assert len(hs) == 2
    blocks = [src.allocate() for _ in range(3)]
    for h, blk in zip(hs, blocks):
        src.insert(h, blk)
    chain = src.export_chain(blocks)
    assert chain["n"] == 3 and chain["block_size"] == 4
    assert chain["hashes"][:2] == hs and chain["hashes"][2] is None
    assert src.metrics()["chains_exported"] == 1
    assert src.num_referenced() == 3            # export took no refs
    src.check()

    dst = BlockPool(8, 4)
    got = dst.adopt_chain(chain)
    assert got is not None and len(got) == 3 and SINK_BLOCK not in got
    assert dst.num_referenced() == 3
    assert dst.lookup(hs) == got[:2]            # prefix index restored
    assert dst.metrics()["chains_adopted"] == 1
    dst.check()


def test_block_pool_export_chain_refuses_sink_and_unreferenced():
    pool = BlockPool(8, 4)
    b = pool.allocate()
    with pytest.raises(ValueError):
        pool.export_chain([SINK_BLOCK, b])
    h = pool.block_hashes([1, 2, 3, 4])
    b2 = pool.allocate()
    pool.insert(h[0], b2)
    pool.release(b2)                            # cached, ref == 0
    with pytest.raises(ValueError):
        pool.export_chain([b2])
    pool.release(b)                             # free, ref == 0
    with pytest.raises(ValueError):
        pool.export_chain([b])
    pool.check()


def test_block_pool_adopt_chain_validates_and_rolls_back():
    """Geometry/dtype mismatches are loud; an adoption the pool cannot
    fully satisfy rolls back EVERY partial allocation and returns None
    (the engine then requeues the handoff, it must not leak blocks)."""
    src = BlockPool(8, 4)
    blocks = [src.allocate() for _ in range(3)]
    chain = src.export_chain(blocks)
    with pytest.raises(ValueError):
        BlockPool(8, 8).adopt_chain(chain)      # block_size mismatch
    with pytest.raises(ValueError):
        BlockPool(8, 4, kv_dtype="int8").adopt_chain(chain)
    tiny = BlockPool(3, 4)                      # 2 usable < chain n=3
    before = tiny.allocatable()
    assert tiny.adopt_chain(chain) is None
    assert tiny.allocatable() == before and tiny.num_referenced() == 0
    assert tiny.metrics()["chains_adopted"] == 0
    tiny.check()


def test_block_pool_grow_appends_and_shrink_clamps_at_referenced_tail():
    """Resize edges: grow appends fresh top ids; shrink never evicts a
    referenced block — a deeper request is clamped at the eviction
    boundary and counted, never raised — and a cached tail block is
    evicted with its hash unpublished.  Block 0 (sink) never moves."""
    pool = BlockPool(10, 4)
    blocks = [pool.allocate() for _ in range(9)]
    assert sorted(blocks) == list(range(1, 10))
    assert pool.shrinkable() == 0
    assert pool.shrink(3) == 0                  # fully referenced: clamp
    assert pool.n_blocks == 10
    assert pool.metrics()["resize_clamps"] == 1
    # free the tail ids 6..9, with a hash published on 9 so the shrink
    # also exercises the eviction + unpublish path
    hs = pool.block_hashes([1, 2, 3, 4])
    pool.insert(hs[0], 9)
    for b in (6, 7, 8, 9):
        pool.release(b)
    assert pool.shrinkable() == 4
    ev0 = pool.evictions
    assert pool.shrink(6) == 4                  # clamped at boundary
    assert pool.n_blocks == 6
    assert pool.metrics()["resize_clamps"] == 2
    assert pool.evictions == ev0 + 1
    assert pool.lookup(hs) == []                # evicted hash unmatchable
    pool.check()
    assert pool.grow(2) == 2 and pool.n_blocks == 8
    # 1 applied shrink + 1 grow; the fully-clamped shrink(3) applied
    # zero blocks and is counted only as a clamp, not a resize
    assert pool.metrics()["resizes"] == 2
    pool.check()
    got = [pool.allocate(), pool.allocate()]    # the fresh top ids
    assert sorted(got) == [6, 7] and SINK_BLOCK not in got
    pool.check()


def test_engine_handoff_parity(lm):
    """Acceptance pin (docs/serving_memory.md): prefill on engine A,
    KV block-chain handoff at first-token time, decode on engine B —
    greedy outputs bitwise-identical to each request's solo generate(),
    in plain paged mode AND paged+chunked."""
    model, variables = lm
    rng = np.random.default_rng(11)
    prompts = {f"h{i}": rng.integers(1, 32, rng.integers(2, 14)).astype(
        np.int32) for i in range(4)}
    for extra in ({}, {"chunked": True, "tick_token_budget": 8}):
        kw = dict(max_new_tokens=5, max_slots=3, prompt_buckets=(8, 16),
                  paged=True, block_size=4, **extra)
        a = ContinuousEngine(model, variables, **kw)
        b = ContinuousEngine(model, variables, **kw)
        results = {}
        for uri, p in prompts.items():
            a.submit(uri, p, on_done=_collect(results),
                     handoff_cb=b.submit_handoff)
        for _ in range(500):
            a.step()
            b.step()
            if len(results) == len(prompts):
                break
        assert set(results) == set(prompts)
        assert a._handoffs_out == len(prompts)
        assert b._handoffs_in == len(prompts)
        assert a.n_active == 0 and b.n_active == 0
        a._pool.check()
        b._pool.check()
        assert a._pool.num_referenced() == 0
        assert b._pool.num_referenced() == 0
        for uri, p in prompts.items():
            solo = np.asarray(generate(model, variables,
                                       jnp.asarray(p[None]), 5))[0]
            np.testing.assert_array_equal(results[uri], solo, err_msg=uri)


def test_engine_handoff_composition_errors(lm):
    """The excluded compositions die at submit time with pointed
    errors, never mid-pump: arena engines (no block tables), sampled
    requests (unsplittable RNG stream), and speculative engines (the
    ROADMAP 'spec-aware KV handoff' follow-on)."""
    model, variables = lm
    p = np.arange(1, 5, dtype=np.int32)
    arena = ContinuousEngine(model, variables, max_new_tokens=4,
                             max_slots=2, prompt_buckets=(8,))
    with pytest.raises(ValueError, match="requires paged"):
        arena.submit("a", p, handoff_cb=lambda st: None)
    with pytest.raises(ValueError, match="paged engine"):
        arena.submit_handoff({})
    paged = ContinuousEngine(model, variables, max_new_tokens=4,
                             max_slots=2, prompt_buckets=(8,),
                             paged=True, block_size=4)
    with pytest.raises(ValueError, match="greedy-only"):
        paged.submit("s", p, temperature=0.7, rng_seed=1,
                     handoff_cb=lambda st: None)
    spec = ContinuousEngine(model, variables, max_new_tokens=4,
                            max_slots=2, prompt_buckets=(8,),
                            paged=True, block_size=4,
                            draft_model=model, draft_variables=variables,
                            speculation_k=2)
    with pytest.raises(ValueError, match="spec-aware KV handoff"):
        spec.submit("d", p, handoff_cb=lambda st: None)
    with pytest.raises(ValueError, match="spec-aware KV handoff"):
        spec.submit_handoff({})


def test_engine_elastic_pool_resize_parity(lm):
    """resize_pool moves the host pool and the device arena in
    lockstep (blocks live on axis 1 of the stacked layout), clamps a
    below-floor shrink at the floor — counted, never raised — and
    greedy outputs stay bitwise-identical across grow and shrink."""
    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=4, prompt_buckets=(8,),
                           paged=True, block_size=4, n_blocks=13,
                           elastic_pool=True)
    assert eng._pool_floor == 4                 # M+1, M = (8+4)/4
    assert eng._pool_ceiling == 13              # CPU: arena-equivalent
    assert eng._resize_step == 4
    p = np.arange(1, 8, dtype=np.int32)
    solo = np.asarray(generate(model, variables, jnp.asarray(p[None]),
                               4))[0]
    results = {}
    for phase, target in (("floor", 1), ("ceiling", eng._pool_ceiling)):
        clamped0 = eng._pool_resize_clamps
        eng.resize_pool(target)
        n = eng._pool.n_blocks
        assert n == max(eng._pool_floor, min(target, eng._pool_ceiling))
        assert eng._pk.shape[1] == n            # device arena followed
        if target < eng._pool_floor:
            assert eng._pool_resize_clamps == clamped0 + 1
        eng._pool.check()
        uri = f"e-{phase}"
        eng.submit(uri, p, on_done=_collect(results))
        eng.drain()
        np.testing.assert_array_equal(results[uri], solo, err_msg=phase)
        eng._pool.check()


def test_engine_maybe_autoresize_policy_loop(lm):
    """The pump-side control loop: an idle over-provisioned pool
    shrinks one step; a degraded goodput class holds the shrink; an
    alloc-fail streak grows back toward the ceiling even while
    goodput is degraded (grow outranks the hold)."""
    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=4,
                           max_slots=4, prompt_buckets=(8,),
                           paged=True, block_size=4, n_blocks=13,
                           elastic_pool=True)
    assert eng.maybe_autoresize() == -4         # idle: shrink one step
    assert eng._pool.n_blocks == 9
    bad = {"interactive": 0.2}
    assert eng.maybe_autoresize(goodput=bad) == 0   # SLO hold
    held = []
    while True:                                 # dry the pool
        blk = eng._pool.allocate()
        if blk is None:
            break
        held.append(blk)
    assert eng.maybe_autoresize(goodput=bad) == 4   # pressure beats hold
    assert eng._pool.n_blocks == 13
    assert eng._pk.shape[1] == 13
    for blk in held:
        eng._pool.release(blk)
    eng._pool.check()
    m = eng.cache_metrics()
    assert m["pool_resizes"] == 2 and m["pool_floor"] == 4
