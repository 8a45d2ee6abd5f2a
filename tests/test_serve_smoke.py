"""End-to-end wire-protocol checks of the serving fleet on the CPU mesh:
a live ``ClusterServing`` behind its embedded broker (and, where the
contract is on the scrape surface, behind ``HttpFrontend``), driven with
real requests and judged on what a client or a Prometheus scrape sees —
``GET /metrics``, ``GET /trace``, result hashes — not on internals.

Each test is one composition that the engine-level files
(``test_router.py``, ``test_kv_store.py``, ``test_brownout.py``,
``test_mesh_paged.py``, ``test_flight.py``) check piecewise.  They count
requests, blocks and counters; none reads a clock for a result (a CPU
run reports correctness and counts, never a rate).  `make serve-smoke`
runs the file whole, `make chaos-smoke` / `overload-smoke` one test."""

import contextlib
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.learn.inference_model import InferenceModel
from analytics_zoo_tpu.models import TransformerLM
from analytics_zoo_tpu.parallel.mesh import make_mesh
from analytics_zoo_tpu.serving import (ClusterServing, HttpFrontend,
                                       InputQueue, OutputQueue,
                                       ServingConfig,
                                       validate_chrome_trace)
from analytics_zoo_tpu.serving.frontdoor import (encode_deadline,
                                                 encode_priority)

VOCAB = 8192


def _generator(max_new, buckets, draft=False, batch_buckets=(1, 2),
               **model_kw):
    kw = dict(vocab_size=VOCAB, hidden_size=128, num_layers=2,
              num_heads=4, intermediate_size=512, max_position=64)
    kw.update(model_kw)
    model = TransformerLM(**kw)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    extra = dict(draft_model=model, draft_variables=variables) \
        if draft else {}
    im = InferenceModel(batch_buckets=batch_buckets)
    return im.load_flax_generator(model, variables, max_new_tokens=max_new,
                                  prompt_buckets=buckets, **extra)


@contextlib.contextmanager
def _stack(im, cfg, http=True, **serving_kw):
    """A started ``ClusterServing`` on its own broker, its front end and
    one queue client each way; everything stopped on the way out."""
    serving = ClusterServing(im, cfg, embedded_broker=True,
                             **serving_kw).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start() if http else None
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        yield serving, fe, inq, outq
    finally:
        if fe is not None:
            fe.stop()
        serving.stop()
        inq.close()
        outq.close()


def _get(fe, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{fe.port}{path}",
                                timeout=30) as r:
        return r.headers.get("Content-Type", ""), r.read()


def _scrape(fe, prefixes):
    """The samples of ``GET /metrics`` whose names start with one of
    ``prefixes``, and the whole body."""
    body = _get(fe, "/metrics")[1].decode()
    out = {}
    for line in body.splitlines():
        if line.startswith(prefixes):
            name, val = line.split()
            out[name] = float(val)
    return out, body


def _tokens(rng, lo, hi=None):
    n = lo if hi is None else int(rng.integers(lo, hi))
    return rng.integers(1, VOCAB, n).astype(np.int32)


def test_wire_paged_chunked_shared_prefix():
    """20 requests through the queue protocol on the paged engine behind
    the chunked scheduler, every prompt one shared 64-token system
    prompt plus its own suffix, shipped whole: every request served, the
    block-level prefix index hit without a ``register_prefix`` call, and
    the always-on TTFT/TPOT histograms filled."""
    slots = 4
    im = _generator(32, (8, 32, 80), batch_buckets=(1, 8, slots),
                    hidden_size=256, num_layers=4, intermediate_size=1024,
                    max_position=128)
    cfg = ServingConfig(prompt_col="tokens", batch_size=slots,
                        batch_timeout_ms=4.0, continuous_batching=True,
                        engine_slots=slots, engine_ticks=4,
                        engine_paged=True, engine_block_size=16,
                        engine_chunked=True)
    rng = np.random.default_rng(11)
    system = _tokens(rng, 64)
    prompts = [np.concatenate([system, _tokens(rng, 4, 9)])
               for _ in range(16)]
    with _stack(im, cfg, http=False) as (serving, _, inq, outq):
        inq.enqueue("warm", tokens=prompts[0])
        assert outq.query("warm", timeout=600) is not None
        for i in range(20):
            inq.enqueue(f"r{i}", tokens=prompts[int(rng.integers(16))])
        served = sum(outq.query(f"r{i}", timeout=600) is not None
                     for i in range(20))
        cache = serving.engine.cache_metrics()
        tel = serving.engine.telemetry
        assert served == 20
        assert cache["prefix_hit_rate"] > 0.0, cache
        assert cache["peak_resident"] >= 1, cache
        assert "p50" in tel.h_ttft.snapshot(), tel.h_ttft.snapshot()
        assert "p50" in tel.h_tpot.snapshot(), tel.h_tpot.snapshot()


def test_scrape_of_a_speculative_paged_chunked_stack():
    """What ``test_telemetry.py::test_http_metrics_merges_engine_registries``
    (paged stack) and ``test_frontdoor.py::test_healthz_enriched`` leave
    open: with all three engine modes composed — the draft through the
    Python API, ``engine_speculation_k`` through the configuration — one
    scrape carries the speculation counters and the draft pool's gauges
    beside the target's, ``?format=json`` still answers the dictionary,
    and ``GET /trace`` holds ``spec_round`` spans."""
    im = _generator(8, (16,), draft=True, batch_buckets=(1, 4))
    cfg = ServingConfig(prompt_col="tokens", batch_size=4,
                        continuous_batching=True, engine_slots=4,
                        engine_paged=True, engine_block_size=8,
                        engine_chunked=True, engine_speculation_k=2)
    rng = np.random.default_rng(3)
    with _stack(im, cfg) as (serving, fe, inq, outq):
        for i in range(6):
            inq.enqueue(f"sm{i}", tokens=_tokens(rng, 12))
        for i in range(6):
            assert outq.query(f"sm{i}", timeout=600) is not None, i
        h = json.loads(_get(fe, "/healthz")[1])
        assert h["engine"]["paged"] and h["engine"]["chunked"] \
            and h["engine"]["speculative"], h
        ct, body = _get(fe, "/metrics")
        assert ct.startswith("text/plain"), ct
        text = body.decode()
        for needle in ('zoo_engine_ttft_seconds{quantile="0.5"}',
                       "zoo_engine_tpot_seconds_count",
                       "zoo_engine_prefix_hit_rate",
                       "zoo_engine_requests_finished_total 6",
                       "zoo_engine_spec_proposed_total",
                       "zoo_engine_spec_accepted_total",
                       "zoo_engine_spec_accept_len",
                       "zoo_engine_draft_free_blocks"):
            assert needle in text, f"{needle!r} missing from /metrics"
        assert "latency" in json.loads(_get(fe, "/metrics?format=json")[1])
        trace = json.loads(_get(fe, "/trace")[1])
        validate_chrome_trace(trace)
        names = {e.get("name") for e in trace["traceEvents"]}
        assert {"queue_wait", "first_token", "request",
                "spec_round"} <= names, names


def test_starved_pool_fires_a_bundle_the_cli_renders(tmp_path):
    """A live spec + paged + chunked stack with a block pool far too
    small for its concurrency: the allocator fails on consecutive ticks,
    the ``AnomalyMonitor`` dumps ONE bundle for that reason, its flight
    ring holds the ticks that fired it, and the stdlib debug CLI renders
    the bundle and one affected request's history, exit code 0
    (``test_flight.py`` holds the monitor and the CLI on built
    bundles)."""
    im = _generator(12, (16,), draft=True, batch_buckets=(1, 4))
    # 10 blocks of 4 at ~6 blocks a request: more concurrency than pool.
    # The SLO and retrace triggers are pushed out of reach, so the one
    # bundle is the allocation streak's.
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=4, engine_paged=True,
                        engine_block_size=4, engine_blocks=10,
                        engine_chunked=True, engine_speculation_k=2,
                        diag_dir=str(tmp_path), diag_min_interval_s=0.0,
                        anomaly_alloc_streak=3,
                        anomaly_breach_burst=10 ** 9,
                        anomaly_steady_ticks=10 ** 9)
    rng = np.random.default_rng(5)
    with _stack(im, cfg, http=False) as (serving, _, inq, outq):
        for i in range(6):
            inq.enqueue(f"an{i}", tokens=_tokens(rng, 12))
        # the earliest admissions keep making progress, so the contended
        # pool still finishes every request
        for i in range(6):
            assert outq.query(f"an{i}", timeout=600) is not None, i
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not serving.anomalies.bundles:
            time.sleep(0.05)
        hist = serving.anomalies.history()
        assert hist, "no bundle despite a starved block pool"
        assert hist[0]["reason"] == "alloc_failure_streak", hist
        bundle = hist[0]["path"]
        assert bundle and os.path.isdir(bundle), hist
    with open(os.path.join(bundle, "flight.json")) as f:
        ticks = json.load(f)["ticks"]
    assert max(t.get("alloc_fail_streak", 0) for t in ticks) >= 3, ticks
    assert any(t.get("alloc_failures", 0) > 0 for t in ticks), ticks[-3:]

    def cli(*args):
        return subprocess.run(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.debug",
             bundle, *args], capture_output=True, text=True, timeout=120)

    proc = cli()
    assert proc.returncode == 0, proc.stderr
    assert "tick timeline" in proc.stdout, proc.stdout
    with open(os.path.join(bundle, "trace.json")) as f:
        events = json.load(f).get("traceEvents", [])
    uri = min(u for u in (e.get("args", {}).get("uri") for e in events)
              if u and u.startswith("an"))
    proc = cli("--uri", uri)
    assert proc.returncode == 0, proc.stderr
    assert uri in proc.stdout, proc.stdout


def test_two_paged_replicas_spread_on_the_scrape_and_survive_a_kill():
    """Two paged replicas behind one broker and one front end: a burst
    spreads over BOTH, read from the per-replica
    ``zoo_router_routed_total_r{r}`` counters of a real scrape; then one
    pump is killed gracefully mid-backlog and no request is lost
    (``test_router.py`` holds the arena fleet on ``router_status()``)."""
    im = _generator(12, (16,))
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, n_replicas=2)
    rng = np.random.default_rng(17)
    n = 12
    with _stack(im, cfg) as (serving, fe, inq, outq):
        for i in range(n):
            inq.enqueue(f"s{i}", tokens=_tokens(rng, 6, 14))
        # both replicas must take traffic before the kill lands
        deadline = time.monotonic() + 300
        while not all(c > 0 for c in serving.router_status()["routed"]):
            assert time.monotonic() < deadline, \
                f"burst never spread: {serving.router_status()['routed']}"
            time.sleep(0.02)
        scraped, body = _scrape(fe, "zoo_router_routed_total_r")
        assert scraped.get("zoo_router_routed_total_r0", 0) > 0, scraped
        assert scraped.get("zoo_router_routed_total_r1", 0) > 0, scraped
        assert "zoo_router_replicas_live 2" in body, "liveness gauge"
        serving.kill_pump(1)
        for i in range(n):
            assert outq.query(f"s{i}", timeout=600) is not None, \
                f"s{i} lost in the kill"
        status = serving.router_status()
        assert status["live"] == [True, False], status
        e1 = serving.engines[1]
        assert e1.n_active == 0 and e1.n_waiting == 0, \
            "killed replica exited with admitted work resident"


def test_prefill_decode_fleet_hands_off_and_outlives_its_prefill_pump():
    """A prefill replica and a decode replica: every greedy request
    prefills on replica 0, hands its KV-block chain off and decodes on
    replica 1 — read from ``zoo_router_role_*`` on a real scrape — then
    the PREFILL pump is killed gracefully and new prompts fall through
    the role preference to the decode replica, none dropped."""
    im = _generator(12, (16,))
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, engine_blocks=48,
                        n_replicas=2, replica_roles=["prefill", "decode"])
    rng = np.random.default_rng(23)
    n = 8
    with _stack(im, cfg) as (serving, fe, inq, outq):
        for i in range(n):
            inq.enqueue(f"d{i}", tokens=_tokens(rng, 6, 14))
        for i in range(n):
            assert outq.query(f"d{i}", timeout=600) is not None, f"d{i}"
        scraped, _ = _scrape(fe, "zoo_router_role_")
        assert scraped.get("zoo_router_role_handoffs_total", 0) >= 1, \
            scraped
        assert scraped.get(
            "zoo_router_role_prefill_routed_total", 0) >= n, scraped
        serving.kill_pump(0)
        for i in range(n, n + 4):
            inq.enqueue(f"d{i}", tokens=_tokens(rng, 6, 14))
        for i in range(n, n + 4):
            assert outq.query(f"d{i}", timeout=600) is not None, \
                f"d{i} lost in the prefill kill"
        status = serving.router_status()
        assert status["live"] == [False, True], status
        e0 = serving.engines[0]
        assert e0.n_active == 0 and e0.n_waiting == 0, \
            "killed prefill replica exited with admitted work resident"


def test_host_tier_readmits_a_spilled_chain_on_the_scrape():
    """A tiny block pool with a host-DRAM spill store
    (``engine_kv_host_store_bytes`` through the configuration): a
    prompt's cached chain is churned out of the pool, the same prompt
    comes again and re-admits it from the store — read from
    ``zoo_engine_kv_*`` on a real scrape (``test_kv_store.py`` holds the
    engine's side)."""
    im = _generator(12, (16, 32))
    # 12 usable blocks, up to 5 for one resident request: cached chains
    # are evicted, and spilled, within a few churn prompts
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, engine_blocks=13,
                        engine_kv_host_store_bytes=1 << 20)
    rng = np.random.default_rng(29)
    repeat = _tokens(rng, 17)          # two full blocks to publish
    with _stack(im, cfg) as (serving, fe, inq, outq):
        inq.enqueue("a0", tokens=repeat)
        assert outq.query("a0", timeout=600) is not None, "a0 lost"
        for i in range(4):
            inq.enqueue(f"c{i}", tokens=_tokens(rng, 24))
            assert outq.query(f"c{i}", timeout=600) is not None, f"c{i}"
        inq.enqueue("a1", tokens=repeat)
        assert outq.query("a1", timeout=600) is not None, "a1 lost"
        scraped, _ = _scrape(fe, "zoo_engine_kv_")
        assert scraped.get("zoo_engine_kv_spill_chains_total", 0) >= 1, \
            scraped
        assert scraped.get(
            "zoo_engine_kv_readmit_chains_total", 0) >= 1, scraped
        assert scraped.get(
            "zoo_engine_kv_readmit_tokens_saved_total", 0) >= 8, scraped


def test_fused_kernel_serves_a_tp2_int8_pool(devices):
    """A tp=2 paged fleet with the fused Pallas read kernel on an int8
    pool, through ``ClusterServing(engine_mesh=...)``: four kv heads
    over two chips, each owning two and the query heads folded onto
    them.  ``capacity_report()`` bills half the pool to each chip and
    the scraped pool gauge agrees with it (``test_mesh_paged.py`` holds
    the engine's outputs; ``chip_smoke.py --chips 4`` the chip's)."""
    mesh = make_mesh(axes={"dp": -1, "tp": 2})
    im = _generator(12, (16, 32))
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, engine_blocks=25,
                        engine_kernel="fused", engine_kv_dtype="int8")
    rng = np.random.default_rng(41)
    with _stack(im, cfg, engine_mesh=mesh) as (serving, fe, inq, outq):
        for i in range(4):
            inq.enqueue(f"f{i}", tokens=_tokens(rng, 10 + 3 * i))
        for i in range(4):
            assert outq.query(f"f{i}", timeout=600) is not None, f"f{i}"
        rep = serving.engines[0].capacity_report()
        assert rep["kernel"] == "fused", rep
        assert rep["kv_dtype"] == "int8", rep
        assert rep["tp"] == 2, rep
        assert rep["arena_bytes_per_chip"] * 2 == rep["arena_bytes"], rep
        scraped, _ = _scrape(fe, "zoo_engine_kv_")
        assert scraped.get("zoo_engine_kv_pool_bytes") == \
            rep["arena_bytes"], (scraped, rep["arena_bytes"])
        assert scraped.get("zoo_engine_kv_bytes_per_token", 0) > 0, scraped


def test_chaos_crash_and_dropped_handoff_recover_on_the_scrape(tmp_path):
    """A prefill + two-decode fleet under a fixed fault schedule: one
    decode pump CRASHES mid-backlog and the first KV handoff is dropped
    in flight.  Every request still reaches a terminal result, the
    redispatched ones with their ``attempts`` recorded, and a real
    scrape shows the death, the redispatch and the handoff's ack-timeout
    retry (docs/debugging.md "Crash recovery runbook";
    ``test_router.py`` holds each fault alone)."""
    im = _generator(12, (16,))
    cfg = ServingConfig(
        prompt_col="tokens", continuous_batching=True,
        engine_slots=2, engine_paged=True, engine_block_size=8,
        engine_blocks=48, n_replicas=3,
        replica_roles=["prefill", "decode", "decode"], retry_budget=3,
        # generous: a cold adoption compiles its scatter, which must not
        # read as a dropped delivery to the sweep
        handoff_ack_timeout_s=3.0,
        diag_dir=str(tmp_path),
        fault_injection=[
            {"kind": "crash_pump", "replica": 2, "at_tick": 2},
            {"kind": "drop_handoff", "at_handoff": 0}])
    rng = np.random.default_rng(29)
    uris = [f"c{i}" for i in range(8)]
    with _stack(im, cfg) as (serving, fe, inq, outq):
        for u in uris:
            inq.enqueue(u, tokens=_tokens(rng, 6, 14))
        # poll the raw result hashes (``outq.query`` consumes them) so
        # that the per-request ``attempts`` stamp can still be read
        deadline = time.monotonic() + 300
        attempts = {}
        for u in uris:
            while True:
                h = inq.client.execute("HGETALL", "result:" + u)
                if h:
                    f = {h[i].decode(): h[i + 1]
                         for i in range(0, len(h), 2)}
                    if "attempts" in f:
                        attempts[u] = int(f["attempts"])
                    break
                assert time.monotonic() < deadline, \
                    f"{u} stranded: never reached a terminal result"
                time.sleep(0.02)
        for u in uris:
            try:
                assert outq.query(u, timeout=60) is not None, \
                    f"{u} vanished after landing"
            except RuntimeError:
                pass            # a terminal error is a terminal outcome
        assert attempts and all(a >= 2 for a in attempts.values()), \
            f"no at-least-once attempts recorded: {attempts}"
        scraped, _ = _scrape(fe, ("zoo_router_replica_deaths_total",
                                  "zoo_router_requests_redispatched_total",
                                  "zoo_engine_handoff_"))
        for name in ("zoo_router_replica_deaths_total",
                     "zoo_router_requests_redispatched_total",
                     "zoo_engine_handoff_timeouts_total",
                     "zoo_engine_handoff_retries_total"):
            assert scraped.get(name, 0) >= 1, (name, scraped)
        status = serving.router_status()
        assert status["deaths"] == 1, status
        assert status["death_reasons"][2] == "pump_exception", status


def test_overload_ladder_ascends_sheds_and_unwinds_on_the_scrape():
    """Two replicas under a saturating mixed-class burst with a tiny
    brownout ladder (queue_high 4, 50 ms controller interval) and three
    batch requests whose deadline had passed at enqueue.  On a real
    scrape the ladder ascended AND fully unwound (transitions >= 2,
    level 0 at the end), the expired requests were shed at admission as
    terminal ``deadline_exceeded`` errors, and every other request
    finished (docs/serving_qos.md "Overload & brownout";
    ``test_brownout.py`` holds the controller and the engine's side)."""
    im = _generator(12, (16,))
    # generous SLO targets: a cold compile's TTFT must not pin windowed
    # goodput at 0 and hold the ladder up — the queue-depth axis alone
    # drives it here
    slo = {f"slo_{dim}_s_{cls}": 600.0
           for dim in ("ttft", "tpot", "queue_wait")
           for cls in ("interactive", "standard", "batch")}
    cfg = ServingConfig(
        prompt_col="tokens", continuous_batching=True,
        engine_slots=2, n_replicas=2,
        brownout=True, brownout_queue_high=4,
        brownout_enter_ticks=2, brownout_exit_ticks=2,
        brownout_interval_s=0.05, brownout_standard_max_new=6, **slo)
    rng = np.random.default_rng(37)
    burst = [(cls, f"{cls[0]}{k}")
             for cls in ("interactive", "standard", "batch")
             for k in range(6)]
    dead = [f"d{k}" for k in range(3)]
    with _stack(im, cfg) as (serving, fe, inq, outq):
        for cls, u in burst:
            inq.enqueue(u, tokens=_tokens(rng, 6, 14),
                        priority=encode_priority(cls))
        for u in dead:
            inq.enqueue(u, tokens=_tokens(rng, 8),
                        priority=encode_priority("batch"),
                        deadline=encode_deadline(1))
        # the batch class the ladder held during the spike included
        for cls, u in burst:
            assert outq.query(u, timeout=600) is not None, f"{u} lost"
        for u in dead:
            with pytest.raises(RuntimeError, match="deadline_exceeded"):
                outq.query(u, timeout=600)
        deadline = time.monotonic() + 120
        while True:
            m, _ = _scrape(fe, ("zoo_brownout_", "zoo_engine_deadline_"))
            if m.get("zoo_brownout_level", -1) == 0 and \
                    m.get("zoo_brownout_transitions_total", 0) >= 2:
                break
            assert time.monotonic() < deadline, \
                f"ladder never unwound to level 0: {m}"
            time.sleep(0.1)
        assert m.get("zoo_brownout_deadline_shed_total", 0) >= \
            len(dead), m
