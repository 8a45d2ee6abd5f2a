"""Telemetry subsystem (serving/telemetry.py): window-histogram edge
cases, registry semantics, Prometheus text exposition, the event
ring's Chrome trace export, engine lifecycle instrumentation across
slot-arena / paged / chunked modes, TraceGuard retrace reporting, the
block pool's observability hook, and abandoned-result accounting."""

import http.client
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.serving.telemetry import (
    EventLog, Gauge, MetricsRegistry, Telemetry, WindowHistogram,
    render_prometheus, validate_chrome_trace)


# ---------------------------------------------------------------------------
# WindowHistogram
# ---------------------------------------------------------------------------

class TestWindowHistogram:
    def test_empty_window(self):
        h = WindowHistogram("x")
        s = h.snapshot()
        assert s["count"] == 0 and s["window"] == 0 and s["sum"] == 0.0
        assert "p50" not in s and "min" not in s
        assert h.percentile(99) is None

    def test_single_sample(self):
        h = WindowHistogram("x")
        h.record(0.25)
        s = h.snapshot()
        assert s["count"] == 1 and s["window"] == 1
        assert s["p50"] == s["p90"] == s["p99"] == 0.25
        assert s["min"] == s["max"] == 0.25 and s["sum"] == 0.25

    def test_wraparound_keeps_last_window(self):
        h = WindowHistogram("x", window=4)
        for v in range(1, 11):          # 1..10 through a 4-slot ring
            h.record(float(v))
        s = h.snapshot()
        # percentiles over {7,8,9,10} only; count/sum over all 10
        assert s["window"] == 4
        assert s["min"] == 7.0 and s["max"] == 10.0
        assert s["p50"] == 8.5
        assert s["count"] == 10 and s["sum"] == 55.0

    def test_percentile_interpolation(self):
        h = WindowHistogram("x")
        h.record(0.0)
        h.record(10.0)
        assert h.percentile(50) == 5.0      # numpy 'linear' method
        assert h.percentile(90) == 9.0

    def test_cumulative_monotonic_across_reset(self):
        h = WindowHistogram("x", window=8)
        for v in (1.0, 2.0, 3.0):
            h.record(v)
        s1 = h.snapshot()
        s2 = h.snapshot()               # snapshot must not mutate
        assert (s1["count"], s1["sum"]) == (s2["count"], s2["sum"]) \
            == (3, 6.0)
        h.reset_window()
        s3 = h.snapshot()
        assert s3["window"] == 0 and "p50" not in s3
        assert s3["count"] == 3 and s3["sum"] == 6.0    # stand
        h.record(5.0)
        s4 = h.snapshot()
        assert s4["count"] == 4 and s4["p50"] == 5.0

    def test_window_must_be_positive(self):
        with pytest.raises(ValueError, match="window"):
            WindowHistogram("x", window=0)


# ---------------------------------------------------------------------------
# registry + Prometheus exposition
# ---------------------------------------------------------------------------

def _parse_prometheus(text):
    """Mini-parser: every sample line must be ``name[{labels}] value``
    with a float-parseable value; returns {sample_key: value} plus the
    set of declared TYPEs."""
    samples, types = {}, {}
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        assert key, line
        samples[key] = float(val)       # raises on malformed values
    return samples, types


class TestRegistryAndRender:
    def test_get_or_create_returns_same_object(self):
        reg = MetricsRegistry()
        assert reg.counter("a_total") is reg.counter("a_total")
        assert reg.histogram("h") is reg.histogram("h")

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("a_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("a_total")

    def test_invalid_name_raises(self):
        with pytest.raises(ValueError, match="invalid metric name"):
            MetricsRegistry().counter("bad name!")

    def test_gauge_fn_refreshes_on_reregistration(self):
        reg = MetricsRegistry()
        reg.gauge("g", fn=lambda: 1.0)
        assert reg.gauge("g", fn=lambda: 2.0).value == 2.0

    def test_failing_gauge_skips_sample_not_scrape(self):
        reg = MetricsRegistry()
        reg.gauge("dead", fn=lambda: 1 / 0)
        c = reg.counter("alive_total")
        c.inc(3)
        samples, _ = _parse_prometheus(render_prometheus(reg))
        assert "dead" not in samples
        assert samples["alive_total"] == 3.0

    def test_render_counters_gauges_summaries(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests").inc(7)
        reg.gauge("depth", "queue depth", fn=lambda: 4)
        reg.gauge("evict_total", kind="counter", fn=lambda: 2)
        h = reg.histogram("lat_seconds", "latency")
        for v in (0.1, 0.2, 0.3):
            h.record(v)
        samples, types = _parse_prometheus(render_prometheus(reg))
        assert types == {"req_total": "counter", "depth": "gauge",
                         "evict_total": "counter",
                         "lat_seconds": "summary"}
        assert samples["req_total"] == 7.0
        assert samples["depth"] == 4.0
        assert samples['lat_seconds{quantile="0.5"}'] == \
            pytest.approx(0.2)
        assert samples["lat_seconds_count"] == 3.0
        assert samples["lat_seconds_sum"] == pytest.approx(0.6)

    def test_first_registration_wins_across_registries(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("dup_total").inc(1)
        b.counter("dup_total").inc(99)
        samples, _ = _parse_prometheus(render_prometheus(a, b))
        assert samples["dup_total"] == 1.0

    def test_special_float_values_render(self):
        reg = MetricsRegistry()
        reg.gauge("nan", fn=lambda: float("nan"))
        reg.gauge("inf", fn=lambda: float("inf"))
        text = render_prometheus(reg)
        assert "nan NaN" in text and "inf +Inf" in text


# ---------------------------------------------------------------------------
# event log + Chrome trace schema
# ---------------------------------------------------------------------------

class TestEventLogTrace:
    def test_to_chrome_is_schema_valid(self):
        ev = EventLog(capacity=64)
        t = time.monotonic()
        ev.span("request", t, 0.5, tid=2, args={"uri": "r0"})
        ev.instant("first_token", t + 0.1, tid=2)
        ev.counter_sample("engine", {"active": 3}, ts=t + 0.2)
        trace = ev.to_chrome(process_name="test")
        validate_chrome_trace(trace)            # raises on violation
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"request", "first_token", "engine",
                "process_name"} <= names
        # the X span carries a µs duration
        x = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert x and x[0]["dur"] == pytest.approx(0.5e6)

    def test_ring_is_bounded(self):
        ev = EventLog(capacity=8)
        for i in range(100):
            ev.instant(f"e{i}", float(i), tid=0)
        events = ev.to_chrome()["traceEvents"]
        kept = [e for e in events if e["ph"] == "i"]
        assert len(kept) == 8
        assert kept[-1]["name"] == "e99"

    @pytest.mark.parametrize("bad", [
        [],                                         # not a dict
        {"traceEvents": {}},                        # not a list
        {"traceEvents": [{"name": "x"}]},           # missing ph
        {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 0,
                          "ts": 0.0}]},             # X without dur
        {"traceEvents": [{"ph": "i", "name": "x", "pid": 1, "tid": 0,
                          "ts": 0.0, "args": 5}]},  # args not a dict
    ])
    def test_validate_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)


# ---------------------------------------------------------------------------
# engine lifecycle instrumentation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    from analytics_zoo_tpu.models.lm import TransformerLM

    model = TransformerLM(vocab_size=32, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64,
                          max_position=64, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


MODES = {
    "arena": {},
    "paged-chunked": dict(paged=True, block_size=4, chunked=True,
                          tick_token_budget=8),
}


class TestEngineTelemetry:
    @pytest.mark.parametrize("mode", list(MODES))
    def test_lifecycle_counters_and_trace(self, lm, mode):
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=5,
                               max_slots=3, prompt_buckets=(8, 16),
                               **MODES[mode])
        tm = eng.telemetry
        rng = np.random.default_rng(0)
        done = {}
        for i, n in enumerate((4, 12, 7)):
            eng.submit(f"r{i}", rng.integers(1, 32, n).astype(np.int32),
                       on_done=lambda u, t: done.__setitem__(u, t))
        eng.drain()
        assert len(done) == 3
        assert tm.c_submitted.value == 3 and tm.c_finished.value == 3
        assert tm.c_tokens.value == 15          # 3 requests x 5 tokens
        assert tm.c_ticks.value > 0 and tm.c_jit_builds.value > 0
        assert tm.h_ttft.snapshot()["count"] == 3
        assert tm.h_tpot.snapshot()["count"] == 12      # 3 x (5 - 1)
        assert tm.h_queue_wait.snapshot()["count"] == 3
        if "chunked" in mode:
            assert tm.c_chunks.value > 0
        trace = tm.dump_trace()
        validate_chrome_trace(trace)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"enqueued", "queue_wait", "first_token", "request",
                "tick", "jit_build"} <= names

    def test_idle_steps_emit_no_tick_events(self, lm):
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=2,
                               max_slots=2, prompt_buckets=(8,))
        before = eng.telemetry.c_ticks.value
        for _ in range(50):                 # idle poll: nothing to do
            assert eng.step() == 0
        assert eng.telemetry.c_ticks.value == before

    def test_engine_prometheus_surface(self, lm):
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=3,
                               max_slots=2, prompt_buckets=(8,),
                               paged=True, block_size=4)
        done = {}
        eng.submit("r0", np.arange(1, 7, dtype=np.int32),
                   on_done=lambda u, t: done.__setitem__(u, t))
        eng.drain()
        samples, types = _parse_prometheus(
            render_prometheus(eng.telemetry.metrics))
        assert samples["zoo_engine_requests_finished_total"] == 1.0
        assert samples["zoo_engine_requests_preempted_total"] == 0.0
        assert samples["zoo_engine_queue_depth"] == 0.0
        assert samples["zoo_engine_active_slots"] == 0.0
        assert 'zoo_engine_ttft_seconds{quantile="0.5"}' in samples
        assert "zoo_engine_free_blocks" in samples
        assert "zoo_engine_prefix_hit_rate" in samples
        assert "zoo_engine_pool_evictions_total" in samples
        assert types["zoo_engine_pool_evictions_total"] == "counter"
        assert types["zoo_engine_ttft_seconds"] == "summary"

    def test_preemption_telemetry(self, lm):
        """A preempted request must count once, re-record its first
        token on readmission, and keep its ORIGINAL arrival (TTFT spans
        the preemption)."""
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        # 7 non-sink blocks for two 6-token prompts wanting 6+8 tokens
        # each (4 blocks apiece): the second admission starves the
        # first mid-decode and forces a preemption
        eng = ContinuousEngine(model, variables, max_new_tokens=8,
                               max_slots=2, prompt_buckets=(8,),
                               paged=True, block_size=4, n_blocks=8,
                               enable_prefix_cache=False)
        tm = eng.telemetry
        rng = np.random.default_rng(2)
        done = {}
        for i in range(3):
            eng.submit(f"r{i}", rng.integers(1, 32, 6).astype(np.int32),
                       on_done=lambda u, t: done.__setitem__(u, t))
        eng.drain()
        assert len(done) == 3
        if tm.c_preempted.value:        # pool pressure reached
            names = {e["name"]
                     for e in tm.dump_trace()["traceEvents"]}
            assert "preempted" in names
        # every request still finished exactly once with full TTFT data
        assert tm.c_finished.value == 3
        assert tm.h_ttft.snapshot()["count"] >= 3


# ---------------------------------------------------------------------------
# TraceGuard -> telemetry
# ---------------------------------------------------------------------------

def test_trace_guard_reports_retrace(lm):
    from analytics_zoo_tpu.lint import RetraceError, trace_guard
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=3,
                           max_slots=2, prompt_buckets=(8, 16))
    rng = np.random.default_rng(4)
    done = {}
    eng.submit("w", rng.integers(1, 32, 5).astype(np.int32),
               on_done=lambda u, t: done.__setitem__(u, t))
    eng.drain()
    before = eng.telemetry.c_retraces.value
    with pytest.raises(RetraceError):
        with trace_guard(eng, name="drift"):
            eng.submit("big", rng.integers(1, 32, 12).astype(np.int32),
                       on_done=lambda u, t: done.__setitem__(u, t))
            eng.drain()
    # the guard reported the compile to the engine's telemetry BEFORE
    # raising: counted and visible in the trace
    assert eng.telemetry.c_retraces.value > before
    names = {e["name"]
             for e in eng.telemetry.dump_trace()["traceEvents"]}
    assert "retrace" in names


# ---------------------------------------------------------------------------
# block pool observability hook
# ---------------------------------------------------------------------------

def test_block_pool_event_cb():
    from analytics_zoo_tpu.serving.paged_cache import BlockPool

    events = []
    pool = BlockPool(3, 4, event_cb=lambda kind, **kw:
                     events.append((kind, kw)))
    b1 = pool.allocate()
    pool.insert(101, b1)
    pool.release(b1)                # parks in the LRU, hash-indexed
    pool.allocate()                 # takes the last free block
    assert pool.allocate() == b1    # free empty -> evicts b1
    assert pool.allocate() is None  # everything referenced
    kinds = [k for k, _ in events]
    assert kinds == ["eviction", "alloc_failure"]
    assert events[0][1]["block"] == b1


# ---------------------------------------------------------------------------
# abandoned-result accounting (ClusterServing._prune_abandoned)
# ---------------------------------------------------------------------------

def test_prune_abandoned_counts_and_traces():
    import flax.linen as nn

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import (
        ClusterServing, RespClient, ServingConfig)

    class _Double(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x * 2.0

    model = _Double()
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 4), np.float32))
    im = InferenceModel().load_flax(model, variables)
    cfg = ServingConfig(batch_size=4, result_ttl_s=5.0)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    try:
        counter = serving.telemetry.metrics.counter(
            "zoo_serving_requests_abandoned_total")
        assert counter.value == 0       # pre-registered, scrapeable
        now = time.monotonic()
        with serving._stats_lock:
            serving._written.append(("ghost", now - 6.0))
        client = RespClient("127.0.0.1", serving.port)
        serving._prune_abandoned(client, now)
        assert counter.value == 1
        events = serving.telemetry.dump_trace()["traceEvents"]
        ab = [e for e in events if e["name"] == "request_abandoned"]
        assert ab and ab[0]["args"]["uri"] == "ghost"
        assert ab[0]["args"]["age_s"] == pytest.approx(6.0, abs=0.5)
    finally:
        serving.stop()


# ---------------------------------------------------------------------------
# full stack: continuous engine behind the HTTP frontend
# ---------------------------------------------------------------------------

def test_http_metrics_merges_engine_registries(lm):
    """One scrape of ``GET /metrics`` must carry all three layers:
    frontend HTTP latency, serving-job counters, engine TTFT/queue/
    pool metrics — and ``GET /trace`` must export a schema-valid
    Chrome trace of the engine's spans."""
    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)

    model, variables = lm
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=4,
                           prompt_buckets=(8,))
    cfg = ServingConfig(prompt_col="tokens", batch_size=2,
                        continuous_batching=True, engine_slots=2,
                        engine_paged=True, engine_block_size=4)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=30,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        rng = np.random.default_rng(6)
        for i in range(2):
            inq.enqueue(f"q{i}", tokens=rng.integers(
                1, 32, 6).astype(np.int32))
        for i in range(2):
            assert outq.query(f"q{i}", timeout=600) is not None, i

        def get(path):
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=30)
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()

        status, body = get("/metrics")
        assert status == 200
        samples, types = _parse_prometheus(body.decode())
        assert samples["zoo_engine_requests_finished_total"] == 2.0
        assert 'zoo_engine_ttft_seconds{quantile="0.99"}' in samples
        assert "zoo_engine_queue_depth" in samples
        assert "zoo_engine_free_blocks" in samples
        assert "zoo_serving_requests_total" in samples
        assert "zoo_http_request_seconds_count" in samples
        assert types["zoo_engine_tpot_seconds"] == "summary"
        status, body = get("/trace")
        assert status == 200
        trace = json.loads(body)
        validate_chrome_trace(trace)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"queue_wait", "first_token", "request"} <= names
    finally:
        inq.close()
        outq.close()
        fe.stop()
        serving.stop()


def test_gauge_set_path():
    g = Gauge("g")
    g.set(3.5)
    assert g.value == 3.5 and g.snapshot() == 3.5
