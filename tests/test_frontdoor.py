"""QoS front door (serving/frontdoor.py + wiring): per-token
streaming, live cancellation, priority/fair-share admission, and
bounded-queue backpressure.  Contracts pinned here:

- scheduler units: weighted deficit-round-robin over (priority class,
  tenant) with aging promotion, appendleft refunds (preemption is
  cost-neutral), and the plain-deque surface the engine swaps in;
- parity: qos OFF (the default) keeps the plain FIFO deque and
  bit-identical greedy outputs — the front door is invisible until
  enabled;
- streaming: every generated token reaches the per-uri token stream
  in order (Redis path and SSE path), terminal markers arrive after
  the last token, and a preemption's re-emitted tokens deduplicate;
- live cancellation: explicit cancel and a mid-stream client
  disconnect both free BOTH pool tenants' blocks immediately — well
  before the result_ttl_s prune — while the TTL path still catches
  non-streaming abandoners (regression);
- backpressure: BacklogFull carries depth + cap and maps to HTTP 429
  with a finite Retry-After.
"""

import http.client
import json
import socket
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.learn.inference_model import InferenceModel
from analytics_zoo_tpu.models.lm import TransformerLM, generate
from analytics_zoo_tpu.serving import (
    BacklogFull, ClusterServing, HttpFrontend, InputQueue, OutputQueue,
    QosPolicy, RespClient, RespServer, ServingConfig, TokenEmitter,
    WeightedWaitQueue, retry_after_s)
from analytics_zoo_tpu.serving.continuous import ContinuousEngine
from analytics_zoo_tpu.serving.frontdoor import (
    MAX_DEADLINE_MS, ThroughputEstimator, decode_deadline,
    decode_priority, decode_str_field, encode_deadline, encode_priority,
    encode_str_field, sse_event, validate_deadline_ms)


class _R:
    """Minimal request record carrying the queue-visible fields."""

    def __init__(self, uri, priority="standard", tenant="", enq_t=None):
        self.uri = uri
        self.priority = priority
        self.tenant = tenant
        self.enq_t = time.monotonic() if enq_t is None else enq_t


# ---------------------------------------------------------------------------
# scheduler units
# ---------------------------------------------------------------------------

class TestQosPolicy:
    def test_class_rank_and_aging(self):
        pol = QosPolicy(aging_s=10.0)
        assert pol.class_rank("interactive", 0.0) == 0
        assert pol.class_rank("standard", 0.0) == 1
        assert pol.class_rank("batch", 0.0) == 2
        # aging promotes one class per aging_s of wait, floor 0
        assert pol.class_rank("batch", 10.0) == 1
        assert pol.class_rank("batch", 25.0) == 0
        assert pol.class_rank("batch", 1000.0) == 0
        # unknown classes behave as standard, never KeyError
        assert pol.class_rank("???", 0.0) == 1

    def test_weights_validated(self):
        with pytest.raises(ValueError):
            QosPolicy(weights={"interactive": 0.0})
        # partial dicts fill from defaults
        pol = QosPolicy(weights={"batch": 2.0})
        assert pol.weights["interactive"] == 8.0
        assert pol.weights["batch"] == 2.0


class TestWeightedWaitQueue:
    def test_weighted_share_across_classes(self):
        """With 8:4:1 weights and saturated per-class backlogs, a drain
        window grants service roughly proportional to weight."""
        q = WeightedWaitQueue(QosPolicy(aging_s=1e9))
        t0 = time.monotonic()
        for i in range(40):
            q.append(_R(f"i{i}", "interactive", enq_t=t0))
            q.append(_R(f"s{i}", "standard", enq_t=t0))
            q.append(_R(f"b{i}", "batch", enq_t=t0))
        first26 = [q.popleft().uri[0] for _ in range(26)]
        counts = {c: first26.count(c) for c in "isb"}
        # 26 grants at 8:4:1 => 16:8:2
        assert counts["i"] == 16 and counts["s"] == 8 and counts["b"] == 2

    def test_tenant_fair_share_within_class(self):
        """Two tenants of one class with equal weight alternate, even
        when one arrived with a deep backlog."""
        q = WeightedWaitQueue(QosPolicy(aging_s=1e9))
        t0 = time.monotonic()
        for i in range(10):
            q.append(_R(f"a{i}", "standard", tenant="A", enq_t=t0))
        for i in range(10):
            q.append(_R(f"b{i}", "standard", tenant="B", enq_t=t0))
        drained = [q.popleft().uri[0] for _ in range(8)]
        # strict alternation after the first grant of each
        assert drained.count("a") == 4 and drained.count("b") == 4

    def test_fifo_within_subqueue(self):
        q = WeightedWaitQueue(QosPolicy())
        t0 = time.monotonic()
        for i in range(5):
            q.append(_R(f"r{i}", "standard", enq_t=t0))
        assert [q.popleft().uri for _ in range(5)] == \
            [f"r{i}" for i in range(5)]

    def test_appendleft_refunds_stride(self):
        """popleft + appendleft (the preemption/blocked-requeue path)
        must be cost-neutral: the victim goes straight back to the
        head and its class pays no extra stride charge."""
        q = WeightedWaitQueue(QosPolicy(aging_s=1e9))
        t0 = time.monotonic()
        for i in range(4):
            q.append(_R(f"b{i}", "batch", enq_t=t0))
        q.append(_R("i0", "interactive", enq_t=t0))
        first = q.popleft()
        q.appendleft(first)
        assert q.popleft().uri == first.uri     # head restored
        assert len(q) == 4

    def test_aging_promotes_batch(self):
        """Aged batch work pays the interactive stride, so it keeps
        pace with fresh interactive traffic instead of being served
        once per 8 grants — the starvation bound in action."""
        now = time.monotonic()

        def drain4(aging_s):
            q = WeightedWaitQueue(QosPolicy(aging_s=aging_s))
            for i in range(4):      # long-waiting batch backlog
                q.append(_R(f"b{i}", "batch", enq_t=now - 1.0))
            for i in range(4):
                q.append(_R(f"i{i}", "interactive", enq_t=now))
            return [q.popleft().uri[0] for _ in range(4)]

        # without aging: one batch grant (FIFO tie-break), then the
        # 8:1 stride holds interactive ahead for the rest of the window
        assert drain4(1e9).count("b") == 1
        # aged to interactive weight: the classes alternate
        assert drain4(0.01).count("b") == 2

    def test_deque_surface(self):
        """The engine swaps this in for collections.deque: remove,
        iteration order, len/bool, and depths() must all behave."""
        q = WeightedWaitQueue(QosPolicy())
        assert not q and len(q) == 0
        rs = [_R(f"r{i}", p, tenant=t) for i, (p, t) in enumerate(
            [("interactive", "x"), ("batch", "y"), ("standard", "")])]
        for r in rs:
            q.append(r)
        assert q and len(q) == 3
        assert set(r.uri for r in q) == {"r0", "r1", "r2"}
        q.remove(rs[1])
        assert len(q) == 2
        with pytest.raises(ValueError):
            q.remove(rs[1])
        d = q.depths()
        assert d[("interactive", "x")] == 1
        assert d[("standard", "")] == 1


# ---------------------------------------------------------------------------
# emitter / codec / backpressure units
# ---------------------------------------------------------------------------

class TestTokenEmitter:
    def test_order_and_terminal(self):
        em = TokenEmitter()
        em.emit("u", 5, 0)
        em.emit("u", 7, 1)
        em.finish("u")
        em.emit("v", 9, 0)
        out = dict(em.drain())
        assert out["u"] == [("tok", 0, 5), ("tok", 1, 7), ("done", 0, 0)]
        assert out["v"] == [("tok", 0, 9)]
        assert em.drain() == []           # drained clean

    def test_overflow_drops_oldest(self):
        em = TokenEmitter(max_events=3)
        for i in range(5):
            em.emit("u", i, i)
        events = dict(em.drain())["u"]
        assert [e[1] for e in events] == [2, 3, 4]
        assert em.dropped == 2

    def test_discard(self):
        em = TokenEmitter()
        em.emit("u", 1, 0)
        em.discard("u")
        assert em.drain() == []


class TestCodecs:
    def test_priority_round_trip(self):
        for p in ("interactive", "standard", "batch"):
            assert decode_priority(
                str(int(np.asarray(encode_priority(p)))).encode()) == p
        with pytest.raises(ValueError):
            encode_priority("urgent")
        # corrupt wire values degrade to standard, never crash the pump
        assert decode_priority(b"99") == "standard"

    def test_str_field_round_trip(self):
        for s in ("", "tenant-a", "uniçode"):
            assert decode_str_field(encode_str_field(s)) == s

    def test_deadline_codec_round_trip(self):
        # header path and body path share ONE validator, so a budget
        # validated either way encodes/decodes identically
        for raw in (1500, 1500.0, "1500"):
            assert validate_deadline_ms(raw) == 1500
        wire = encode_deadline(1500, now_wall=1000.0)
        assert wire.dtype == np.int64
        assert int(wire) == 1_001_500
        # decode lands in the consumer's monotonic domain
        t = decode_deadline(wire, now_wall=1000.2, now_mono=50.0)
        assert t == pytest.approx(50.0 + 1.3)
        assert decode_deadline(np.int64(0)) == 0.0

    @pytest.mark.parametrize("bad", [
        -5, 0, float("nan"), float("inf"), -float("inf"),
        MAX_DEADLINE_MS + 1, True, False, "soon", None, [1500],
    ])
    def test_deadline_validation_rejects_with_pointed_message(self, bad):
        with pytest.raises(ValueError, match="deadline_ms"):
            validate_deadline_ms(bad)

    def test_deadline_ceiling_message_names_the_unit_bug(self):
        # an absolute epoch-ms timestamp where a budget belongs is the
        # classic client bug — the message must say so
        with pytest.raises(ValueError, match="24h ceiling"):
            validate_deadline_ms(1.7e12)

    def test_sse_event_format(self):
        b = sse_event("token", {"index": 0, "token": 5})
        assert b.startswith(b"event: token\ndata: ")
        assert b.endswith(b"\n\n")
        assert json.loads(b.split(b"data: ")[1]) == \
            {"index": 0, "token": 5}


class TestBackpressure:
    def test_backlog_full_attrs(self):
        broker = RespServer(port=0).start()     # no consumer loop
        try:
            inq = InputQueue(port=broker.port, max_backlog=2)
            for i in range(2):
                inq.enqueue(f"q{i}", x=np.ones(2, np.float32))
            with pytest.raises(BacklogFull) as ei:
                inq.enqueue("q2", x=np.ones(2, np.float32))
            assert ei.value.depth == 2
            assert ei.value.max_backlog == 2
            assert isinstance(ei.value, RuntimeError)   # back-compat
            # the rejecting entry was rolled back, not trimmed
            c = RespClient("127.0.0.1", broker.port)
            assert int(c.execute("XLEN", "serving_stream")) == 2
        finally:
            broker.stop()

    def test_retry_after_finite_and_clamped(self):
        assert retry_after_s(0, 4.0) == 1
        assert retry_after_s(40, 4.0) == 10
        assert retry_after_s(10 ** 9, 0.001) == 120     # hi clamp
        assert retry_after_s(5, 0.0) == 120             # rate=0 finite

    def test_retry_after_monotone_with_brownout_level(self):
        # satellite: the hint must grow (never shrink) as the ladder
        # deepens, stay finite at every level, and keep the clamps
        hints = [retry_after_s(40, 4.0, level=lv) for lv in range(5)]
        assert hints == sorted(hints)
        assert hints[0] == 10 and hints[1] == 20
        assert all(1 <= h <= 120 for h in hints)
        assert retry_after_s(10 ** 9, 4.0, level=4) == 120   # hi clamp
        assert retry_after_s(0, 4.0, level=4) == 1           # lo clamp
        # a negative level is treated as 0, not a discount
        assert retry_after_s(40, 4.0, level=-3) == \
            retry_after_s(40, 4.0, level=0)

    def test_throughput_estimator_ewma(self):
        est = ThroughputEstimator(fallback_rate=4.0)
        assert est.rate() == 4.0
        est.observe(0.0, now=0.0)
        est.observe(10.0, now=1.0)      # 10 req/s sample
        assert 4.0 < est.rate() <= 10.0
        est.observe(5.0, now=2.0)       # counter reset: ignored
        assert est.rate() > 0

    def test_http_429_with_retry_after(self):
        """A saturated admission queue answers /v1/generate with 429 +
        finite Retry-After (satellite a: BacklogFull -> HTTP 429)."""
        broker = RespServer(port=0).start()     # no consumer
        fe = HttpFrontend(redis_port=broker.port, timeout=2,
                          max_backlog=2).start()
        try:
            codes = []
            for _ in range(3):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", fe.port, timeout=15)
                conn.request("POST", "/v1/generate", json.dumps(
                    {"prompt": [1, 2, 3], "stream": True}),
                    {"Content-Type": "application/json"})
                resp = conn.getresponse()
                codes.append(resp.status)
                if resp.status == 429:
                    ra = resp.getheader("Retry-After")
                    body = json.loads(resp.read())
                    assert ra is not None and 1 <= int(ra) <= 120
                    assert body["retry_after_s"] == int(ra)
                    break
                resp.close()
            assert codes[-1] == 429, codes
            assert fe.c_rejected.value >= 1
        finally:
            fe.stop()
            broker.stop()


class _StubServing:
    """The minimal fleet surface the front door's admission matrix
    reads: live-pump count, brownout ladder level, and the healthz
    mode flags.  Every other attribute access raises, which the
    frontend's guards must absorb (a half-dead fleet must not take
    the HTTP path down with it)."""

    def __init__(self, live=1, level=0):
        self._live = live
        self._level = level

    def accepting_replicas(self):
        return self._live

    def brownout_level(self):
        return self._level

    def mode_flags(self):
        return {}


def _post_generate(fe, body, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    conn.request("POST", "/v1/generate", json.dumps(body), h)
    resp = conn.getresponse()
    out = (resp.status, dict(resp.getheaders()),
           json.loads(resp.read() or b"{}"))
    conn.close()
    return out


class TestAdmissionMatrix:
    """429-vs-503 contract (satellite: the codes are a protocol, not a
    mood): 429 means "the fleet is alive but won't take THIS request
    now — honor Retry-After"; 503 is reserved for zero live replicas.
    A browned-out class with live replicas must therefore see 429, and
    a dead fleet must see 503 even for a class the ladder admits."""

    def _stack(self, live, level):
        broker = RespServer(port=0).start()
        fe = HttpFrontend(redis_port=broker.port, timeout=1,
                          max_backlog=8).start()
        fe.serving = _StubServing(live=live, level=level)
        return broker, fe

    def test_brownout_shed_is_429_while_fleet_live(self):
        broker, fe = self._stack(live=1, level=1)
        try:
            status, headers, body = _post_generate(
                fe, {"prompt": [1, 2, 3], "priority": "batch"})
            assert status == 429
            assert "brownout level 1" in body["error"]
            assert "batch" in body["error"]
            ra = headers.get("Retry-After")
            assert ra is not None and 1 <= int(ra) <= 120
            # header and body carry the SAME hint by construction
            assert body["retry_after_s"] == int(ra)
        finally:
            fe.stop()
            broker.stop()

    def test_brownout_retry_after_monotone_with_level(self):
        hints = []
        for level in (1, 4):
            broker, fe = self._stack(live=1, level=level)
            try:
                status, headers, body = _post_generate(
                    fe, {"prompt": [1, 2, 3], "priority": "batch"})
                assert status == 429
                hints.append(int(headers["Retry-After"]))
            finally:
                fe.stop()
                broker.stop()
        assert hints[1] > hints[0], hints

    def test_admitted_class_passes_the_gate_under_brownout(self):
        # interactive survives every level; with no consumer behind
        # the broker the request times out at 504 — which PROVES it
        # was admitted (neither 429-shed nor 503-refused)
        broker, fe = self._stack(live=1, level=4)
        try:
            status, _, body = _post_generate(
                fe, {"prompt": [1, 2, 3], "priority": "interactive"})
            assert status == 504, body
        finally:
            fe.stop()
            broker.stop()

    def test_zero_live_replicas_is_503_even_for_admitted_class(self):
        for level in (0, 4):
            broker, fe = self._stack(live=0, level=level)
            try:
                status, headers, body = _post_generate(
                    fe, {"prompt": [1, 2, 3],
                         "priority": "interactive"})
                assert status == 503, (level, body)
                assert "no live replicas" in body["error"]
                ra = headers.get("Retry-After")
                assert ra is not None and 1 <= int(ra) <= 120
                assert body["retry_after_s"] == int(ra)
            finally:
                fe.stop()
                broker.stop()

    def test_healthz_carries_brownout_block(self):
        broker, fe = self._stack(live=1, level=2)
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", fe.port, timeout=15)
            conn.request("GET", "/healthz")
            h = json.loads(conn.getresponse().read())
            conn.close()
            assert h["brownout"]["level"] == 2
            assert h["brownout"]["admitting"] == \
                ["interactive", "standard"]
        finally:
            fe.stop()
            broker.stop()


class TestDeadlineHttpPaths:
    """The deadline budget's HTTP surface (satellite: codec
    hardening): header and body are ONE validated field — agreeing
    duplicates pass, disagreement and malformed values are a pointed
    400, and a valid budget reaches the wire (the request then times
    out at 504 against a consumer-less broker, proving admission)."""

    def _stack(self):
        broker = RespServer(port=0).start()
        fe = HttpFrontend(redis_port=broker.port, timeout=1,
                          max_backlog=8).start()
        return broker, fe

    @pytest.mark.parametrize("send", ["header", "body", "both"])
    def test_valid_budget_admits_via_either_path(self, send):
        broker, fe = self._stack()
        try:
            body = {"prompt": [1, 2, 3]}
            headers = {}
            if send in ("header", "both"):
                headers["X-Request-Deadline-Ms"] = "30000"
            if send in ("body", "both"):
                body["deadline_ms"] = 30000
            status, _, resp = _post_generate(fe, body, headers)
            assert status == 504, (send, resp)
        finally:
            fe.stop()
            broker.stop()

    def test_disagreeing_header_and_body_is_400(self):
        broker, fe = self._stack()
        try:
            status, _, resp = _post_generate(
                fe, {"prompt": [1, 2, 3], "deadline_ms": 5000},
                {"X-Request-Deadline-Ms": "6000"})
            assert status == 400
            assert "disagree" in resp["error"]
        finally:
            fe.stop()
            broker.stop()

    @pytest.mark.parametrize("bad", ["-5", "0", "nan", "inf", "soon",
                                     str(MAX_DEADLINE_MS + 1)])
    def test_malformed_header_budget_is_400(self, bad):
        broker, fe = self._stack()
        try:
            status, _, resp = _post_generate(
                fe, {"prompt": [1, 2, 3]},
                {"X-Request-Deadline-Ms": bad})
            assert status == 400, (bad, resp)
            assert "deadline_ms" in resp["error"]
        finally:
            fe.stop()
            broker.stop()

    def test_malformed_body_budget_is_400(self):
        broker, fe = self._stack()
        try:
            for bad in (-5, 0, "soon", MAX_DEADLINE_MS + 1, True):
                status, _, resp = _post_generate(
                    fe, {"prompt": [1, 2, 3], "deadline_ms": bad})
                assert status == 400, (bad, resp)
                assert "deadline_ms" in resp["error"]
        finally:
            fe.stop()
            broker.stop()


# ---------------------------------------------------------------------------
# engine-level: on_token hook, qos parity, composed abort
# ---------------------------------------------------------------------------

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


class TestEngineStreamingAndQos:
    def test_submit_rejects_unknown_priority(self, lm):
        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=3,
                               max_slots=2, prompt_buckets=(8,))
        with pytest.raises(ValueError, match="priority"):
            eng.submit("u", np.ones(3, np.int32),
                       on_done=lambda *a: None, priority="urgent")

    def test_on_token_streams_every_token_in_order(self, lm):
        """The per-tick hook sees exactly the final token sequence, in
        order, with contiguous indices."""
        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=5,
                               max_slots=2, prompt_buckets=(8,))
        rng = np.random.default_rng(0)
        seen, results = {}, {}
        for i in range(3):
            eng.submit(f"s{i}", rng.integers(1, 32, 5).astype(np.int32),
                       on_done=lambda u, t: results.__setitem__(u, t),
                       on_token=lambda u, t, ix: seen.setdefault(
                           u, []).append((ix, t)))
        eng.drain()
        assert set(seen) == set(results)
        for u, pairs in seen.items():
            assert [ix for ix, _ in pairs] == list(range(5))
            np.testing.assert_array_equal(
                np.asarray([t for _, t in pairs]), results[u])

    def test_qos_off_is_plain_deque(self, lm):
        import collections

        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=3,
                               max_slots=2, prompt_buckets=(8,))
        assert type(eng._waiting) is collections.deque
        assert eng.cache_metrics()["qos"] is False

    def test_qos_on_parity_with_qos_off(self, lm):
        """Same workload through a qos engine and a plain engine: greedy
        outputs are identical (the scheduler only reorders admission)."""
        model, variables = lm
        rng = np.random.default_rng(3)
        prompts = {f"p{i}": rng.integers(1, 32, 5).astype(np.int32)
                   for i in range(6)}
        outs = []
        for qos in (None, QosPolicy()):
            eng = ContinuousEngine(model, variables, max_new_tokens=4,
                                   max_slots=2, prompt_buckets=(8,),
                                   qos=qos)
            res = {}
            for i, (u, p) in enumerate(prompts.items()):
                eng.submit(u, p,
                           on_done=lambda u, t: res.__setitem__(u, t),
                           priority=("interactive", "standard",
                                     "batch")[i % 3])
            eng.drain()
            outs.append(res)
        assert set(outs[0]) == set(outs[1]) == set(prompts)
        for u in prompts:
            np.testing.assert_array_equal(outs[0][u], outs[1][u],
                                          err_msg=u)
            solo = np.asarray(generate(
                model, variables, jnp.asarray(prompts[u][None]), 4))[0]
            np.testing.assert_array_equal(outs[0][u], solo, err_msg=u)

    def test_qos_grant_order_prefers_interactive(self, lm):
        """More waiters than slots: interactive submissions admitted
        strictly before batch ones that arrived earlier."""
        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=3,
                               max_slots=1, prompt_buckets=(8,),
                               qos=QosPolicy(aging_s=1e9))
        rng = np.random.default_rng(4)
        order = []
        done = {}
        for i in range(3):
            eng.submit(f"b{i}", rng.integers(1, 32, 4).astype(np.int32),
                       on_done=lambda u, t: done.__setitem__(u, t),
                       on_token=lambda u, t, ix: (
                           order.append(u) if ix == 0 else None),
                       priority="batch")
        eng.submit("i0", rng.integers(1, 32, 4).astype(np.int32),
                   on_done=lambda u, t: done.__setitem__(u, t),
                   on_token=lambda u, t, ix: (
                       order.append(u) if ix == 0 else None),
                   priority="interactive")
        eng.drain()
        # b0 may have been admitted before i0 arrived (1 slot), but i0
        # must outrank the REMAINING batch backlog
        assert order.index("i0") <= 1, order
        assert len(done) == 4

    def test_midstream_abort_spec_paged_chunked_frees_both_pools(
            self, lm):
        """The acceptance composition: a speculative + paged + chunked
        engine aborted mid-stream (after its first streamed token)
        returns BOTH tenants' pools to zero references immediately."""
        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=6,
                               max_slots=2, prompt_buckets=(8, 16),
                               draft_model=model,
                               draft_variables=variables,
                               speculation_k=2, paged=True,
                               block_size=4, chunked=True,
                               tick_token_budget=16,
                               enable_prefix_cache=False,
                               qos=QosPolicy())
        rng = np.random.default_rng(5)
        streamed = {}
        done = {}
        for i in range(3):
            eng.submit(f"a{i}", rng.integers(1, 32, 12).astype(np.int32),
                       on_done=lambda u, t: done.__setitem__(u, t),
                       on_token=lambda u, t, ix: streamed.setdefault(
                           u, []).append(t),
                       priority="interactive", tenant=f"t{i % 2}")
        # step until at least one row has streamed a token mid-flight
        for _ in range(40):
            eng.step()
            if streamed and eng.n_active > 0:
                break
        assert streamed, "no tokens streamed before abort"
        live = [u for u in streamed if u not in done] or \
            [f"a{i}" for i in range(3) if f"a{i}" not in done]
        assert live, "everything finished before the abort"
        for u in {f"a{i}" for i in range(3)} - set(done):
            assert eng.abort(u) is True
        m = eng.cache_metrics()
        assert m["referenced_blocks"] == 0, m
        assert m["draft_referenced_blocks"] == 0, m
        with eng._pool_lock:
            eng._pool.check()
            eng._dpool.check()


# ---------------------------------------------------------------------------
# wire level: streaming + cancellation through the serving stack
# ---------------------------------------------------------------------------

def _spec_stack(max_new=48, result_ttl_s=300.0, timeout=60):
    """spec + paged + chunked + qos ClusterServing with an SSE-capable
    HTTP frontend — the full acceptance composition."""
    model = _tiny_lm()
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    im = InferenceModel().load_flax_generator(
        model, variables, max_new_tokens=max_new, prompt_buckets=(8,),
        draft_model=model, draft_variables=variables, speculation_k=2)
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=4, engine_chunked=True,
                        engine_tick_token_budget=16, qos_enabled=True,
                        result_ttl_s=result_ttl_s)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=timeout,
                      serving=serving).start()
    return model, variables, serving, fe


class TestStreamingStack:
    def test_redis_stream_and_sse_and_disconnect(self):
        """One stack, three contracts: (1) the Redis-queue per-token
        stream equals solo generation with a done terminal; (2) SSE
        over /v1/generate delivers >= 2 token chunks before completion;
        (3) a client socket dropped mid-stream frees BOTH pools' blocks
        well before result_ttl_s (300s here — only live cancellation
        can explain sub-15s reclamation)."""
        model, variables, serving, fe = _spec_stack()
        try:
            rng = np.random.default_rng(7)
            p = rng.integers(1, 32, 5).astype(np.int32)
            ref = np.asarray(generate(model, variables,
                                      jnp.asarray(p[None]), 48))[0]

            # (1) Redis-queue streaming
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            uri = inq.enqueue("st1", tokens=p, stream=np.int32(1),
                              priority=encode_priority("interactive"),
                              tenant=encode_str_field("tA"))
            evs = [e for e in outq.stream_events(uri, timeout=60)
                   if "ping" not in e]
            assert evs[-1] == {"done": True}
            toks = [e["token"] for e in evs[:-1]]
            assert [e["index"] for e in evs[:-1]] == list(range(48))
            np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                          ref)

            # (2) SSE end-to-end
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=90)
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": p.tolist(), "stream": True,
                 "priority": "interactive", "tenant": "tB"}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type", "").startswith(
                "text/event-stream")
            raw = resp.read().decode()
            events = [c for c in raw.split("\n\n")
                      if c.strip() and not c.startswith(":")]
            tok_events = [c for c in events
                          if c.startswith("event: token")]
            assert len(tok_events) >= 2
            assert any(c.startswith("event: done") for c in events)
            sse_toks = [json.loads(c.split("data: ", 1)[1])["token"]
                        for c in tok_events]
            np.testing.assert_array_equal(
                np.asarray(sse_toks, np.int32), ref)

            # (3) disconnect mid-stream -> both pools reclaimed NOW
            s = socket.create_connection(("127.0.0.1", fe.port),
                                         timeout=30)
            body = json.dumps({"tokens": p.tolist(), "stream": True})
            s.sendall((f"POST /v1/generate HTTP/1.1\r\n"
                       f"Host: x\r\nContent-Type: application/json\r\n"
                       f"Content-Length: {len(body)}\r\n\r\n"
                       f"{body}").encode())
            buf = b""
            while b"event: token" not in buf:
                chunk = s.recv(4096)
                assert chunk, f"stream closed early: {buf!r}"
                buf += chunk
            # hard drop with data in flight
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         b"\x01\x00\x00\x00\x00\x00\x00\x00")
            s.close()
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                m = serving.engine.cache_metrics()
                if (m["referenced_blocks"] == 0
                        and m["draft_referenced_blocks"] == 0
                        and fe.c_disconnects.value >= 1):
                    break
                time.sleep(0.05)
            m = serving.engine.cache_metrics()
            assert m["referenced_blocks"] == 0, m
            assert m["draft_referenced_blocks"] == 0, m
            assert fe.c_disconnects.value >= 1
            assert serving.telemetry.metrics.counter(
                "zoo_serving_stream_disconnects_total").value >= 1

            # the stack still serves after the violence
            uri2 = inq.enqueue("after", tokens=p)
            r = outq.query(uri2, timeout=60)
            np.testing.assert_array_equal(np.asarray(r), ref)
        finally:
            fe.stop()
            serving.stop()

    def test_explicit_cancel_frees_blocks(self):
        """InputQueue.cancel mid-generation: the cancelled terminal
        reaches the streaming client and both pools drop to zero
        references long before the 300s TTL."""
        model, variables, serving, fe = _spec_stack()
        try:
            rng = np.random.default_rng(9)
            p = rng.integers(1, 32, 5).astype(np.int32)
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            uri = inq.enqueue("c1", tokens=p, stream=np.int32(1))
            saw = []
            for ev in outq.stream_events(uri, timeout=60):
                if "ping" in ev:
                    continue
                saw.append(ev)
                if "token" in ev and len(saw) == 1:
                    inq.cancel(uri)
                if any(k in ev for k in
                       ("done", "cancelled", "error")):
                    break
            assert {"cancelled": True} in saw or {"done": True} in saw
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                m = serving.engine.cache_metrics()
                if (m["referenced_blocks"] == 0
                        and m["draft_referenced_blocks"] == 0):
                    break
                time.sleep(0.05)
            m = serving.engine.cache_metrics()
            assert m["referenced_blocks"] == 0, m
            assert m["draft_referenced_blocks"] == 0, m
            if {"cancelled": True} in saw:
                assert serving.telemetry.metrics.counter(
                    "zoo_serving_requests_cancelled_total").value >= 1

            # /v1/cancel on an unknown uri is a harmless 200
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=30)
            conn.request("POST", "/v1/cancel",
                         json.dumps({"uri": "ghost"}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["status"] == "cancelling"
        finally:
            fe.stop()
            serving.stop()

    def test_ttl_prune_still_catches_nonstreaming_abandoners(self):
        """Regression: live cancellation must not replace the TTL
        safety net — a non-streaming result nobody queries is still
        pruned after result_ttl_s."""
        model, variables, serving, fe = _spec_stack(max_new=4)
        try:
            rng = np.random.default_rng(11)
            p = rng.integers(1, 32, 5).astype(np.int32)
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            # warm the engine first: a short TTL during the compile
            # would hit the IN-FLIGHT prune, not the result prune
            assert outq.query(inq.enqueue("warm", tokens=p),
                              timeout=60) is not None
            serving.config.result_ttl_s = 0.5
            inq.enqueue("ghost", tokens=p)
            c = RespClient("127.0.0.1", serving.port)
            seen = False
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if c.execute("HGETALL", "result:ghost"):
                    seen = True
                    break
                time.sleep(0.02)
            assert seen
            time.sleep(0.6)                     # ttl elapses
            inq.enqueue("live", tokens=p)       # any batch prunes
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if not c.execute("HGETALL", "result:ghost"):
                    break
                time.sleep(0.02)
            assert not c.execute("HGETALL", "result:ghost")
        finally:
            fe.stop()
            serving.stop()

    def test_healthz_enriched(self):
        model, variables, serving, fe = _spec_stack(max_new=4)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=30)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            h = json.loads(resp.read())
            assert resp.status == 200
            assert h["status"] == "ok"          # legacy key kept
            assert h["accepting"] is True and h["backpressure"] is False
            assert h["backlog"] == 0
            eng = h["engine"]
            assert eng == {"continuous": True, "paged": True,
                           "chunked": True, "speculative": True,
                           "qos": True, "brownout": False}
            assert h["brownout"] == {
                "level": 0,
                "admitting": ["interactive", "standard", "batch"]}
        finally:
            fe.stop()
            serving.stop()


# ---------------------------------------------------------------------------
# a tick's tokens leave under the NEXT step's device call: what is
# delivered, and in what order, is what the pump delivered when it waited
# for the broker between two steps (server._flush_emitter,
# engine.after_dispatch)
# ---------------------------------------------------------------------------

def _plain_stack(max_new=16, slots=3, **cfg_kw):
    """paged + chunked ClusterServing, no draft, no front end."""
    model = _tiny_lm()
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    im = InferenceModel().load_flax_generator(
        model, variables, max_new_tokens=max_new, prompt_buckets=(8,))
    cfg = ServingConfig(**dict(dict(
        prompt_col="tokens", continuous_batching=True, engine_slots=slots,
        engine_paged=True, engine_block_size=4, engine_chunked=True,
        engine_tick_token_budget=16), **cfg_kw))
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    return model, variables, serving


def _raw_stream(client, uri):
    """The token stream as the broker holds it: ('tok', index, token),
    ('restart', attempt) and terminal markers, in entry order."""
    out = []
    for _, flat in client.execute("XRANGE", "tok:" + uri, "-", "+"):
        f = {flat[i].decode(): flat[i + 1] for i in range(0, len(flat), 2)}
        if "t" in f:
            out.append(("tok", int(f["i"]), int(f["t"])))
        elif "restart" in f:
            out.append(("restart", int(f["restart"])))
        else:
            out.append((next(iter(f)),))
    return out


def _wait_results(client, uris, timeout=120):
    deadline = time.monotonic() + timeout
    for u in uris:
        while not client.execute("HGETALL", "result:" + u):
            assert time.monotonic() < deadline, f"{u} never landed"
            time.sleep(0.02)


class TestTokensLeaveUnderTheDevice:
    @pytest.fixture(scope="class")
    def stack(self):
        model, variables, serving = _plain_stack()
        inq = InputQueue(port=serving.port)
        outq = OutputQueue(port=serving.port)
        try:
            yield model, variables, serving, inq, outq
        finally:
            inq.close()
            outq.close()
            serving.stop()

    def test_concurrent_streams_in_index_order_terminal_last(self, stack):
        """Five streams over three slots (two wait for a slot): each
        arrives in index order with ``done`` last, and the greedy tokens
        are bitwise those of solo generation."""
        model, variables, serving, inq, outq = stack
        rng = np.random.default_rng(41)
        prompts = {f"o{i}": rng.integers(1, 32, 3 + i).astype(np.int32)
                   for i in range(5)}
        for u, p in prompts.items():
            inq.enqueue(u, tokens=p, stream=np.int32(1))
        c = RespClient("127.0.0.1", serving.port)
        _wait_results(c, prompts)
        time.sleep(0.3)
        for u, p in prompts.items():
            raw = _raw_stream(c, u)         # nothing deduplicated
            assert raw[-1] == ("done",), (u, raw)
            assert [e[1] for e in raw[:-1]] == list(range(16)), (u, raw)
            ref = np.asarray(generate(model, variables,
                                      jnp.asarray(p[None]), 16))[0]
            np.testing.assert_array_equal(
                np.asarray([e[2] for e in raw[:-1]], np.int32), ref)
            evs = [e for e in outq.stream_events(u, timeout=60)
                   if "ping" not in e]
            assert evs[-1] == {"done": True} and len(evs) == 17
            np.testing.assert_array_equal(
                np.asarray(outq.query(u, timeout=60)), ref)
        c.close()
        tm = serving.engine.telemetry
        assert tm.c_flush_events.value >= 5 * 17
        assert tm.c_flush_overlapped.value >= 5 * 17 // 2

    def test_last_done_leaves_in_the_pass_the_engine_goes_idle(
            self, stack, monkeypatch):
        """The last running request's final token and ``done`` do not
        wait for a next device call: with the idle claim made to take
        1.5 s, they are in the broker long before the next pass ends."""
        model, variables, serving, inq, outq = stack
        read = ClusterServing._read_batch

        def slow_idle_claim(self, client, consumer, block_ms=200):
            if block_ms >= 200:             # the engine is empty
                time.sleep(1.5)
            return read(self, client, consumer, block_ms)

        monkeypatch.setattr(ClusterServing, "_read_batch", slow_idle_claim)
        p = np.arange(1, 6, dtype=np.int32)
        inq.enqueue("last", tokens=p, stream=np.int32(1))
        c = RespClient("127.0.0.1", serving.port)
        _wait_results(c, ["last"])          # published inside the step
        t_result = time.monotonic()
        deadline = t_result + 10
        while True:
            raw = _raw_stream(c, "last")
            if raw and raw[-1] == ("done",):
                break
            assert time.monotonic() < deadline, raw
            time.sleep(0.01)
        assert time.monotonic() - t_result < 1.0
        assert [e[1] for e in raw[:-1]] == list(range(16))
        c.execute("DEL", "tok:last")
        c.close()

    def test_cancelled_marker_with_no_step_behind_it_arrives(self, stack):
        """A cancel that beat its request's admission: the marker is put
        in the emitter at claim time, no device call follows, and it
        leaves at the end of that pass."""
        model, variables, serving, inq, outq = stack
        inq.cancel("early")
        deadline = time.monotonic() + 30
        while inq.client.execute("XRANGE", "serving_cancel", "-", "+"):
            assert time.monotonic() < deadline      # the pump parks it
            time.sleep(0.02)
        inq.enqueue("early", tokens=np.arange(1, 5, dtype=np.int32),
                    stream=np.int32(1))
        evs = [e for e in outq.stream_events("early", timeout=30)
               if "ping" not in e]
        assert evs == [{"cancelled": True}]

    def test_midstream_cancel_ends_the_stream_in_order(self, stack):
        model, variables, serving, inq, outq = stack
        inq.enqueue("mid", tokens=np.arange(1, 6, dtype=np.int32),
                    stream=np.int32(1))
        saw = []
        for ev in outq.stream_events("mid", timeout=60):
            if "ping" in ev:
                continue
            saw.append(ev)
            if len(saw) == 1:
                inq.cancel("mid")
        assert saw[-1] in ({"cancelled": True}, {"done": True})
        assert [e["index"] for e in saw[:-1]] == list(range(len(saw) - 1))

    def test_submit_error_marker_arrives(self, stack):
        """A streaming request the engine refuses (prompt over the
        widest bucket): the ``error`` marker has no step behind it."""
        model, variables, serving, inq, outq = stack
        inq.enqueue("wide", tokens=np.ones(40, np.int32),
                    stream=np.int32(1))
        evs = [e for e in outq.stream_events("wide", timeout=30)
               if "ping" not in e]
        assert len(evs) == 1 and "submit failed" in evs[0]["error"]


def test_graceful_kill_pump_loses_no_stream_event():
    """``kill_pump`` while streams are in flight on the killed replica:
    it drains in place, and every stream is whole — indices in order,
    ``done`` last, tokens those of solo generation."""
    model, variables, serving = _plain_stack(slots=2, n_replicas=2)
    try:
        inq = InputQueue(port=serving.port)
        outq = OutputQueue(port=serving.port)
        rng = np.random.default_rng(43)
        prompts = {f"k{i}": rng.integers(1, 32, 3 + i % 4).astype(np.int32)
                   for i in range(6)}
        for u, p in prompts.items():
            inq.enqueue(u, tokens=p, stream=np.int32(1))
        c = RespClient("127.0.0.1", serving.port)
        deadline = time.monotonic() + 120
        while not any(len(_raw_stream(c, u)) for u in prompts):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        serving.kill_pump(1)                # streams are in flight
        _wait_results(c, prompts)
        t1 = next(t for t in serving._threads
                  if t.name == "zoo-serving-cb-1")
        t1.join(timeout=60)
        assert not t1.is_alive(), "pump 1 never exited"
        assert all(n > 0 for n in serving.router_status()["routed"])
        for u, p in prompts.items():
            raw = _raw_stream(c, u)
            assert raw[-1] == ("done",), (u, raw)
            assert [e[1] for e in raw[:-1]] == list(range(16)), (u, raw)
            ref = np.asarray(generate(model, variables,
                                      jnp.asarray(p[None]), 16))[0]
            np.testing.assert_array_equal(
                np.asarray([e[2] for e in raw[:-1]], np.int32), ref)
        c.close()
    finally:
        serving.stop()


def test_no_stale_token_behind_a_restart_marker():
    """An injected pump crash mid-generation: the dead pump's tokens
    are in the broker BEFORE the supervisor writes the redispatch's
    ``restart`` marker, so what follows the marker is the new attempt
    alone — indices 0..n-1 in order, then ``done``."""
    model, variables, serving = _plain_stack(
        max_new=12, slots=1, n_replicas=2, retry_budget=3,
        fault_injection=[{"kind": "crash_pump", "replica": 1,
                          "at_tick": 5}])
    try:
        inq = InputQueue(port=serving.port)
        rng = np.random.default_rng(47)
        prompts = {f"z{i}": rng.integers(1, 32, 3 + i % 4).astype(np.int32)
                   for i in range(6)}
        for u, p in prompts.items():
            inq.enqueue(u, tokens=p, stream=np.int32(1))
        c = RespClient("127.0.0.1", serving.port)
        _wait_results(c, prompts)
        time.sleep(0.3)
        status = serving.router_status()
        assert status["death_reasons"] == [None, "pump_exception"]
        restarted = 0
        for u, p in prompts.items():
            raw = _raw_stream(c, u)
            assert raw[-1] == ("done",), (u, raw)
            marks = [j for j, e in enumerate(raw) if e[0] == "restart"]
            restarted += bool(marks)
            before = raw[:marks[-1]] if marks else []
            after = raw[marks[-1] + 1:-1] if marks else raw[:-1]
            # the dead attempt: a prefix of the indices, in order
            dead = [e[1] for e in before if e[0] == "tok"]
            assert dead == list(range(len(dead))), (u, raw)
            assert [e[1] for e in after] == list(range(12)), (u, raw)
            ref = np.asarray(generate(model, variables,
                                      jnp.asarray(p[None]), 12))[0]
            np.testing.assert_array_equal(
                np.asarray([e[2] for e in after], np.int32), ref)
        assert restarted >= 1 and status["redispatched"] >= restarted
        c.close()
    finally:
        serving.stop()
