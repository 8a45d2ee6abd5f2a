"""Cluster Serving: RESP broker, queues, serving loop, HTTP frontend.

Mirrors the reference's serving test surface (SURVEY.md §4: batching-logic
specs without the streaming substrate, embedded/local Redis) — here the
embedded RESP broker plays local Redis, and a tiny flax model serves real
predictions end-to-end.
"""

import http.client
import json
import time

import flax.linen as nn
import jax
import numpy as np
import pytest

from analytics_zoo_tpu.learn.inference_model import InferenceModel
from analytics_zoo_tpu.serving import (
    ClusterServing, HttpFrontend, InputQueue, OutputQueue, RespClient,
    RespServer, ServingConfig)


class _Double(nn.Module):
    @nn.compact
    def __call__(self, x):
        return x * 2.0


def _serving(batch_size=8, timeout_ms=20.0):
    model = _Double()
    variables = model.init(jax.random.key(0), np.zeros((1, 4), np.float32))
    im = InferenceModel().load_flax(model, variables)
    cfg = ServingConfig(batch_size=batch_size, batch_timeout_ms=timeout_ms)
    return ClusterServing(im, cfg, embedded_broker=True).start()


# ---------------------------------------------------------------------------
# RESP broker
# ---------------------------------------------------------------------------

class TestRespBroker:
    def test_basic_commands(self):
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)
            assert c.execute("PING") in (b"PONG", "PONG")
            c.execute("HSET", "h", "f", "v")
            assert c.execute("HGETALL", "h") == [b"f", b"v"]
            c.execute("DEL", "h")
            assert c.execute("HGETALL", "h") == []
        finally:
            srv.stop()

    def test_stream_xadd_xread_xlen(self):
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)
            id1 = c.execute("XADD", "s", "*", "k", "1")
            c.execute("XADD", "s", "*", "k", "2")
            assert int(c.execute("XLEN", "s")) == 2
            out = c.execute("XREAD", "COUNT", "10", "STREAMS", "s", "0-0")
            entries = out[0][1]
            assert len(entries) == 2
            out2 = c.execute("XREAD", "COUNT", "10", "STREAMS", "s", id1)
            assert len(out2[0][1]) == 1
        finally:
            srv.stop()

    def test_a_follower_reads_the_tail_whatever_the_clock_does(self,
                                                               monkeypatch):
        """A reader that follows a stream gets what lies above its last id
        and no more, found from the stream's end (``Stream.after``): ids
        grow even when the wall clock steps back, entries deleted in the
        middle leave the order standing, and a cursor from before the
        first entry reads them all."""
        import analytics_zoo_tpu.serving.resp as resp

        now = [1000.0]
        monkeypatch.setattr(resp.time, "time", lambda: now[0])
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)
            ids = []
            for i in range(6):
                ids.append(c.execute("XADD", "s", "*", "k", str(i)))
                now[0] -= 0.25 if i == 2 else -0.001
            key = lambda e: tuple(map(int, e.split(b"-")))
            assert sorted(ids, key=key) == ids
            read = lambda last, *opt: [e[0] for e in c.execute(
                "XREAD", *opt, "STREAMS", "s", last)[0][1]]
            assert read("0-0") == ids
            assert read(ids[3]) == ids[4:]
            assert read(ids[1], "COUNT", "2") == ids[2:4]
            assert c.execute("XREAD", "STREAMS", "s", ids[5]) is None
            c.execute("XDEL", "s", ids[2], ids[4])
            assert read(ids[0]) == [ids[1], ids[3], ids[5]]
            c.execute("XGROUP", "CREATE", "s", "g", ids[3])
            got = c.execute("XREADGROUP", "GROUP", "g", "w", "COUNT", "8",
                            "STREAMS", "s", ">")
            assert [e[0] for e in got[0][1]] == [ids[5]]
        finally:
            srv.stop()

    def test_split_pipeline_returns_what_pipeline_returned(self):
        """send() + collect() are the two halves of pipeline(): the
        same commands give the same replies, on the same connection, and
        the connection is in step afterwards."""
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)

            def cmds(k):
                return [("HSET", k, "f", "v"), ("HGETALL", k),
                        ("SADD", k + "s", "a", "b"), ("PING",),
                        ("XLEN", k + "x")]
            whole = c.pipeline(cmds("p"))
            assert c.send(cmds("q")) is None
            assert c.collect() == whole == [1, [b"f", b"v"], 2, "PONG", 0]
            assert c.collect() == []            # nothing outstanding
            assert c.execute("PING") == "PONG"
            # the replies wait in the socket for as long as they must
            c.send([("XADD", "t", "*", "i", str(i)) for i in range(64)])
            time.sleep(0.2)
            assert len(c.collect()) == 64
            assert int(c.execute("XLEN", "t")) == 64
        finally:
            srv.stop()

    def test_split_pipeline_error_in_the_middle_is_raised_at_collect(self):
        """An error reply does not surface at send(); collect() reads
        EVERY reply and then raises the first error, so the next command
        reads its own reply."""
        from analytics_zoo_tpu.serving.resp import RedisError
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)
            c.send([("HSET", "h", "f", "v"), ("NOSUCH", "x"),
                    ("XGROUP", "DESTROY", "s", "g"), ("HGETALL", "h")])
            with pytest.raises(RedisError, match="unknown command NOSUCH"):
                c.collect()
            assert c.collect() == []
            assert c.execute("HGETALL", "h") == [b"f", b"v"]
        finally:
            srv.stop()

    @pytest.mark.parametrize("second", ["send", "pipeline", "execute"])
    def test_one_pipeline_outstanding_on_a_connection(self, second):
        """Replies come back in the order the commands went, so nothing
        may be written behind a pipeline whose replies are unread: a
        second send (or any command) before collect() is refused, and
        the refusal leaves the outstanding pipeline collectable."""
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)
            c.send([("HSET", "h", "f", "v"), ("PING",)])
            with pytest.raises(RuntimeError, match="outstanding"):
                if second == "execute":
                    c.execute("PING")
                else:
                    getattr(c, second)([("PING",)])
            assert c.collect() == [1, "PONG"]
            assert c.pipeline([("PING",)]) == ["PONG"]
        finally:
            srv.stop()

    def test_xrange_id_bounds(self):
        """XRANGE honours real Redis range semantics — the supervisor's
        redispatch re-reads a dead replica's entries by EXACT id, so a
        broker that ignores the bounds resurrects the wrong request."""
        srv = RespServer(port=0).start()
        try:
            c = RespClient("127.0.0.1", srv.port)
            ids = [c.execute("XADD", "s", "*", "k", str(i))
                   for i in range(4)]
            # full range: '-' .. '+'
            assert len(c.execute("XRANGE", "s", "-", "+")) == 4
            # exact-id lookup returns THAT entry, not the stream head
            for i, eid in enumerate(ids):
                got = c.execute("XRANGE", "s", eid, eid)
                assert len(got) == 1
                assert got[0][0] == eid
                assert got[0][1] == [b"k", str(i).encode()]
            # sub-range is inclusive on both ends
            mid = c.execute("XRANGE", "s", ids[1], ids[2])
            assert [e[0] for e in mid] == [ids[1], ids[2]]
            # COUNT caps the reply
            assert len(c.execute(
                "XRANGE", "s", "-", "+", "COUNT", "2")) == 2
            # a bare-ms start bound means seq 0 (catches everything
            # at that millisecond)
            ms = ids[0].decode().split("-")[0]
            assert len(c.execute("XRANGE", "s", ms, "+")) == 4
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# end-to-end: queues -> serving loop -> results
# ---------------------------------------------------------------------------

class TestClusterServing:
    def test_enqueue_predict_query(self):
        serving = _serving()
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            x = np.arange(4, dtype=np.float32)
            uri = inq.enqueue("req-1", x=x)
            r = outq.query(uri, timeout=10)
            np.testing.assert_allclose(r, x * 2.0)
        finally:
            serving.stop()

    def test_micro_batching_many_requests(self):
        serving = _serving(batch_size=4)
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            xs = {f"r{i}": np.full(4, i, np.float32) for i in range(12)}
            for uri, x in xs.items():
                inq.enqueue(uri, x=x)
            for uri, x in xs.items():
                r = outq.query(uri, timeout=10)
                np.testing.assert_allclose(r, x * 2.0, err_msg=uri)
            assert serving.stats["requests"] == 12
            assert serving.stats["batches"] >= 3   # batch cap is 4
        finally:
            serving.stop()

    def test_backlog_and_dequeue(self):
        serving = _serving()
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            for i in range(3):
                inq.enqueue(f"d{i}", x=np.ones(4, np.float32))
            deadline = time.monotonic() + 10
            got = {}
            while len(got) < 3 and time.monotonic() < deadline:
                got.update(outq.dequeue())
                time.sleep(0.02)
            assert set(got) == {"d0", "d1", "d2"}
            assert serving.backlog() >= 0
        finally:
            serving.stop()

    def test_backlog_drops_to_zero_after_consumption(self):
        """XLEN must mean PENDING entries, not total retained history."""
        serving = _serving()
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            for i in range(5):
                inq.enqueue(f"b{i}", x=np.ones(4, np.float32))
            for i in range(5):
                assert outq.query(f"b{i}", timeout=10) is not None
            deadline = time.monotonic() + 5
            while serving.backlog() > 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert serving.backlog() == 0
        finally:
            serving.stop()

    def test_enqueue_rejects_over_max_backlog(self):
        """Producer-side cap rejects instead of silently trimming unread
        requests (ADVICE r1: no MAXLEN trim on XADD)."""
        from analytics_zoo_tpu.serving.resp import RespServer

        broker = RespServer(port=0).start()   # no consumer loop
        try:
            inq = InputQueue(port=broker.port, max_backlog=3)
            for i in range(3):
                inq.enqueue(f"q{i}", x=np.ones(2, np.float32))
            with pytest.raises(RuntimeError, match="backlog"):
                inq.enqueue("q3", x=np.ones(2, np.float32))
            c = RespClient("127.0.0.1", broker.port)
            assert int(c.execute("XLEN", "serving_stream")) == 3
        finally:
            broker.stop()

    def test_enqueue_rejects_str_fields(self):
        """Strings would become |U ndarrays and fail deep inside the
        server; the enqueue-side guard names the fix immediately (same
        contract as the raw-bytes rejection)."""
        q = InputQueue.__new__(InputQueue)      # no broker needed: the
        q.max_backlog = 0                       # guard fires before I/O
        with pytest.raises(TypeError, match="str"):
            q.enqueue("u1", x="hello")

    def test_abandoned_results_pruned_after_ttl(self):
        """Results nobody queries must not grow broker memory forever."""
        serving = _serving()
        serving.config.result_ttl_s = 0.2
        try:
            inq = InputQueue(port=serving.port)
            inq.enqueue("ghost", x=np.ones(4, np.float32))
            c = RespClient("127.0.0.1", serving.port)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if c.execute("HGETALL", "result:ghost"):
                    break
                time.sleep(0.02)
            time.sleep(0.3)   # ttl elapses
            # any later batch triggers the prune
            inq.enqueue("live", x=np.ones(4, np.float32))
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if not c.execute("HGETALL", "result:ghost"):
                    break
                time.sleep(0.02)
            assert not c.execute("HGETALL", "result:ghost")
            keys = c.execute("SMEMBERS", "__result_keys__") or []
            assert b"ghost" not in keys
        finally:
            serving.stop()

    def test_config_from_yaml(self, tmp_path):
        p = tmp_path / "config.yaml"
        p.write_text(
            "model:\n  path: /models/m\n"
            "redis:\n  src: 10.0.0.5:6380\n"
            "params:\n  batch_size: 64\n  prompt_col: tokens\n"
            "  prompt_pad_id: 3\n  continuous_batching: true\n"
            "  engine_slots: 16\n  eos_id: 2\n  engine_ticks: 4\n")
        cfg = ServingConfig.from_yaml(str(p))
        assert cfg.model_path == "/models/m"
        assert (cfg.redis_host, cfg.redis_port) == ("10.0.0.5", 6380)
        assert cfg.batch_size == 64
        assert cfg.prompt_col == "tokens" and cfg.prompt_pad_id == 3
        assert cfg.continuous_batching is True
        assert cfg.engine_slots == 16
        assert cfg.eos_id == 2 and cfg.engine_ticks == 4

    def test_config_core_number_is_not_batch_size(self, tmp_path):
        """Reference config.yaml: core_number = CPU cores; a ported config
        must not have its micro-batch silently set to the core count."""
        p = tmp_path / "config.yaml"
        p.write_text(
            "model:\n  path: /models/m\n"
            "params:\n  core_number: 4\n")
        cfg = ServingConfig.from_yaml(str(p))
        assert cfg.batch_size == 32      # default, NOT 4
        assert cfg.core_number == 4


# ---------------------------------------------------------------------------
# HTTP frontend
# ---------------------------------------------------------------------------

class TestHttpFrontend:
    @pytest.fixture()
    def stack(self):
        serving = _serving()
        fe = HttpFrontend(redis_port=serving.port, timeout=10,
                          serving=serving).start()
        yield serving, fe
        fe.stop()
        serving.stop()

    def _post(self, port, path, payload):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
        conn.request("POST", path, json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    def _get(self, port, path):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    def test_predict_json_lists(self, stack):
        _, fe = stack
        status, body = self._post(fe.port, "/predict", {
            "instances": [{"x": [1.0, 2.0, 3.0, 4.0]},
                          {"x": [5.0, 6.0, 7.0, 8.0]}]})
        assert status == 200
        np.testing.assert_allclose(body["predictions"],
                                   [[2, 4, 6, 8], [10, 12, 14, 16]])

    def test_predict_b64_tensor(self, stack):
        import base64
        _, fe = stack
        x = np.arange(4, dtype=np.float32)
        status, body = self._post(fe.port, "/predict", {
            "instances": [{"x": {
                "b64": base64.b64encode(x.tobytes()).decode(),
                "shape": [4], "dtype": "float32"}}]})
        assert status == 200
        np.testing.assert_allclose(body["predictions"][0], x * 2.0)

    def test_bad_payload_400(self, stack):
        _, fe = stack
        status, body = self._post(fe.port, "/predict",
                                  {"instances": [{"x": {"b64": "!!!"}}]})
        assert status == 400
        assert "error" in body

    def test_health_and_metrics(self, stack):
        _, fe = stack
        assert self._get(fe.port, "/healthz")[0] == 200
        self.test_predict_json_lists(stack)
        # legacy JSON dict lives behind ?format=json now
        status, m = self._get(fe.port, "/metrics?format=json")
        assert status == 200
        assert m["latency"]["count"] >= 1
        assert m["latency"]["p50_ms"] > 0
        # the job books a batch after its results are visible and its
        # entries acknowledged (server._publish_batch), so the reply can
        # overtake the counter: wait for it
        deadline = time.monotonic() + 10
        while m["serving"]["requests"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
            m = self._get(fe.port, "/metrics?format=json")[1]
        assert m["serving"]["requests"] >= 2
        assert "backlog" in m

    def test_metrics_default_is_prometheus_text(self, stack):
        _, fe = stack
        self.test_predict_json_lists(stack)
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=15)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type", "").startswith("text/plain")
        text = resp.read().decode()
        assert "# TYPE zoo_http_request_seconds summary" in text
        assert 'zoo_http_request_seconds{quantile="0.5"}' in text
        assert "zoo_http_request_seconds_count" in text
        assert "zoo_serving_requests_total" in text
        assert "zoo_http_backlog" in text

    def test_unknown_route_404(self, stack):
        _, fe = stack
        assert self._get(fe.port, "/nope")[0] == 404

    def test_backend_outage_is_502_not_400(self):
        """A dead broker is a server-side failure (ADVICE r1: backend
        outages must not be reported as client errors)."""
        broker = RespServer(port=0).start()
        fe = HttpFrontend(redis_port=broker.port, timeout=2).start()
        broker.stop()     # backend dies after the frontend comes up
        try:
            status, body = self._post(fe.port, "/predict",
                                      {"instances": [{"x": [1.0]}]})
            assert status == 502, body
            assert "error" in body
        finally:
            fe.stop()

    def test_timeout_shares_one_deadline(self):
        """n instances must time out within ~timeout, not n * timeout."""
        broker = RespServer(port=0).start()     # broker but NO serving loop
        fe = HttpFrontend(redis_port=broker.port, timeout=0.5).start()
        try:
            t0 = time.monotonic()
            status, body = self._post(fe.port, "/predict", {
                "instances": [{"x": [1.0]} for _ in range(5)]})
            dt = time.monotonic() - t0
            assert status == 504
            assert dt < 2.0, f"timeouts compounded: {dt:.1f}s"
            # failed requests still count toward latency percentiles
            assert fe.latency.snapshot()["count"] == 1
        finally:
            fe.stop()
            broker.stop()


# ---------------------------------------------------------------------------
# encoded-image payloads (ref: Cluster Serving image path — enqueue
# compressed bytes, server-side decode + resize before inference)
# ---------------------------------------------------------------------------

class _MeanPix(nn.Module):
    """[B, H, W, 3] uint8 -> per-image mean pixel (checks decode fidelity)."""

    @nn.compact
    def __call__(self, x):
        return x.astype(np.float32).mean(axis=(1, 2, 3))


def _png_bytes(arr):
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, "PNG")    # lossless: means must match
    return buf.getvalue()


class TestImageServing:
    def _image_serving(self, image_shape):
        model = _MeanPix()
        variables = model.init(
            jax.random.key(0), np.zeros((1, 8, 8, 3), np.uint8))
        im = InferenceModel().load_flax(model, variables)
        cfg = ServingConfig(batch_size=4, batch_timeout_ms=20.0,
                            image_shape=image_shape)
        return ClusterServing(im, cfg, embedded_broker=True).start()

    def test_enqueue_image_decodes_and_predicts(self):
        serving = self._image_serving(image_shape=None)
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            rng = np.random.default_rng(0)
            imgs = {f"img-{i}": rng.integers(0, 256, (8, 8, 3), np.uint8)
                    for i in range(6)}
            for uri, arr in imgs.items():
                inq.enqueue_image(uri, image=_png_bytes(arr))
            for uri, arr in imgs.items():
                r = outq.query(uri, timeout=15)
                assert r is not None, uri
                np.testing.assert_allclose(float(r), arr.mean(), rtol=1e-5)
        finally:
            serving.stop()

    def test_image_resize_to_model_shape(self):
        serving = self._image_serving(image_shape=[8, 8])
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            # 16x16 constant image resizes to 8x8 with the same mean
            arr = np.full((16, 16, 3), 77, np.uint8)
            uri = inq.enqueue_image(image=_png_bytes(arr))
            r = outq.query(uri, timeout=15)
            assert r is not None
            np.testing.assert_allclose(float(r), 77.0, atol=0.5)
        finally:
            serving.stop()

    def test_mixed_tensor_and_image_columns_rejected_gracefully(self):
        """A plain tensor enqueue still works on an image-configured
        server (the IMG! magic is per-value, not per-server)."""
        serving = self._image_serving(image_shape=None)
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            arr = np.full((8, 8, 3), 11, np.uint8)
            uri = inq.enqueue("tensor-req", x=arr)
            r = outq.query(uri, timeout=15)
            np.testing.assert_allclose(float(r), 11.0, rtol=1e-5)
        finally:
            serving.stop()

    def test_bad_payload_errors_without_batch_loss(self):
        """One corrupt image must error fast for ITS client while its
        batchmates still get results (no silent whole-batch drop)."""
        serving = self._image_serving(image_shape=None)
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            arr = np.full((8, 8, 3), 42, np.uint8)
            good = [inq.enqueue_image(f"g{i}", image=_png_bytes(arr))
                    for i in range(3)]
            bad = inq.enqueue_image("bad", image=b"not-an-image")
            for uri in good:
                r = outq.query(uri, timeout=15)
                assert r is not None
                np.testing.assert_allclose(float(r), 42.0, rtol=1e-5)
            with pytest.raises(RuntimeError, match="decode failed"):
                outq.query(bad, timeout=15)
        finally:
            serving.stop()

    def test_shape_mismatch_isolated(self):
        """Without a configured resize, a differently-sized image errors
        individually instead of killing np.stack for the batch."""
        serving = self._image_serving(image_shape=None)
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            a8 = np.full((8, 8, 3), 10, np.uint8)
            a16 = np.full((16, 16, 3), 20, np.uint8)
            u1 = inq.enqueue_image("s1", image=_png_bytes(a8))
            u2 = inq.enqueue_image("s2", image=_png_bytes(a16))
            results, errors = 0, 0
            for u in (u1, u2):
                try:
                    r = outq.query(u, timeout=15)
                    assert r is not None
                    results += 1
                except RuntimeError:
                    errors += 1
            # whichever decoded first set the batch shape; the other
            # errored — but exactly one of each, nothing lost
            assert (results, errors) == (2, 0) or (results, errors) == (1, 1)
        finally:
            serving.stop()

    def test_grayscale_png_normalised_to_rgb(self):
        serving = self._image_serving(image_shape=None)
        try:
            import io

            from PIL import Image

            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            buf = io.BytesIO()
            Image.fromarray(np.full((8, 8), 99, np.uint8), "L").save(
                buf, "PNG")
            uri = inq.enqueue_image(image=buf.getvalue())
            r = outq.query(uri, timeout=15)
            np.testing.assert_allclose(float(r), 99.0, rtol=1e-5)
        finally:
            serving.stop()

    def test_http_frontend_image_payload(self):
        """POST /predict with {"image_b64": ...} — the akka frontend's
        image-body parity path."""
        import base64

        from analytics_zoo_tpu.serving import HttpFrontend

        serving = self._image_serving(image_shape=None)
        fe = HttpFrontend(redis_port=serving.port, serving=serving).start()
        try:
            arr = np.full((8, 8, 3), 33, np.uint8)
            body = json.dumps({"instances": [
                {"x": {"image_b64":
                       base64.b64encode(_png_bytes(arr)).decode()}}]})
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=20)
            conn.request("POST", "/predict", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200, out
            np.testing.assert_allclose(out["predictions"][0], 33.0,
                                       rtol=1e-5)
        finally:
            fe.stop()
            serving.stop()

    def test_model_hot_reload_between_batches(self):
        """reload_model swaps the served model without dropping requests."""
        serving = _serving()        # _Double model
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            x = np.arange(4, dtype=np.float32)
            r1 = outq.query(inq.enqueue("before", x=x), timeout=10)
            np.testing.assert_allclose(r1, x * 2.0)

            class _Triple(nn.Module):
                @nn.compact
                def __call__(self, x):
                    return x * 3.0

            m = _Triple()
            im = InferenceModel().load_flax(
                m, m.init(jax.random.key(0), np.zeros((1, 4), np.float32)))
            serving.reload_model(im)
            r2 = outq.query(inq.enqueue("after", x=x), timeout=10)
            np.testing.assert_allclose(r2, x * 3.0)
        finally:
            serving.stop()

    def test_incompatible_reload_errors_not_blackholes(self):
        """Requests hitting a bad hot-reloaded model get fast error
        results, not query timeouts."""
        serving = _serving()
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            serving.reload_model(InferenceModel())    # never loaded
            uri = inq.enqueue("doomed", x=np.zeros(4, np.float32))
            with pytest.raises(RuntimeError, match="dispatch failed"):
                outq.query(uri, timeout=15)
        finally:
            serving.stop()


# ---------------------------------------------------------------------------
# consumer groups / multi-worker serving (ref: Flink source parallelism
# over XREADGROUP — horizontal scaling of the serving loop)
# ---------------------------------------------------------------------------

class TestConsumerGroups:
    def test_xreadgroup_claims_are_disjoint(self):
        broker = RespServer(port=0).start()
        try:
            c1 = RespClient(port=broker.port)
            c2 = RespClient(port=broker.port)
            c1.execute("XGROUP", "CREATE", "s", "g", "0-0")
            for i in range(10):
                c1.execute("XADD", "s", "*", "i", str(i))
            got1 = c1.execute("XREADGROUP", "GROUP", "g", "a", "COUNT", 6,
                              "BLOCK", 100, "STREAMS", "s", ">")
            got2 = c2.execute("XREADGROUP", "GROUP", "g", "b", "COUNT", 6,
                              "BLOCK", 100, "STREAMS", "s", ">")
            ids1 = {e[0] for e in got1[0][1]}
            ids2 = {e[0] for e in (got2[0][1] if got2 else [])}
            assert ids1.isdisjoint(ids2)
            assert len(ids1) + len(ids2) == 10
            # XACK clears pending
            acked = c1.execute("XACK", "s", "g", *sorted(ids1))
            assert acked == len(ids1)
            pend = c1.execute("XPENDING", "s", "g")
            assert pend[0] == len(ids2)
        finally:
            broker.stop()

    def test_busygroup_and_nogroup_errors(self):
        broker = RespServer(port=0).start()
        try:
            c = RespClient(port=broker.port)
            c.execute("XGROUP", "CREATE", "s", "g", "$")
            with pytest.raises(Exception, match="BUSYGROUP"):
                c.execute("XGROUP", "CREATE", "s", "g", "$")
            with pytest.raises(Exception, match="NOGROUP"):
                c.execute("XREADGROUP", "GROUP", "nope", "a", "COUNT", 1,
                          "BLOCK", 10, "STREAMS", "s", ">")
        finally:
            broker.stop()

    def test_multi_worker_serving_exactly_once(self):
        """2 worker loops on one stream: every request answered exactly
        once, none duplicated, none lost."""
        model = _Double()
        variables = model.init(jax.random.key(0),
                               np.zeros((1, 4), np.float32))
        im = InferenceModel().load_flax(model, variables)
        cfg = ServingConfig(batch_size=4, batch_timeout_ms=5.0, workers=2)
        serving = ClusterServing(im, cfg, embedded_broker=True).start()
        try:
            inq = InputQueue(port=serving.port)
            outq = OutputQueue(port=serving.port)
            xs = {f"m{i}": np.full(4, i, np.float32) for i in range(40)}
            for uri, x in xs.items():
                inq.enqueue(uri, x=x)
            for uri, x in xs.items():
                r = outq.query(uri, timeout=20)
                assert r is not None, uri
                np.testing.assert_allclose(r, x * 2.0, err_msg=uri)
            # results become client-visible BEFORE the worker's stats
            # update (publish pipeline -> ack -> stats); poll briefly so
            # a busy host doesn't read the counter inside that window
            deadline = time.time() + 5
            while serving.stats["requests"] < 40 and time.time() < deadline:
                time.sleep(0.05)
            assert serving.stats["requests"] == 40
            assert serving.backlog() == 0
        finally:
            serving.stop()


class TestFromConfig:
    def test_from_config_openvino_round_trip(self, tmp_path):
        """cluster-serving-start parity: one config.yaml naming an IR
        artifact assembles the whole serving job."""
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_openvino import _mlp_ir

        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        xml, (w1, b1, w2) = _mlp_ir(tmp_path, rng)
        cfgp = tmp_path / "config.yaml"
        cfgp.write_text(
            f"model:\n  path: {xml}\n"
            "params:\n  batch_size: 16\n")
        serving = ClusterServing.from_config(str(cfgp),
                                             embedded_broker=True).start()
        try:
            iq = InputQueue(port=serving.port)
            oq = OutputQueue(port=serving.port)
            x = rng.normal(size=(4,)).astype(np.float32)
            iq.enqueue("cfg-req", x=x)
            got = np.asarray(oq.query("cfg-req", timeout=30))
            h = np.maximum(x[None] @ w1 + b1, 0.0)
            import jax

            ref = np.asarray(jax.nn.softmax(
                jnp.asarray(h @ w2), axis=1))[0]
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        finally:
            serving.stop()

    def test_from_config_rejects_unknown_artifact(self, tmp_path):
        cfgp = tmp_path / "config.yaml"
        # existing file with unrecognised format -> cannot infer
        blob = tmp_path / "weights.bin"
        blob.write_bytes(b"\0" * 8)
        cfgp.write_text(f"model:\n  path: {blob}\n")
        with pytest.raises(ValueError, match="cannot infer"):
            ClusterServing.from_config(str(cfgp))
        # nonexistent path -> file-not-found, NOT 'cannot infer' (a
        # typo'd path of ANY extension must read as a typo)
        for typo in ("/models/typo_dir", "/models/typo.xml",
                     "/models/typo.pt"):
            cfgp.write_text(f"model:\n  path: {typo}\n")
            with pytest.raises(FileNotFoundError, match="does not exist"):
                ClusterServing.from_config(str(cfgp))
        cfgp.write_text("model:\n  path: ''\n")
        with pytest.raises(ValueError, match="model.path"):
            ClusterServing.from_config(str(cfgp))

    def test_from_config_rejects_continuous_batching(self, tmp_path):
        """continuous_batching needs a load_flax_generator model, which
        no config-routable artifact is — from_config must say so at
        assembly time, pointing at the knob (ADVICE r4)."""
        blob = tmp_path / "weights.xml"
        blob.write_bytes(b"<net/>")
        cfgp = tmp_path / "config.yaml"
        cfgp.write_text(
            f"model:\n  path: {blob}\n"
            "params:\n  continuous_batching: true\n")
        with pytest.raises(ValueError, match="load_flax_generator"):
            ClusterServing.from_config(str(cfgp))


def test_cli_http_port_serves_over_http(tmp_path):
    """cluster-serving-start --http-port: one command line assembles
    broker + serving loop + HTTP frontend from a config.yaml."""
    import http.client
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_openvino import _mlp_ir

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.serving.__main__ import main

    rng = np.random.default_rng(6)
    xml, (w1, b1, w2) = _mlp_ir(tmp_path, rng)
    cfgp = tmp_path / "config.yaml"
    cfgp.write_text(f"model:\n  path: {xml}\n"
                    "params:\n  batch_size: 8\n")
    serving, frontend, shutdown = main(
        [str(cfgp), "--embedded-broker", "--http-port", "0"],
        block=False)
    try:
        assert frontend is not None and frontend.port > 0
        x = rng.normal(size=(4,)).astype(np.float32)
        conn = http.client.HTTPConnection("127.0.0.1", frontend.port,
                                          timeout=30)
        conn.request("POST", "/predict",
                     json.dumps({"instances": [{"x": x.tolist()}]}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        got = np.asarray(json.loads(resp.read())["predictions"][0])
        h = np.maximum(x[None] @ w1 + b1, 0.0)
        ref = np.asarray(jax.nn.softmax(jnp.asarray(h @ w2), axis=1))[0]
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    finally:
        shutdown()
