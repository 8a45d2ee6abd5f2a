"""The fan-out threads on the engine's clock (serving/telemetry.py
FanoutClock): the embedded broker's connection threads and the front
door's stream writers each keep an account of CPU, wake-ups, events
written and the fan-out's delay on a token, and every engine reads the
process's totals into its flight records through a cursor of its own."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.learn.inference_model import InferenceModel
from analytics_zoo_tpu.models.lm import TransformerLM
from analytics_zoo_tpu.serving import (ClusterServing, HttpFrontend,
                                       InputQueue, OutputQueue,
                                       ServingConfig)
from analytics_zoo_tpu.serving.continuous import ContinuousEngine
from analytics_zoo_tpu.serving.frontdoor import TokenEmitter
from analytics_zoo_tpu.serving.resp import RespClient, RespServer
from analytics_zoo_tpu.serving.telemetry import (FANOUT_KINDS, FanoutClock,
                                                 render_prometheus)

FIELDS = ("fanout_broker_cpu_ms", "fanout_door_cpu_ms", "fanout_wakes",
          "fanout_events", "fanout_lag_ms_sum", "fanout_lag_n",
          "fanout_lag_ms_max")


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=32, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64,
                          max_position=64, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


def _on_thread(fn):
    t = threading.Thread(target=fn)
    t.start()
    t.join()


def _spin(seconds):
    t_end = time.thread_time() + seconds
    while time.thread_time() < t_end:
        pass


def _sse(port, body, headers=None):
    """POST /v1/generate and read the whole SSE answer: [(event, data)]."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/generate", body=json.dumps(body),
                 headers=dict({"Content-Type": "application/json"},
                              **(headers or {})))
    resp = conn.getresponse()
    assert resp.status == 200
    out, ev = [], None
    for raw in resp.read().decode().splitlines():
        if raw.startswith("event: "):
            ev = raw[7:]
        elif raw.startswith("data: "):
            out.append((ev, json.loads(raw[6:])))
    conn.close()
    return out


# ---------------------------------------------------------------------------
# the clock alone
# ---------------------------------------------------------------------------

def test_a_wake_is_booked_into_exactly_one_flight_record(lm):
    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=3, max_slots=2,
                           prompt_buckets=(8,))
    clock = FanoutClock()
    eng.fanout = clock.cursor()
    other = clock.cursor()          # a second engine's cursor
    acct = clock.open("broker")

    def five_wakes():
        a = clock.open("door")
        for _ in range(5):
            a.wake()
        clock.close(a)

    _on_thread(five_wakes)
    acct.wake()                     # a live thread's wake
    eng.submit("r0", np.arange(1, 6, dtype=np.int32))
    eng.drain()
    acct.wake()
    acct.wake()
    eng.submit("r1", np.arange(1, 6, dtype=np.int32))
    eng.drain()
    recs = eng.flight.snapshot()
    assert len(recs) >= 4
    wakes = [r["fanout_wakes"] for r in recs]
    assert sum(wakes) == 8
    # the six before r0 land in r0's first record, the two before r1 in
    # r1's first: every other record books none
    first_r1 = next(i for i, r in enumerate(recs)
                    if "r1" in r["decode_uris"] + r["prefill_uris"])
    assert wakes[0] == 6 and wakes[first_r1] == 2
    assert sum(wakes) - wakes[0] - wakes[first_r1] == 0
    assert all(set(FIELDS) <= set(r) for r in recs)
    # the other cursor reads the same wakes once, whatever this one took
    assert other.take()["fanout_wakes"] == 8
    assert other.take()["fanout_wakes"] == 0
    clock.close(acct)


def test_a_thread_that_exits_keeps_its_totals():
    clock = FanoutClock()
    cur, cur2 = clock.cursor(), clock.cursor()

    def door_thread():
        a = clock.open("door")
        _spin(0.02)
        a.wake()
        a.wrote(int(time.monotonic() * 1e6) - 3000)     # 3 ms ago
        a.wrote()                                       # no stamp
        _spin(0.02)                 # after the last wake: close books it
        clock.close(a)

    _on_thread(door_thread)
    assert clock._free == {"broker": [], "door": [0]}   # row left
    tot, _ = clock.totals()
    assert tot["door"][1:3] == [1, 2] and tot["door"][4] == 1
    assert tot["door"][0] >= 0.03
    assert tot["broker"] == [0.0, 0, 0, 0.0, 0]
    d = cur.take()
    assert d["fanout_wakes"] == 1 and d["fanout_events"] == 2
    assert d["fanout_lag_n"] == 1
    assert 3.0 <= d["fanout_lag_ms_sum"] == d["fanout_lag_ms_max"] < 1000
    assert d["fanout_door_cpu_ms"] >= 30 and d["fanout_broker_cpu_ms"] == 0
    # folded into the kind's total, booked once; the other cursor reads
    # the same, its maximum too
    assert cur.take() == dict.fromkeys(FIELDS, 0)
    assert cur2.take() == d
    with pytest.raises(ValueError, match="kind"):
        clock.open("pump")
    assert FANOUT_KINDS == ("broker", "door")


def test_the_cpu_clock_is_read_at_most_once_a_gate(monkeypatch):
    """A wake inside ``FANOUT_CPU_READ_S`` of the thread's previous
    reading counts itself and reads no CPU clock; the first wake past it
    books all the CPU since that reading."""
    from analytics_zoo_tpu.serving import telemetry
    clock = FanoutClock()
    cur = clock.cursor()
    monkeypatch.setattr(telemetry, "FANOUT_CPU_READ_S", 0.2)
    a = clock.open("broker")
    _spin(0.03)
    a.wake()
    a.wake()
    cpu, wakes = clock._cols["broker"][0], clock._cols["broker"][1]
    assert cpu[a.row] == 0.0 and wakes[a.row] == 2
    time.sleep(0.2)                 # wall past the gate, no CPU
    a.wake()
    assert 0.03 <= cpu[a.row] < 0.2
    d = cur.take()
    assert d["fanout_wakes"] == 3 and d["fanout_broker_cpu_ms"] >= 30
    clock.close(a)


def test_lag_field_travels_from_the_flush_to_the_sse_write(monkeypatch):
    """``_flush_emitter`` stamps each token entry; the broker keeps the
    field; ``TokenTail`` passes it on; the SSE writer books write
    time minus stamp.  An entry without the field books no lag."""
    from analytics_zoo_tpu.serving import telemetry
    # the front door's writer (its stream hub) outlives the stream: its
    # CPU is booked at a wake past the gate, not when a stream ends
    monkeypatch.setattr(telemetry, "FANOUT_CPU_READ_S", 0.0)
    clock = FanoutClock()
    broker = RespServer(port=0, fanout=clock).start()
    fe = HttpFrontend(redis_port=broker.port, timeout=30).start()
    client = RespClient(port=broker.port)
    try:
        emitter = TokenEmitter()
        emitter.emit("u1", 11, 0)
        emitter.emit("u1", 12, 1)
        t0 = time.monotonic()
        assert ClusterServing._flush_emitter(None, client, emitter,
                                             wait=True) == 2
        # an external producer's entry (no stamp), then the terminal
        client.execute("XADD", "tok:u1", "*", "i", 2, "t", 13)
        client.execute("XADD", "tok:u1", "*", "done", "1")
        raw = client.execute("XRANGE", "tok:u1", "-", "+")
        fields = [{f[i].decode(): f[i + 1] for i in range(0, len(f), 2)}
                  for _, f in raw]
        stamps = [int(f["s"]) for f in fields[:2]]
        assert stamps[0] == stamps[1]           # one reading a flush
        assert t0 - 1e-6 <= stamps[0] * 1e-6 <= time.monotonic()
        assert "s" not in fields[2]
        cur = fe.fanout.cursor()
        evs = _sse(fe.port, {"prompt": [1, 2, 3], "stream": True},
                   {"X-Request-Id": "u1"})
        t_read = time.monotonic()
        assert [e for e, _ in evs] == ["start", "token", "token", "token",
                                      "done"]
        assert [d["token"] for e, d in evs if e == "token"] == [11, 12, 13]
        assert "stamp" not in evs[1][1]         # the wire carries none
        d = cur.take()
        assert d["fanout_events"] == 4 and d["fanout_lag_n"] == 2
        assert d["fanout_wakes"] >= 1
        top = (t_read - stamps[0] * 1e-6) * 1e3
        assert 0 < d["fanout_lag_ms_max"] <= top
        assert d["fanout_lag_ms_max"] <= d["fanout_lag_ms_sum"] <= 2 * top
        assert d["fanout_door_cpu_ms"] > 0
        # the detached front door books on a clock of its own
        assert fe.fanout is not clock
        assert clock.totals()[0]["broker"][1] > 0
    finally:
        client.close()
        fe.stop()
        broker.stop()


# ---------------------------------------------------------------------------
# the real stack
# ---------------------------------------------------------------------------

def test_a_streamed_request_books_cpu_wakes_events_and_lag(lm):
    model, variables = lm
    max_new = 12
    im = InferenceModel().load_flax_generator(
        model, variables, max_new_tokens=max_new, prompt_buckets=(8,))
    cfg = ServingConfig(prompt_col="prompt", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=4, engine_chunked=True,
                        engine_tick_token_budget=16)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq, outq = InputQueue(port=serving.port), OutputQueue(port=serving.port)
    try:
        assert fe.fanout is serving.telemetry.fanout
        assert serving.broker.fanout is serving.telemetry.fanout
        evs = _sse(fe.port, {"prompt": [1, 2, 3, 4, 5], "stream": True})
        tokens = [d for e, d in evs if e == "token"]
        assert len(tokens) == max_new and evs[-1][0] == "done"
        # a second request ticks the engine again, so its records take
        # what the stream's threads did after the first request's last
        # tick (it is not streamed: no door thread, no lag)
        inq.enqueue("after", prompt=np.arange(1, 6, dtype=np.int32))
        assert outq.query("after", timeout=600) is not None
        time.sleep(0.2)
        recs = serving.engine.flight.snapshot()
        text = fe.prometheus()
        trace = serving.engine.telemetry.dump_trace()
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    total = {k: sum(r[k] for r in recs) for k in FIELDS[:-1]}
    assert total["fanout_broker_cpu_ms"] > 0
    assert total["fanout_door_cpu_ms"] > 0
    assert total["fanout_wakes"] >= max_new
    assert total["fanout_events"] == max_new + 1        # tokens and done
    assert total["fanout_lag_n"] == max_new             # one a token
    assert total["fanout_lag_ms_sum"] > 0
    assert max(r["fanout_lag_ms_max"] for r in recs) > 0
    for name in ("fanout_cpu_seconds_total_broker",
                 "fanout_cpu_seconds_total_door", "fanout_wakes_total",
                 "fanout_events_total"):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(f"zoo_engine_{name} "))
        assert float(line.split()[1]) > 0, line
    assert "# TYPE zoo_engine_fanout_wakes_total counter" in text
    samples = [e for e in trace["traceEvents"]
               if e.get("ph") == "C" and e.get("name") == "fanout"]
    assert len(samples) == len(recs)
    assert set(samples[0]["args"]) == set(FIELDS)


def test_an_engine_with_no_front_door_books_zeros(lm):
    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=3, max_slots=2,
                           prompt_buckets=(8,))
    eng.submit("r0", np.arange(1, 6, dtype=np.int32))
    eng.drain()
    recs = eng.flight.snapshot()
    assert recs and all(r[k] == 0 for r in recs for k in FIELDS)
    text = render_prometheus(eng.telemetry.metrics)
    assert "zoo_engine_fanout_events_total 0" in text


def test_every_replica_reads_the_process_clock(lm):
    """Two replicas in one process: each engine holds a cursor of its own
    into the serving job's clock, which the embedded broker books on."""
    model, variables = lm
    im = InferenceModel().load_flax_generator(
        model, variables, max_new_tokens=4, prompt_buckets=(8,))
    cfg = ServingConfig(prompt_col="prompt", continuous_batching=True,
                        engine_slots=2, n_replicas=2)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    try:
        clock = serving.telemetry.fanout
        assert serving.engines[1].telemetry is not serving.telemetry
        assert serving.engines[1].telemetry.fanout is clock
        assert all(e.fanout.clock is clock for e in serving.engines)
        assert len({id(e.fanout._peaks) for e in serving.engines}) == 2
        assert len(clock._peaks) == 2
        assert serving.broker.fanout is clock
    finally:
        serving.stop()
    # a stopped job's engines keep no lag maxima on the clock
    assert clock._peaks == ()


def test_a_read_resets_the_lag_maxima_in_place():
    """A door thread may hold a cursor's list of maxima while the cursor
    reads it: the read zeroes that list, so the next lag lands in the
    next read; a closed cursor's list is dropped."""
    clock = FanoutClock()
    cur, other = clock.cursor(), clock.cursor()
    a = clock.open("door")
    held = clock._peaks             # what a writer walks
    a.wrote(int(time.monotonic() * 1e6) - 5000)
    assert cur.take()["fanout_lag_ms_max"] >= 5.0
    assert cur._peaks is held[0] and held[0] == [0.0]
    a.wrote(int(time.monotonic() * 1e6) - 2000)     # into the held list
    assert 2.0 <= cur.take()["fanout_lag_ms_max"] < 5.0
    assert other.take()["fanout_lag_ms_max"] >= 5.0
    cur.close()
    assert clock._peaks == (other._peaks,)
    a.wrote(int(time.monotonic() * 1e6) - 1000)
    assert cur._peaks == [0.0] and other._peaks[0] > 0
    clock.close(a)
