"""The pump thread's lap clock (serving/telemetry.py LapClock): every
millisecond of the engine's cycle, from the end of one ``engine.step`` to
the end of the next, is booked to a named phase, wall and CPU, with no
remainder — in the tick's flight record (``phases``), as spans on the
engine-loop track, and in one cumulative counter per phase.  Driven through
``ClusterServing(embedded_broker=True)`` on a toy model, once per tick
kind."""

import re
import time
from statistics import median

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.lm import TransformerLM
from analytics_zoo_tpu.serving.continuous import ContinuousEngine
from analytics_zoo_tpu.serving.telemetry import (
    PHASES, LapClock, Telemetry, render_prometheus, validate_chrome_trace)

PUMP = ["observe", "control", "flush", "house", "cancel", "claim", "submit"]
STEP = ["admit", "plan", "dispatch", "device_wait", "book", "publish"]
# one letter per in-step phase: plan -> dispatch -> device_wait -> book
# (callbacks taken out of it) between two admissions; a monolithic
# admission runs its own prefill (dispatch, device_wait) inside admit.
# Under a pump every device call is followed by the pump's token flush
# (engine.after_dispatch): dispatch -> flush -> device_wait
LETTER = dict(admit="a", plan="p", dispatch="d", device_wait="w", book="b",
              publish="u", flush="f")
_IN_STEP = r"^(a|{dw}|u)*a(p({dw}(b|u)+)?)*(a|{dw}|u)*a$"
IN_STEP = re.compile(_IN_STEP.format(dw="dw"))
IN_STEP_PUMPED = re.compile(_IN_STEP.format(dw="dfw"))
# a lap's two clocks are read one after the other
SLACK_MS = 0.1 + time.get_clock_info("thread_time").resolution * 1e3


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=32, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64,
                          max_position=64, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return model, variables


# ---------------------------------------------------------------------------
# the clock alone
# ---------------------------------------------------------------------------

class TestLapClock:
    def test_laps_are_contiguous_and_take_closes_the_cycle(self):
        clock = LapClock()
        t_first = clock.lap("house")
        time.sleep(0.01)
        clock.lap("claim")
        t_last = clock.lap("admit")
        laps, folded = clock.take()
        assert [n for n, _, _ in laps] == ["house", "claim", "admit"]
        assert folded == 0
        assert sum(w for _, w, _ in laps[1:]) == pytest.approx(
            t_last - t_first, abs=1e-9)
        assert laps[1][1] >= 0.01 and laps[1][2] < 0.005    # slept: no CPU
        assert clock.take() == ([], 0)

    def test_fold_sums_per_phase_and_bounds_the_list(self):
        clock = LapClock()
        for _ in range(1000):               # a pump idling for minutes
            for name in ("flush", "house", "cancel", "idle_wait", "submit"):
                clock.lap(name)
            clock.fold()
        clock.lap("house")
        clock.lap("admit")
        laps, folded = clock.take()
        assert [n for n, _, _ in laps] == [
            "flush", "house", "cancel", "idle_wait", "submit", "house",
            "admit"]
        assert folded == 5

    def test_drive_restarts_the_cpu_clock_and_names_the_rest(self):
        clock = LapClock()
        assert clock.rest == "outside"
        clock.drive("submit")
        assert clock.rest == "submit"
        laps, _ = clock.take()
        assert [(n, c) for n, _, c in laps] == [("outside", 0.0)]

    def test_tick_feeds_counters_and_spans(self):
        tm = Telemetry()
        phases = [("idle_wait", 5.0, 0.0), ("house", 0.001, 0.001),
                  ("admit", 0.002, 0.002), ("device_wait", 0.05, 0.0005)]
        tm.tick(100.0, 0.052, {}, phases, folded=1)
        assert tm.c_phase_wall["idle_wait"].value == 5.0
        assert tm.c_phase_wall["device_wait"].value == 0.05
        assert tm.c_phase_cpu["device_wait"].value == 0.0005
        spans = {e[1]: e for e in tm.events.snapshot() if e[0] == "X"}
        assert "idle_wait" not in spans         # a folded sum has no place
        assert spans["device_wait"][2] == pytest.approx(100.002)
        assert spans["house"][2] == pytest.approx(100.0 - 0.001)
        assert spans["device_wait"][5] == {"cpu_ms": 0.5}


# ---------------------------------------------------------------------------
# through the pump, once per tick kind
# ---------------------------------------------------------------------------

KINDS = {
    # default engine: slot arena, whole-prompt prefill inside admission
    "monolithic": (dict(), dict()),
    "paged": (dict(engine_paged=True, engine_block_size=4), dict()),
    "chunked": (dict(engine_paged=True, engine_block_size=4,
                     engine_chunked=True, engine_tick_token_budget=8),
                dict()),
    # budget 4 = four decode rows: a fifth request's prefill stalls and
    # the tick runs with no chunk (_decode_only_tick); answers long
    # enough for four rows to decode at once
    "decode-only": (dict(engine_slots=5, engine_paged=True,
                         engine_block_size=4, engine_chunked=True,
                         engine_tick_token_budget=4), dict(max_new=24)),
    "speculative": (dict(engine_paged=True, engine_block_size=4,
                         engine_chunked=True, engine_speculation_k=2),
                    dict(draft=True)),
}


def _serve(lm, cfg_kw, draft=False, max_new=6):
    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import ClusterServing, ServingConfig

    model, variables = lm
    im = InferenceModel(batch_buckets=(1, 2))
    kw = dict(draft_model=model, draft_variables=variables) if draft else {}
    im.load_flax_generator(model, variables, max_new_tokens=max_new,
                           prompt_buckets=(4, 16), **kw)
    cfg = ServingConfig(**dict(dict(prompt_col="tokens", batch_size=8,
                                    continuous_batching=True,
                                    engine_slots=3), **cfg_kw))
    return ClusterServing(im, cfg, embedded_broker=True).start()


def _ask(serving, names, rng, lens):
    from analytics_zoo_tpu.serving import InputQueue, OutputQueue

    inq, outq = InputQueue(port=serving.port), OutputQueue(port=serving.port)
    try:
        for name, n in zip(names, lens):
            inq.enqueue(name, tokens=rng.integers(1, 32, n).astype(np.int32))
        return {name: np.asarray(outq.query(name, timeout=600))
                for name in names}
    finally:
        inq.close()
        outq.close()


@pytest.fixture(scope="module", params=list(KINDS))
def served(request, lm):
    """One stack per tick kind: two waves of requests with an idle
    stretch between them, then the engine's records."""
    cfg_kw, extra = KINDS[request.param]
    serving = _serve(lm, cfg_kw, **extra)
    try:
        rng = np.random.default_rng(26)
        _ask(serving, [f"a{i}" for i in range(3)], rng, (5, 9, 14))
        time.sleep(0.5)                     # idle passes, folded
        _ask(serving, [f"b{i}" for i in range(6)], rng,
             (6, 3, 11, 7, 13, 4))
        time.sleep(0.3)
        eng = serving.engine
        yield {"kind": request.param, "ticks": eng.flight.snapshot(),
               "telemetry": eng.telemetry}
    finally:
        serving.stop()


def _busy(ticks):
    """Cycles the pump went straight through: no idle pass in them."""
    return [t for t in ticks[1:]
            if not {"idle_wait", "outside"} & {p[0] for p in t["phases"]}]


def test_every_cycle_is_accounted_for_with_no_remainder(served):
    ticks = served["ticks"]
    assert len(ticks) >= 8 and all("phases" in t for t in ticks)
    for a, b in zip(ticks, ticks[1:]):
        cycle_ms = (b["ts"] - a["ts"]) * 1e3 + b["dur_ms"] - a["dur_ms"]
        assert sum(p[1] for p in b["phases"]) == pytest.approx(
            cycle_ms, abs=0.02), b
        # ts and dur_ms keep their meaning: the in-step phases are the step
        first_admit = [p[0] for p in b["phases"]].index("admit")
        assert sum(p[1] for p in b["phases"][first_admit:]) == \
            pytest.approx(b["dur_ms"], abs=0.02)
        # a pump drives the engine: what its token flush sent this cycle
        assert 0 <= b["flush_events_overlapped"] <= b["flush_events"]


def test_phase_names_are_the_documented_set_in_cycle_order(served):
    ticks = served["ticks"]
    seen = {p[0] for t in ticks for p in t["phases"]}
    assert seen <= set(PHASES)
    assert set(PUMP + STEP) | {"idle_wait"} <= seen
    busy = _busy(ticks)
    assert len(busy) >= 4
    for t in busy:
        names = [p[0] for p in t["phases"]]
        k = names.index("admit")
        assert names[:k] == PUMP, names
        # every device call is followed by the flush, then the wait
        assert IN_STEP_PUMPED.match(
            "".join(LETTER[n] for n in names[k:])), names
    if served["kind"] in ("chunked", "decode-only"):
        # the benchmark's engine: one device call a step, so the first
        # occurrences are the catalog's order exactly, and the in-step
        # flush is one lap between dispatch and device_wait
        for t in busy:
            names = [p[0] for p in t["phases"]]
            first = sorted(set(names), key=names.index)
            assert first == [n for n in PUMP + STEP if n in first], names
            if "dispatch" in names:
                d = names.index("dispatch")
                assert names[d:d + 3] == ["dispatch", "flush",
                                          "device_wait"], names
                assert names.count("flush") == 2
        assert "publish" in seen and "plan" in seen
    if served["kind"] == "decode-only":
        assert any(t["kind"] == "chunked" and t["chunks"] == 0
                   and t["prefill_rows"] for t in ticks), \
            "no tick ran with its prefill stalled"
    if served["kind"] == "speculative":
        assert {"spec", "spec_chunked"} & {t["kind"] for t in ticks}


def test_cpu_never_exceeds_wall(served):
    for t in served["ticks"]:
        for name, wall, cpu in t["phases"]:
            assert 0.0 <= cpu + SLACK_MS and cpu <= wall + SLACK_MS, \
                (name, wall, cpu)
    # the wait for the device is wall without CPU, the planning is CPU
    busy = _busy(served["ticks"])
    wait = [sum(p[1] - p[2] for p in t["phases"] if p[0] == "claim")
            for t in busy]
    assert median(wait) > 0.3       # XREADGROUP BLOCK 1 / a 1 ms wait


def test_an_idle_stretch_is_folded_into_one_bounded_cycle(served):
    idle = [t for t in served["ticks"][1:]
            if "idle_wait" in {p[0] for p in t["phases"]}]
    assert idle, "the half second between the waves left no idle cycle"
    longest = max(idle, key=lambda t: sum(p[1] for p in t["phases"]))
    waited = sum(p[1] for p in longest["phases"] if p[0] == "idle_wait")
    assert waited >= 300.0
    assert len(longest["phases"]) <= 2 * len(PHASES) + 8


def test_counters_and_spans_carry_the_same_cycle(served):
    tm, ticks = served["telemetry"], served["ticks"]
    text = render_prometheus(tm.metrics)
    for ph in PHASES:
        assert f"zoo_engine_phase_seconds_total_{ph} " in text
        assert f"zoo_engine_phase_cpu_seconds_total_{ph} " in text
        assert f"# TYPE zoo_engine_phase_seconds_total_{ph} counter" in text
    for ph in ("device_wait", "claim", "admit"):
        booked = sum(p[1] for t in ticks for p in t["phases"]
                     if p[0] == ph) / 1e3
        assert tm.c_phase_wall[ph].value == pytest.approx(booked, rel=0.01)
    trace = tm.dump_trace()
    validate_chrome_trace(trace)
    spans = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e["tid"] == tm.events.TID_ENGINE]
    assert {"tick", "device_wait", "claim", "flush"} <= \
        {e["name"] for e in spans}
    # the in-step phases nest under their tick's span
    tick = [e for e in spans if e["name"] == "tick"][-1]
    inside = [e for e in spans if e["name"] in STEP + ["flush"]
              and tick["ts"] - 1 <= e["ts"]
              and e["ts"] + e["dur"] <= tick["ts"] + tick["dur"] + 1]
    assert sum(e["dur"] for e in inside) == pytest.approx(tick["dur"],
                                                          abs=5.0)


# ---------------------------------------------------------------------------
# off-CPU against on-CPU, told apart
# ---------------------------------------------------------------------------

def _phase_ms(ticks, phase, col):
    return median(sum(p[col] for p in t["phases"] if p[0] == phase)
                  for t in ticks)


def test_a_sleep_is_wall_and_a_busy_loop_is_cpu(lm, monkeypatch):
    from analytics_zoo_tpu.serving import ClusterServing
    from analytics_zoo_tpu.serving import policy as scheduler_policy

    serving = _serve(lm, KINDS["chunked"][0], max_new=8)
    try:
        rng = np.random.default_rng(7)
        eng = serving.engine
        _ask(serving, ["w0", "w1", "w2"], rng, (14, 12, 15))
        time.sleep(0.3)
        before = _busy(eng.flight.snapshot())
        n_before = len(eng.flight.snapshot())

        flush = ClusterServing._flush_emitter
        plan = scheduler_policy.plan_chunks

        def slow_flush(self, *a, **kw):
            time.sleep(0.02)                # a broker that answers late
            return flush(self, *a, **kw)

        def hot_plan(*a, **kw):
            c0 = time.thread_time()         # a planner with too much to do
            while time.thread_time() - c0 < 0.02:
                pass
            return plan(*a, **kw)

        monkeypatch.setattr(ClusterServing, "_flush_emitter", slow_flush)
        monkeypatch.setattr(scheduler_policy, "plan_chunks", hot_plan)
        _ask(serving, ["x0", "x1", "x2"], rng, (14, 12, 15))
        time.sleep(0.3)
        after = _busy(eng.flight.snapshot()[n_before:])
    finally:
        serving.stop()
    assert len(before) >= 4 and len(after) >= 4
    assert _phase_ms(after, "flush", 1) - _phase_ms(before, "flush", 1) >= 20
    assert _phase_ms(after, "flush", 2) - _phase_ms(before, "flush", 2) < 2
    chunk = [t for t in after if t["kind"] == "chunked" and t["chunks"]]
    chunk0 = [t for t in before if t["kind"] == "chunked" and t["chunks"]]
    assert chunk and chunk0
    assert _phase_ms(chunk, "plan", 1) - _phase_ms(chunk0, "plan", 1) >= 19
    assert _phase_ms(chunk, "plan", 2) - _phase_ms(chunk0, "plan", 2) >= 19


# ---------------------------------------------------------------------------
# an engine nobody pumps; the ring switched off
# ---------------------------------------------------------------------------

def _drive(lm, flight_capacity, **kw):
    model, variables = lm
    eng = ContinuousEngine(model, variables, max_new_tokens=5, max_slots=3,
                           prompt_buckets=(8, 16),
                           flight_capacity=flight_capacity, **kw)
    rng = np.random.default_rng(3)
    done = {}
    for i, n in enumerate((4, 12, 7, 9)):
        eng.submit(f"r{i}", rng.integers(1, 32, n).astype(np.int32),
                   on_done=lambda u, t: done.__setitem__(u, np.array(t)))
    eng.drain()
    for _ in range(200):
        assert eng.step() == 0              # idle polls fold, never grow
    assert len(eng.telemetry.clock.take()[0]) <= 1
    return eng, done


ENGINES = {"arena": {},
           "paged-chunked": dict(paged=True, block_size=4, chunked=True,
                                 tick_token_budget=8)}


@pytest.mark.parametrize("mode", list(ENGINES))
def test_without_the_pump_the_rest_of_the_cycle_is_outside(lm, mode):
    eng, done = _drive(lm, 64, **ENGINES[mode])
    assert len(done) == 4
    ticks = eng.flight.snapshot()
    assert len(ticks) >= 3
    for a, b in zip(ticks, ticks[1:]):
        names = [p[0] for p in b["phases"]]
        assert names[0] == "outside" and set(names[1:]) <= set(STEP), names
        assert IN_STEP.match("".join(LETTER[n] for n in names[1:])), names
        cycle_ms = (b["ts"] - a["ts"]) * 1e3 + b["dur_ms"] - a["dur_ms"]
        assert sum(p[1] for p in b["phases"]) == pytest.approx(
            cycle_ms, abs=0.02)
    assert any("publish" in [p[0] for p in t["phases"]] for t in ticks)
    # no pump, no token flush: neither the lap nor the counters
    assert not any("flush_events" in t for t in ticks)


@pytest.mark.parametrize("mode", list(ENGINES))
def test_greedy_tokens_are_bitwise_equal_without_the_ring(lm, mode):
    on, done_on = _drive(lm, 64, **ENGINES[mode])
    off, done_off = _drive(lm, 0, **ENGINES[mode])
    assert on.flight is not None and off.flight is None
    assert set(done_on) == set(done_off) == {f"r{i}" for i in range(4)}
    for u in done_on:
        np.testing.assert_array_equal(done_on[u], done_off[u])
    # no ring, but the counters still run
    assert off.telemetry.c_phase_wall["device_wait"].value > 0


# ---------------------------------------------------------------------------
# the token flush under the device: where it runs, what it counts
# ---------------------------------------------------------------------------

def test_streamed_tokens_are_flushed_under_the_next_device_call(lm):
    """Streaming requests through the pump: every token event and every
    ``done`` marker is counted once in ``flush_events``; those that left
    from ``engine.after_dispatch`` (a device call in flight) are the
    overlapped ones, the rest left at the end of a pass (the engine went
    idle) — and the in-step flush is booked between ``dispatch`` and
    ``device_wait`` with the step's identity intact."""
    from analytics_zoo_tpu.serving import InputQueue, OutputQueue

    serving = _serve(lm, KINDS["chunked"][0], max_new=12)
    try:
        eng = serving.engine
        inq = InputQueue(port=serving.port)
        outq = OutputQueue(port=serving.port)
        rng = np.random.default_rng(31)
        names = [f"s{i}" for i in range(3)]
        for name, n in zip(names, (5, 9, 14)):
            inq.enqueue(name, tokens=rng.integers(1, 32, n).astype(np.int32),
                        stream=np.int32(1))
        for name in names:
            evs = [e for e in outq.stream_events(name, timeout=600)
                   if "ping" not in e]
            assert evs[-1] == {"done": True}
            assert [e["index"] for e in evs[:-1]] == list(range(12))
        time.sleep(0.3)
        ticks = eng.flight.snapshot()
        tm = eng.telemetry
    finally:
        serving.stop()
    sent = 3 * 12 + 3               # tokens and done markers
    assert tm.c_flush_events.value == sent
    assert sum(t["flush_events"] for t in ticks) <= sent    # + the open cycle
    over = tm.c_flush_overlapped.value
    assert sum(t["flush_events_overlapped"] for t in ticks) == over
    # all but the last tick's events found a device call to leave under
    assert sent - 12 <= over < sent
    text = render_prometheus(tm.metrics)
    assert f"zoo_engine_flush_events_total {sent}" in text
    assert f"zoo_engine_flush_events_overlapped_total {over}" in text
    for t in ticks:
        names_ = [p[0] for p in t["phases"]]
        if t["flush_events_overlapped"]:
            d = names_.index("dispatch")
            assert names_[d + 1] == "flush", names_
        k = names_.index("admit")
        assert sum(p[1] for p in t["phases"][k:]) == pytest.approx(
            t["dur_ms"], abs=0.02)
