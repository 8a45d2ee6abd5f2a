"""Flight recorder + SLO watchdog subsystem (serving/flight.py):
ring semantics, SLO judgement and goodput accounting, correlated
structured logging, anomaly triggers, bundle round-trips through the
stdlib debug CLI, the engine's per-tick records (with greedy parity
recorder-on vs off), the live HTTP surfaces (/debug/flight, /healthz
SLO fields, X-Request-Id correlation), and the doc-drift guard tying
docs/observability.md to the real scrape."""

import json
import http.client
import logging
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.lm import TransformerLM, generate
from analytics_zoo_tpu.serving.flight import (
    FLIGHT_SCHEMA_VERSION, AnomalyMonitor, FlightRecorder,
    JsonLogFormatter, RingLogHandler, SloPolicy, SloWatchdog,
    dump_bundle, install_flight_logging, prune_bundles,
    request_uri_context)
from analytics_zoo_tpu.serving.frontdoor import normalize_request_id
from analytics_zoo_tpu.serving.telemetry import (
    MetricsRegistry, render_prometheus)


# ---------------------------------------------------------------------------
# FlightRecorder ring
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=4)
        for _ in range(10):
            fr.record({"seq": fr.next_seq()})
        assert len(fr) == 4
        seqs = [t["seq"] for t in fr.snapshot()]
        assert seqs == [7, 8, 9, 10]        # oldest first, newest kept

    def test_snapshot_last_trims_tail(self):
        fr = FlightRecorder(capacity=8)
        for _ in range(5):
            fr.record({"seq": fr.next_seq()})
        assert [t["seq"] for t in fr.snapshot(last=2)] == [4, 5]
        assert fr.snapshot(last=99) == fr.snapshot()

    def test_seq_survives_wraparound(self):
        fr = FlightRecorder(capacity=2)
        for _ in range(100):
            fr.record({"seq": fr.next_seq()})
        assert fr.snapshot()[0]["seq"] == 99    # history loss visible

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError, match="capacity"):
            FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# SLO policy + watchdog
# ---------------------------------------------------------------------------

class TestSloWatchdog:
    def test_good_request_scores_goodput_one(self):
        wd = SloWatchdog(SloPolicy())
        wd.observe_queue_wait("interactive", 0.01, "r0")
        wd.observe_ttft("interactive", 0.05, "r0")
        wd.observe_finish("interactive", "r0", 0.01)
        st = wd.status()["per_class"]["interactive"]
        assert st == {"finished": 1, "good": 1, "goodput": 1.0,
                      "breaches": {"ttft": 0, "tpot": 0,
                                   "queue_wait": 0}}

    def test_one_breach_marks_the_request_bad(self):
        pol = SloPolicy(targets={"interactive": {
            "ttft": 0.1, "tpot": 0.1, "queue_wait": 0.1}})
        wd = SloWatchdog(pol)
        wd.observe_queue_wait("interactive", 5.0, "r0")     # breach
        wd.observe_ttft("interactive", 0.05, "r0")
        wd.observe_finish("interactive", "r0", 0.05)
        st = wd.status()["per_class"]["interactive"]
        assert st["finished"] == 1 and st["good"] == 0
        assert st["goodput"] == 0.0
        assert st["breaches"]["queue_wait"] == 1
        assert st["breaches"]["ttft"] == 0
        recent = wd.status()["recent_breaches"]
        assert recent and recent[-1]["metric"] == "queue_wait"
        assert recent[-1]["uri"] == "r0"

    def test_zero_target_disables_dimension(self):
        pol = SloPolicy(targets={"batch": {"ttft": 0.0}})
        wd = SloWatchdog(pol)
        wd.observe_ttft("batch", 9999.0, "r0")
        wd.observe_finish("batch", "r0", None)
        st = wd.status()["per_class"]["batch"]
        assert st["good"] == 1 and st["breaches"]["ttft"] == 0

    def test_unknown_priority_maps_to_standard(self):
        wd = SloWatchdog(SloPolicy())
        wd.observe_finish(None, "r0", None)
        wd.observe_finish("bogus", "r1", None)
        assert wd.status()["per_class"]["standard"]["finished"] == 2

    def test_dropped_request_counts_nowhere(self):
        pol = SloPolicy(targets={"standard": {"ttft": 0.01}})
        wd = SloWatchdog(pol)
        wd.observe_ttft("standard", 1.0, "r0")      # breach, in flight
        wd.drop("r0")                               # errored/cancelled
        wd.observe_finish("standard", "r1", None)   # unrelated finish
        st = wd.status()["per_class"]["standard"]
        # the breach COUNTER stands (it happened) but the dropped
        # request neither finished nor dragged r1's goodput down
        assert st["finished"] == 1 and st["good"] == 1
        assert st["breaches"]["ttft"] == 1

    def test_breach_burst_window(self):
        pol = SloPolicy(targets={"standard": {"queue_wait": 0.01}})
        wd = SloWatchdog(pol)
        for i in range(5):
            wd.observe_queue_wait("standard", 1.0, f"r{i}")
        assert wd.breach_burst(window_s=60.0) == 5
        assert wd.breach_burst(window_s=0.0) == 0

    def test_prometheus_families_and_values(self):
        reg = MetricsRegistry()
        pol = SloPolicy(targets={"interactive": {"ttft": 0.1}})
        wd = SloWatchdog(pol, registry=reg)
        wd.observe_ttft("interactive", 5.0, "r0")
        wd.observe_finish("interactive", "r0", None)
        wd.observe_finish("batch", "r1", None)
        text = render_prometheus(reg)
        assert "zoo_slo_goodput_interactive 0.0" in text
        assert "zoo_slo_goodput_batch 1.0" in text
        assert "zoo_slo_requests_total_interactive 1" in text
        assert "zoo_slo_good_requests_total_interactive 0" in text
        assert "zoo_slo_ttft_breaches_total_interactive 1" in text
        assert "# TYPE zoo_slo_requests_total_interactive counter" \
            in text
        assert "# TYPE zoo_slo_goodput_interactive gauge" in text


# ---------------------------------------------------------------------------
# correlated structured logging
# ---------------------------------------------------------------------------

class TestCorrelatedLogging:
    def _record(self, msg="hello", **extra):
        rec = logging.LogRecord("analytics_zoo_tpu", logging.INFO,
                                __file__, 1, msg, (), None)
        for k, v in extra.items():
            setattr(rec, k, v)
        return rec

    def test_formatter_picks_up_contextvar_uri(self):
        fmt = JsonLogFormatter()
        with request_uri_context("req-7"):
            line = fmt.format(self._record())
        out = json.loads(line)
        assert out["uri"] == "req-7" and out["msg"] == "hello"
        assert out["level"] == "INFO"
        # outside the context the uri is absent, not null
        assert "uri" not in json.loads(fmt.format(self._record()))

    def test_explicit_extra_beats_contextvar(self):
        fmt = JsonLogFormatter()
        with request_uri_context("ambient"):
            out = json.loads(fmt.format(self._record(uri="explicit")))
        assert out["uri"] == "explicit"

    def test_ring_handler_is_bounded(self):
        ring = RingLogHandler(capacity=3)
        for i in range(10):
            ring.emit(self._record(msg=f"m{i}"))
        tail = ring.snapshot()
        assert [r["msg"] for r in tail] == ["m7", "m8", "m9"]
        assert [r["msg"] for r in ring.snapshot(last=1)] == ["m9"]

    def test_install_is_idempotent(self):
        logger = logging.getLogger("analytics_zoo_tpu")
        before = list(logger.handlers)
        try:
            a = install_flight_logging()
            b = install_flight_logging()
            assert a is b
            rings = [h for h in logger.handlers
                     if isinstance(h, RingLogHandler)]
            assert len(rings) == 1
        finally:
            for h in list(logger.handlers):
                if h not in before and isinstance(h, RingLogHandler):
                    logger.removeHandler(h)


# ---------------------------------------------------------------------------
# normalize_request_id
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("raw,expect", [
    ("req-1", "req-1"),
    ("a.b:c_D9", "a.b:c_D9"),
    ("x" * 128, "x" * 128),
    ("x" * 129, None),                  # too long
    ("", None),
    (None, None),
    ("has space", None),
    ("new\nline", None),
    ("sneaky\x00", None),
    (42, None),                         # not a string
])
def test_normalize_request_id(raw, expect):
    assert normalize_request_id(raw) == expect


# ---------------------------------------------------------------------------
# anomaly monitor
# ---------------------------------------------------------------------------

class TestAnomalyMonitor:
    def _mon(self, dumps, **kw):
        kw.setdefault("min_interval_s", 0.0)
        return AnomalyMonitor(
            lambda reason, detail: dumps.append((reason, detail))
            or f"/tmp/{reason}", **kw)

    def test_alloc_streak_is_edge_triggered(self):
        dumps = []
        mon = self._mon(dumps, alloc_streak=3)
        for streak in (1, 2, 3, 4, 5):      # one long drought
            mon.poll(alloc_fail_streak=streak)
        assert [r for r, _ in dumps] == ["alloc_failure_streak"]
        mon.poll(alloc_fail_streak=0)       # streak breaks: re-arms
        mon.poll(alloc_fail_streak=3)
        assert len(dumps) == 2
        assert dumps[0][1]["streak_ticks"] == 3

    def test_rate_limit_swallows_repeat_triggers(self):
        dumps = []
        mon = self._mon(dumps, alloc_streak=1, min_interval_s=3600.0)
        mon.poll(alloc_fail_streak=1)
        mon.poll(alloc_fail_streak=0)
        mon.poll(alloc_fail_streak=1)       # re-armed but rate-limited
        assert len(dumps) == 1

    def test_steady_state_retrace_uses_baseline(self):
        dumps = []
        mon = self._mon(dumps, steady_after_ticks=10)
        mon.poll(ticks=5, compiles=4)       # warmup: compiles are free
        mon.poll(ticks=11, compiles=7)      # first steady poll: baseline
        assert dumps == []
        mon.poll(ticks=12, compiles=7)
        assert dumps == []
        mon.poll(ticks=13, compiles=9)      # growth past the baseline
        assert [r for r, _ in dumps] == ["steady_state_retrace"]
        assert dumps[0][1]["new_compiles"] == 2

    def test_breach_burst_trigger_rearms_below_threshold(self):
        class _Wd:
            burst = 0

            def breach_burst(self, window_s):
                return self.burst

        dumps = []
        mon = self._mon(dumps, breach_burst=4)
        wd = _Wd()
        wd.burst = 4
        mon.poll(watchdog=wd)
        mon.poll(watchdog=wd)               # still high: armed stays off
        assert len(dumps) == 1
        wd.burst = 0
        mon.poll(watchdog=wd)               # quiet: re-arm
        wd.burst = 9
        mon.poll(watchdog=wd)
        assert [r for r, _ in dumps] == ["slo_breach_burst"] * 2

    def test_crash_dumps_and_dump_errors_never_raise(self):
        dumps = []
        mon = self._mon(dumps)
        assert mon.crash("Traceback ...") == "/tmp/engine_crash"
        assert mon.history()[0]["reason"] == "engine_crash"

        def boom(reason, detail):
            raise OSError("disk full")

        mon2 = AnomalyMonitor(boom, min_interval_s=0.0, alloc_streak=1)
        mon2.poll(alloc_fail_streak=1)      # must not propagate
        assert mon2.history()[0]["path"] is None


# ---------------------------------------------------------------------------
# bundle round-trip through the stdlib CLI
# ---------------------------------------------------------------------------

class TestBundleAndCli:
    def _bundle(self, tmp_path):
        fr = FlightRecorder(capacity=8)
        for k in ("decode", "chunked", "spec"):
            fr.record({"seq": fr.next_seq(), "ts": 1.0, "dur_ms": 2.5,
                       "kind": k, "active": 1, "queue_depth": 0,
                       "alloc_failures": 1, "alloc_fail_streak": 2})
        wd = SloWatchdog(SloPolicy(targets={"standard": {"ttft": 0.1}}))
        wd.observe_ttft("standard", 1.0, "req-1")
        wd.observe_finish("standard", "req-1", None)
        ring = RingLogHandler(capacity=8)
        with request_uri_context("req-1"):
            ring.emit(logging.LogRecord(
                "analytics_zoo_tpu", logging.WARNING, __file__, 1,
                "pool dry", (), None))
        return dump_bundle(
            str(tmp_path), reason="alloc_failure_streak",
            detail={"streak_ticks": 2}, flight=fr,
            config={"engine_slots": 2, "flight_capacity": 8},
            logs=ring.snapshot(), slo=wd.status())

    def test_bundle_layout_and_manifest(self, tmp_path):
        path = self._bundle(tmp_path)
        assert os.path.basename(path).startswith(
            "flight-") and path.endswith("alloc_failure_streak")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["reason"] == "alloc_failure_streak"
        assert manifest["n_flight_ticks"] == 3
        for name in manifest["files"]:
            assert os.path.exists(os.path.join(path, name)), name
        with open(os.path.join(path, "flight.json")) as f:
            flight = json.load(f)
        assert [t["kind"] for t in flight["ticks"]] == \
            ["decode", "chunked", "spec"]
        with open(os.path.join(path, "logs.jsonl")) as f:
            logs = [json.loads(ln) for ln in f]
        assert logs[0]["uri"] == "req-1"    # contextvar correlation

    def test_cli_renders_bundle_rc0(self, tmp_path, capsys):
        from analytics_zoo_tpu.serving import debug

        path = self._bundle(tmp_path)
        assert debug.main([path]) == 0
        out = capsys.readouterr().out
        assert "alloc_failure_streak" in out
        assert "tick timeline" in out
        assert "goodput=0.000" in out       # the breached class
        assert "pool dry" in out            # the log tail

    def test_cli_unknown_bundle_or_uri_rc2(self, tmp_path):
        from analytics_zoo_tpu.serving import debug

        assert debug.main([str(tmp_path / "nope")]) == 2
        path = self._bundle(tmp_path)
        assert debug.main([path, "--uri", "ghost"]) == 2

    def test_cli_runs_without_package_deps(self, tmp_path):
        """The CLI contract: the renderer itself is stdlib-only, so the
        FILE runs on a bare python (no jax, no numpy — ``-S`` keeps
        site-packages out and a stray dependency import would fail).
        The ``-m`` spelling additionally needs the package importable;
        ``test_serve_smoke.py``'s starved-pool test covers that path."""
        from analytics_zoo_tpu.serving import debug

        path = self._bundle(tmp_path)
        proc = subprocess.run(
            [sys.executable, "-S", os.path.abspath(debug.__file__),
             path], capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "tick timeline" in proc.stdout

    def test_prune_keeps_newest(self, tmp_path):
        paths = []
        for i in range(4):
            p = tmp_path / f"flight-2026010{i}-000000-test"
            p.mkdir()
            os.utime(p, (i, i))
            paths.append(p)
        assert prune_bundles(str(tmp_path), keep=2) == 2
        left = sorted(os.listdir(tmp_path))
        assert left == [paths[2].name, paths[3].name]
        assert prune_bundles(str(tmp_path / "missing"), keep=1) == 0


# ---------------------------------------------------------------------------
# schema versioning + spec-acceptance section (the simulator's contract)
# ---------------------------------------------------------------------------

class TestSchemaVersioning:
    """Bundles are a versioned interchange format now that the offline
    simulator (serving/sim) replays them: every tick record, flight.json
    and manifest.json carry ``schema_version`` so a replayer can refuse
    bundles written by a future engine instead of misreading them."""

    def test_record_stamps_schema_version(self):
        fr = FlightRecorder(capacity=2)
        fr.record({"seq": fr.next_seq()})
        assert fr.snapshot()[0]["schema_version"] == \
            FLIGHT_SCHEMA_VERSION

    def test_record_keeps_explicit_version(self):
        # setdefault semantics: a caller replaying old ticks through a
        # new recorder must not have their version silently upgraded
        fr = FlightRecorder(capacity=2)
        fr.record({"seq": fr.next_seq(), "schema_version": 0})
        assert fr.snapshot()[0]["schema_version"] == 0

    def test_bundle_files_carry_schema_version(self, tmp_path):
        fr = FlightRecorder(capacity=2)
        fr.record({"seq": fr.next_seq(), "kind": "decode"})
        path = dump_bundle(str(tmp_path), reason="versioned",
                           detail={}, flight=fr)
        with open(os.path.join(path, "manifest.json")) as f:
            assert json.load(f)["schema_version"] == \
                FLIGHT_SCHEMA_VERSION
        with open(os.path.join(path, "flight.json")) as f:
            flight = json.load(f)
        assert flight["schema_version"] == FLIGHT_SCHEMA_VERSION
        assert flight["ticks"][0]["schema_version"] == \
            FLIGHT_SCHEMA_VERSION

    def test_spec_acceptance_round_trips(self, tmp_path):
        acc = {"k": 2, "rounds": 5, "counts": [1, 1, 3],
               "mean_accepted": 1.4}
        path = dump_bundle(str(tmp_path), reason="spec", detail={},
                           spec_acceptance=acc)
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert "spec_acceptance.json" in manifest["files"]
        with open(os.path.join(path, "spec_acceptance.json")) as f:
            assert json.load(f) == acc

    def test_spec_acceptance_absent_when_not_given(self, tmp_path):
        path = dump_bundle(str(tmp_path), reason="nospec", detail={})
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert "spec_acceptance.json" not in manifest["files"]
        assert not os.path.exists(
            os.path.join(path, "spec_acceptance.json"))

    def test_simulation_doc_pins_current_version(self):
        """Doc-drift guard (same spirit as test_doc_drift_guard below):
        docs/simulation.md states the schema_version the code writes.
        Bumping FLIGHT_SCHEMA_VERSION without re-documenting the
        migration fails here."""
        doc_path = os.path.join(os.path.dirname(__file__), os.pardir,
                                "docs", "simulation.md")
        with open(doc_path) as f:
            doc = f.read()
        assert f"current schema_version: {FLIGHT_SCHEMA_VERSION}" \
            in doc


# ---------------------------------------------------------------------------
# engine-level: per-tick records, watchdog wiring, greedy parity
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


@pytest.mark.slow
class TestEngineFlight:
    """Engine builds are compile-heavy on the CPU box, so this class
    is out of the tier-1 'not slow' budget; `make serve-smoke` runs
    this file unfiltered."""

    def test_composed_engine_records_full_schema(self, lm):
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=5,
                               max_slots=3, prompt_buckets=(8, 16),
                               draft_model=model,
                               draft_variables=variables,
                               speculation_k=2, paged=True,
                               block_size=4, chunked=True,
                               tick_token_budget=16,
                               flight_capacity=64)
        rng = np.random.default_rng(0)
        done = {}
        for i, n in enumerate((4, 12, 7)):
            eng.submit(f"r{i}", rng.integers(1, 32, n).astype(np.int32),
                       on_done=lambda u, t: done.__setitem__(u, t))
        eng.drain()
        assert len(done) == 3
        ticks = eng.flight.snapshot()
        assert len(ticks) == eng.telemetry.c_ticks.value
        seqs = [t["seq"] for t in ticks]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert {t["kind"] for t in ticks} <= {"spec", "spec_chunked"}
        expect = {"seq", "ts", "dur_ms", "kind", "active",
                  "queue_depth", "decode_uris", "prefill_uris",
                  "preempted", "compiles", "alloc_failures",
                  "alloc_fail_streak", "free_blocks",
                  "draft_free_blocks", "used_blocks",
                  "draft_used_blocks", "spec_proposed", "spec_accepted",
                  "budget", "budget_used"}
        assert expect <= set(ticks[-1]), sorted(ticks[-1])
        # every finished uri showed up in some tick's row sets
        seen = set()
        for t in ticks:
            seen.update(t["decode_uris"])
            seen.update(t["prefill_uris"])
        assert set(done) <= seen
        assert eng.alloc_fail_streak == 0

    def test_flight_capacity_zero_disables(self, lm):
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        eng = ContinuousEngine(model, variables, max_new_tokens=3,
                               max_slots=2, prompt_buckets=(8,),
                               flight_capacity=0)
        assert eng.flight is None
        done = {}
        eng.submit("r0", np.arange(1, 6, dtype=np.int32),
                   on_done=lambda u, t: done.__setitem__(u, t))
        eng.drain()
        assert len(done) == 1               # recording is purely opt-out

    def test_greedy_parity_recorder_on_vs_off(self, lm):
        """The recorder is host-side only: greedy outputs are bitwise
        identical with the ring attached and detached, and both match
        the single-request reference decode."""
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        rng = np.random.default_rng(3)
        prompts = {f"p{i}": rng.integers(1, 32, 5).astype(np.int32)
                   for i in range(4)}
        outs = []
        for cap in (64, 0):
            eng = ContinuousEngine(model, variables, max_new_tokens=4,
                                   max_slots=2, prompt_buckets=(8,),
                                   paged=True, block_size=4,
                                   chunked=True, tick_token_budget=8,
                                   flight_capacity=cap)
            res = {}
            for u, p in prompts.items():
                eng.submit(u, p,
                           on_done=lambda u, t: res.__setitem__(u, t))
            eng.drain()
            outs.append(res)
        assert set(outs[0]) == set(outs[1]) == set(prompts)
        for u in prompts:
            np.testing.assert_array_equal(outs[0][u], outs[1][u],
                                          err_msg=u)
            solo = np.asarray(generate(
                model, variables, jnp.asarray(prompts[u][None]), 4))[0]
            np.testing.assert_array_equal(outs[0][u], solo, err_msg=u)

    def test_telemetry_feeds_watchdog(self, lm):
        """The Telemetry request hooks drive the watchdog with the SAME
        stamps the histograms see: impossible targets make every
        request breach; default targets keep them all good."""
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        model, variables = lm
        rng = np.random.default_rng(5)
        # 1e9: even a cold-start jit compile meets the target; 1e-9:
        # nothing can (CPU cold starts blow the DEFAULT targets, so
        # this test pins explicit ones)
        for targets, good in ((1e9, 3), (1e-9, 0)):
            eng = ContinuousEngine(model, variables, max_new_tokens=4,
                                   max_slots=2, prompt_buckets=(8,))
            pol = SloPolicy(
                targets={c: {m: targets for m in
                             ("ttft", "tpot", "queue_wait")}
                         for c in ("interactive", "standard", "batch")})
            wd = SloWatchdog(pol, registry=eng.telemetry.metrics)
            eng.telemetry.watchdog = wd
            done = {}
            for i in range(3):
                eng.submit(f"r{i}",
                           rng.integers(1, 32, 5).astype(np.int32),
                           on_done=lambda u, t: done.__setitem__(u, t),
                           priority="interactive")
            eng.drain()
            st = wd.status()["per_class"]["interactive"]
            assert st["finished"] == 3, st
            assert st["good"] == good, (targets, st)
            if good == 0:       # tpot judged too (multi-token requests)
                assert st["breaches"]["tpot"] >= 1, st
                assert st["breaches"]["ttft"] == 3, st


# ---------------------------------------------------------------------------
# live stack: /debug/flight, /healthz SLO, X-Request-Id, doc drift
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stack(lm):
    """One spec+paged+chunked+qos ClusterServing behind HttpFrontend,
    shared by every HTTP-surface test in this module."""
    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, ServingConfig)

    model, variables = lm
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=4,
                           prompt_buckets=(8,),
                           draft_model=model, draft_variables=variables)
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=4, engine_chunked=True,
                        engine_speculation_k=2, qos_enabled=True)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    try:
        yield serving, fe
    finally:
        fe.stop()
        serving.stop()


def _post(fe, body, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=600)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     dict({"Content-Type": "application/json"},
                          **(headers or {})))
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(fe, path):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=600)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


@pytest.mark.slow
class TestLiveStack:
    """Shares the one live spec+paged+chunked stack above; slow for
    the same reason as TestEngineFlight (serve-smoke runs it)."""

    def test_client_request_id_honored_and_echoed(self, stack):
        serving, fe = stack
        prompt = list(range(1, 8))
        status, headers, body = _post(
            fe, {"tokens": prompt}, {"X-Request-Id": "client-id-1"})
        assert status == 200, body
        assert headers.get("X-Request-Id") == "client-id-1"
        # the id IS the uri on every surface: the engine's span ring
        events = serving.engine.telemetry.dump_trace()["traceEvents"]
        uris = {e.get("args", {}).get("uri") for e in events}
        assert "client-id-1" in uris

    def test_unusable_request_id_falls_back_to_uuid(self, stack):
        _, fe = stack
        status, headers, _ = _post(
            fe, {"tokens": list(range(1, 8))},
            {"X-Request-Id": "bad id with spaces"})
        assert status == 200
        echoed = headers.get("X-Request-Id")
        assert echoed and echoed != "bad id with spaces"

    def test_sse_start_event_carries_request_id(self, stack):
        _, fe = stack
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=600)
        try:
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": list(range(1, 8)), "stream": True}),
                {"Content-Type": "application/json",
                 "X-Request-Id": "sse-id-1"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("X-Request-Id") == "sse-id-1"
            raw = resp.read().decode()
        finally:
            conn.close()
        first = [c for c in raw.split("\n\n") if c.strip()][0]
        assert first.startswith("event: start"), first
        assert json.loads(first.split("data: ", 1)[1])["uri"] == "sse-id-1"

    def test_healthz_carries_slo_fields(self, stack):
        _, fe = stack
        status, body = _get(fe, "/healthz")
        assert status == 200
        h = json.loads(body)
        assert set(h["slo"]) == {"goodput", "breaches"}
        for cls in ("interactive", "standard", "batch"):
            assert 0.0 <= h["slo"]["goodput"][cls] <= 1.0
            assert h["slo"]["breaches"][cls] >= 0

    def test_debug_flight_live_view(self, stack):
        _, fe = stack
        status, body = _get(fe, "/debug/flight?n=5")
        assert status == 200
        d = json.loads(body)
        assert d["capacity"] > 0
        assert 1 <= len(d["ticks"]) <= 5
        rec = d["ticks"][-1]
        assert {"seq", "kind", "active", "alloc_fail_streak"} <= set(rec)
        assert "per_class" in d["slo"]
        assert isinstance(d["anomalies"], list)

    def test_doc_drift_guard(self, stack):
        """docs/observability.md and the live scrape must agree: every
        documented ``zoo_*`` family exists in /metrics, and every
        exported family is documented (bare name under its layer
        heading or the full prefixed name)."""
        _, fe = stack
        text = fe.prometheus()
        families = set(re.findall(r"# TYPE (\S+) ", text))
        assert families, "scrape rendered no TYPE lines"

        doc_path = os.path.join(os.path.dirname(__file__), os.pardir,
                                "docs", "observability.md")
        with open(doc_path) as f:
            doc = f.read()
        # expand foo_{a,b,c} shorthand into foo_a foo_b foo_c
        for base, alts in re.findall(r"([a-z0-9_]+)_\{([a-z_,]+)\}",
                                     doc):
            doc += " " + " ".join(f"{base}_{a}"
                                  for a in alts.split(","))

        prefixes = ("zoo_engine_", "zoo_serving_", "zoo_http_",
                    "zoo_slo_", "zoo_router_")
        undocumented = [f for f in families
                        if f not in doc
                        and not any(f.startswith(p)
                                    and f[len(p):] in doc
                                    for p in prefixes)]
        assert not undocumented, (
            f"families exported but missing from docs/observability.md: "
            f"{sorted(undocumented)}")

        phantom = []
        for name in set(re.findall(r"zoo_[a-z0-9_]*[a-z0-9]", doc)):
            if len(name.split("_")) < 3:
                continue                    # layer globs like zoo_engine
            base = re.sub(r"_(count|sum)$", "", name)
            if base not in families:
                phantom.append(name)
        assert not phantom, (
            f"documented names absent from a live scrape: "
            f"{sorted(phantom)}")
