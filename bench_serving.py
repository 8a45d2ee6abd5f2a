"""Cluster Serving benchmark — req/s + latency percentiles (BASELINE.md
config #6).

Measures the full system: N client threads enqueue through the RESP wire
protocol into the embedded broker, the pipelined serving loop micro-batches
and runs the jitted model on the TPU, results are polled back by the
clients.  Latency is client-observed end-to-end (enqueue -> result in hand).

Prints one JSON line per scenario and writes SERVING_BENCH.json (a run-time
output, git-ignored).  The scenarios are chip measurements: a scenario
child that finds no TPU exits non-zero, every row names the device it ran
on, and a failed scenario or row fails the run.  The ``--smoke`` legs are
the opposite: CPU dry runs of the wire protocol (``make serve-smoke``).
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np


def run_scenario(model_kind: str, n_clients: int, requests_per_client: int,
                 batch_size: int = 64, workers: int = 1) -> dict:
    import flax.linen as nn
    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import (
        ClusterServing, InputQueue, OutputQueue, ServingConfig)

    if model_kind == "mlp":
        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                for w in (256, 256, 128):
                    x = nn.relu(nn.Dense(w)(x))
                return nn.Dense(10)(x)

        model, feat = MLP(), np.zeros((1, 64), np.float32)
        cfg = ServingConfig(batch_size=batch_size, batch_timeout_ms=2.0,
                            workers=workers)
    elif model_kind.startswith("resnet18"):
        # REAL serving economics: encoded JPEG in over
        # the wire, native decode + resize on the server's thread pool,
        # uint8 H2D, normalisation on device, ResNet-18 forward on TPU.
        import jax.numpy as jnp

        from analytics_zoo_tpu.models import resnet18

        class ServedResNet18(nn.Module):
            @nn.compact
            def __call__(self, x):          # uint8 [B, 224, 224, 3]
                x = x.astype(jnp.float32) / 255.0
                mean = jnp.asarray([0.485, 0.456, 0.406])
                std = jnp.asarray([0.229, 0.224, 0.225])
                x = (x - mean) / std
                return resnet18(1000)(x, train=False)

        model = ServedResNet18()
        feat = np.zeros((1, 224, 224, 3), np.uint8)
        cfg = ServingConfig(batch_size=batch_size, batch_timeout_ms=4.0,
                            image_shape=[224, 224], workers=workers)
    elif model_kind.startswith("lm"):
        # generative serving: ragged token prompts in, 32 greedy tokens
        # out through the KV-cache scan (models/lm.generate).  "lm-spec"
        # adds SELF-draft speculative decoding: acceptance is ~k+1 by
        # construction, so the row measures the UPPER BOUND of the
        # round-trip amortisation (real drafts sit between this and the
        # plain "lm" row; models/distill.py closes the gap).
        from analytics_zoo_tpu.models import TransformerLM

        model = TransformerLM(vocab_size=8192, hidden_size=256,
                              num_layers=4, num_heads=4,
                              intermediate_size=1024, max_position=128)
        feat = np.zeros((1, 32), np.int32)
        cfg = ServingConfig(batch_size=batch_size, batch_timeout_ms=4.0,
                            workers=workers, prompt_col="tokens")
    else:
        raise ValueError(model_kind)

    variables = model.init(jax.random.key(0), feat)
    im = InferenceModel(batch_buckets=(1, 8, 32, batch_size))
    if model_kind == "lm-spec":
        im.load_flax_generator(model, variables, max_new_tokens=32,
                               prompt_buckets=(32,),
                               draft_model=model,
                               draft_variables=variables,
                               speculation_k=4)
    elif model_kind == "lm":
        im.load_flax_generator(model, variables, max_new_tokens=32,
                               prompt_buckets=(32,))
    else:
        # "-int8": weight-only quantized serving (the OpenVINO int8
        # role, memory-capacity mode); "-int8mxu": on-MXU int8 (dynamic
        # activation quant, int32 accumulation — the speed mode)
        quant = None
        if model_kind.endswith("-int8"):
            quant = "int8"
        elif model_kind.endswith("-int8mxu"):
            quant = "int8_mxu"
        im.load_flax(model, variables, quantize=quant)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()

    # warm the jit buckets so compile time is not measured
    for b in (1, 8, 32, batch_size):
        x = np.zeros((b,) + feat.shape[1:], feat.dtype)
        im.predict(x + 1 if model_kind.startswith("lm") else x)

    jpegs = []
    if model_kind.startswith("resnet18"):
        # a handful of distinct 256x256 JPEGs; server resizes to 224
        import io

        from PIL import Image

        rng = np.random.default_rng(7)
        for _ in range(8):
            arr = rng.integers(0, 256, (256, 256, 3)).astype(np.uint8)
            buf = io.BytesIO()
            Image.fromarray(arr).save(buf, "JPEG", quality=85)
            jpegs.append(buf.getvalue())

    lat: list = []
    lock = threading.Lock()
    errors: list = []

    def client(idx: int):
        inq = InputQueue(port=serving.port)
        outq = OutputQueue(port=serving.port)
        rng = np.random.default_rng(idx)
        mine = []
        try:
            for i in range(requests_per_client):
                t0 = time.perf_counter()
                if jpegs:
                    uri = inq.enqueue_image(
                        f"c{idx}-{i}", image=jpegs[(idx + i) % len(jpegs)])
                elif model_kind.startswith("lm"):
                    toks = rng.integers(
                        1, 8192, int(rng.integers(8, 33))).astype(np.int32)
                    uri = inq.enqueue(f"c{idx}-{i}", tokens=toks)
                else:
                    x = rng.normal(size=(64,)).astype(np.float32)
                    uri = inq.enqueue(f"c{idx}-{i}", x=x)
                r = outq.query(uri, timeout=60, poll_interval=0.001)
                if r is None:
                    raise TimeoutError(f"client {idx} req {i}")
                mine.append(time.perf_counter() - t0)
        except Exception as e:      # surface, don't hang the bench
            with lock:
                errors.append(repr(e))
        finally:
            with lock:
                lat.extend(mine)
            inq.close()
            outq.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    served = serving.stats["requests"]
    avg_fill = served / max(1, serving.stats["batches"])
    serving.stop()
    if errors:
        raise RuntimeError(f"bench clients failed: {errors[:3]}")
    a = np.asarray(lat)
    extra = {}
    if getattr(im, "spec_stats", None):
        extra["spec_mean_accepted_per_round"] = round(
            im.spec_stats["mean_accepted_per_round"], 2)
        extra["spec_note"] = ("self-draft upper bound: acceptance ~k+1 "
                              "by construction")
    if im.quant_stats:
        extra["weight_compression"] = im.quant_stats["compression"]
        extra["int8_role"] = (
            "memory-capacity knob, not throughput: the fused dequant "
            "taxes every forward (~35% req/s vs fp measured) and buys "
            "~4x model capacity per chip; see docs/architecture.md")
    return {
        **extra,
        "workers": workers,
        "model": model_kind,
        "clients": n_clients,
        "requests": int(a.size),
        "req_per_sec": round(a.size / wall, 1),
        "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 2),
        "p90_ms": round(float(np.percentile(a, 90)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 2),
        "avg_batch_fill": round(avg_fill, 1),
    }


def _latency_percentiles(timings: dict) -> dict:
    """TTFT / TPOT percentile columns from the engine's per-request
    wall-clock stamps (``ContinuousEngine.pop_request_timings``): TTFT
    = first token emitted - arrival (queueing + prefill), TPOT =
    consecutive token gaps pooled over every request (each gap is one
    engine-tick-granularity inter-token wait a streaming client would
    observe — the metric long monolithic prefills spike)."""
    ttft, gaps = [], []
    for t in timings.values():
        ts = t["token_times"]
        if ts:
            ttft.append(ts[0] - t["arrival"])
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))

    def pct(a, q):
        return round(float(np.percentile(np.asarray(a), q)) * 1e3, 2) \
            if a else None

    return {
        "ttft_p50_ms": pct(ttft, 50), "ttft_p90_ms": pct(ttft, 90),
        "ttft_p99_ms": pct(ttft, 99),
        "tpot_p50_ms": pct(gaps, 50), "tpot_p90_ms": pct(gaps, 90),
        "tpot_p99_ms": pct(gaps, 99),
    }


def _stream_percentiles(telemetry) -> dict:
    """TTFT / TPOT percentile columns straight from the engine's
    always-on telemetry histograms (``zoo_engine_ttft_seconds`` /
    ``zoo_engine_tpot_seconds``) — the same numbers ``GET /metrics``
    exports, no ``record_timings`` flag and no raw-stamp
    post-processing.  ``telemetry.reset_windows()`` after warmup is
    what scopes the window to measured traffic (compile time never
    pollutes the percentiles)."""
    def cols(h, label):
        s = h.snapshot()
        return {f"{label}_p{q}_ms":
                (round(s[f"p{q}"] * 1e3, 2) if f"p{q}" in s else None)
                for q in (50, 90, 99)}

    return {**cols(telemetry.h_ttft, "ttft"),
            **cols(telemetry.h_tpot, "tpot")}


def run_poisson_scenario(continuous: bool, rate_per_s: float,
                         n_requests: int, slots: int = 8,
                         prefix_mode: str = "none",
                         paged: bool = False,
                         chunked: bool = False) -> dict:
    """Open-loop mixed generative workload: requests arrive at Poisson
    times (not closed-loop clients), 80% short prompts / 20% long, all
    wanting 32 tokens.  The metric that separates the two serving modes
    is SHORT-request p50: under micro-batching a short prompt convoys
    behind the whole co-batched generation (plus the previous batch),
    while continuous batching admits it into the running decode arena
    and publishes it the moment it finishes.

    ``prefix_mode`` (continuous only) benchmarks prefix caching on a
    system-prompt workload (every request = one shared PFX-token prefix
    + its own short suffix — one request class, so only the short_*
    percentiles are reported): "full" ships the concatenated prompt
    every time, "cached" registers the prefix once and ships only
    suffixes — the delta is the per-request prefill the cache amortises
    away.

    ``paged=True`` serves from the block-pool KV cache instead of the
    slot arena and adds cache columns to the row: peak pool occupancy
    (sampled during the run), prefix-cache hit rate, max co-resident
    requests, preemptions, evictions.  With ``prefix_mode="full"`` the
    concatenated system prompt is shipped every time and the BLOCK-level
    prefix index dedups it automatically — no register_prefix call —
    which is the shared-system-prompt scenario the hit-rate column
    belongs to.

    Continuous rows also report **TTFT** (arrival -> first token) and
    **TPOT** (inter-token gap) p50/p90/p99 from the engine's always-on
    telemetry histograms — the streaming metrics the end-to-end latency
    column can't see (micro-batch mode delivers all tokens at once, so
    those columns only exist for the engine), and the same numbers a
    Prometheus scrape of ``GET /metrics`` would report.  ``chunked=True`` serves
    through the token-budget chunked-prefill scheduler."""
    import queue as _q

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, InputQueue, OutputQueue, ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=256, num_layers=4,
                          num_heads=4, intermediate_size=1024,
                          max_position=128)
    variables = model.init(jax.random.key(0), np.zeros((1, 32), np.int32))
    im = InferenceModel(batch_buckets=(1, 8, slots))
    im.load_flax_generator(model, variables, max_new_tokens=32,
                           prompt_buckets=(8, 32)
                           if prefix_mode == "none" else (8, 32, 80))
    cfg = ServingConfig(prompt_col="tokens", batch_size=slots,
                        batch_timeout_ms=4.0,
                        continuous_batching=continuous,
                        engine_slots=slots,
                        # 4 tokens per device call: admission granularity
                        # vs host round-trips
                        engine_ticks=4,
                        engine_paged=paged, engine_block_size=16,
                        engine_chunked=chunked)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()

    # paged cache columns: occupancy is instantaneous (drained pool ==
    # 0), so a sampler thread records the PEAK while requests are live
    occ_peak = [0.0]
    occ_stop = threading.Event()

    def occ_sampler():
        while not occ_stop.wait(0.05):
            m = serving.engine.cache_metrics()
            occ_peak[0] = max(occ_peak[0], m.get("occupancy", 0.0))

    occ_thread = None
    if paged:
        occ_thread = threading.Thread(target=occ_sampler, daemon=True)
        occ_thread.start()
    inq = InputQueue(port=serving.port)
    rng = np.random.default_rng(11)
    pid = None
    PFX = 64                    # the win scales with prefix length
    if prefix_mode != "none":
        assert continuous, "prefix_mode needs the continuous engine"
        system = rng.integers(1, 8192, PFX).astype(np.int32)
        if prefix_mode == "cached":
            pid = serving.register_prefix(system)
        # system-prompt workload: all requests share the prefix; the
        # suffixes are short
        short = [np.concatenate([system, rng.integers(
            1, 8192, int(rng.integers(4, 9))).astype(np.int32)])
            for _ in range(16)]
        long_ = short
    else:
        short = [rng.integers(1, 8192, int(rng.integers(4, 9))).astype(
            np.int32) for _ in range(16)]
        long_ = [rng.integers(1, 8192, int(rng.integers(24, 33))).astype(
            np.int32) for _ in range(16)]

    def enqueue_req(uri, p):
        if pid is not None:
            # ship ONLY the suffix; the engine splices the cached prefix
            inq.enqueue(uri, tokens=p[PFX:], prefix=np.int32(pid))
        else:
            inq.enqueue(uri, tokens=p)

    # warm both compile paths through the real serving loop
    wq = OutputQueue(port=serving.port)
    enqueue_req("warm-s", short[0])
    enqueue_req("warm-l", long_[0])
    wq.query("warm-s", timeout=600)
    wq.query("warm-l", timeout=600)
    if continuous:
        # TTFT/TPOT come from the always-on telemetry histograms; only
        # the warmup samples (which carry compile time) must go, so
        # clear the percentile windows and let measured traffic refill
        # them — cumulative counters are untouched by design
        serving.engine.telemetry.reset_windows()

    enq_t: dict = {}
    kinds: dict = {}
    lat: dict = {}
    lock = threading.Lock()
    uris: "_q.Queue" = _q.Queue()
    errors: list = []

    def waiter():
        outq = OutputQueue(port=serving.port)
        try:
            while True:
                uri = uris.get()
                if uri is None:
                    return
                r = outq.query(uri, timeout=120, poll_interval=0.001)
                t1 = time.perf_counter()
                if r is None:
                    with lock:
                        errors.append(f"timeout {uri}")
                else:
                    with lock:
                        lat[uri] = t1 - enq_t[uri]
        except Exception as e:
            with lock:
                errors.append(repr(e))
        finally:
            outq.close()

    n_waiters = 16
    waiters = [threading.Thread(target=waiter) for _ in range(n_waiters)]
    for w in waiters:
        w.start()
    t_start = time.perf_counter()
    for i in range(n_requests):
        is_short = rng.random() < 0.8
        p = (short if is_short else long_)[int(rng.integers(16))]
        uri = f"r{i}"
        kinds[uri] = "short" if is_short else "long"
        enq_t[uri] = time.perf_counter()
        enqueue_req(uri, p)
        uris.put(uri)
        time.sleep(float(rng.exponential(1.0 / rate_per_s)))
    for _ in waiters:
        uris.put(None)
    for w in waiters:
        w.join()
    wall = time.perf_counter() - t_start
    cache = serving.engine.cache_metrics() if paged else None
    stream = _stream_percentiles(serving.engine.telemetry) \
        if continuous else {}
    if occ_thread is not None:
        occ_stop.set()
        occ_thread.join()
    serving.stop()
    inq.close()
    wq.close()
    if errors:
        raise RuntimeError(f"poisson bench failed: {errors[:3]}")

    def pct(sel, q):
        a = np.asarray([v for u, v in lat.items() if kinds[u] == sel])
        return round(float(np.percentile(a, q)) * 1e3, 2) if a.size \
            else None

    name = "lm-poisson-cb" if continuous else "lm-poisson"
    if prefix_mode != "none":
        name = f"lm-prefix-{prefix_mode}"
    if paged:
        name = "lm-sysprompt-pg" if prefix_mode != "none" \
            else "lm-poisson-pg"
    if chunked:
        name += "-ck"
    out = {
        "model": name,
        "mode": "continuous" if continuous else "microbatch",
        "rate_per_s": rate_per_s,
        "requests": len(lat),
        "req_per_sec": round(len(lat) / wall, 1),
        "short_p50_ms": pct("short", 50),
        "short_p90_ms": pct("short", 90),
        **stream,
    }
    if prefix_mode == "none":
        # prefix rows have ONE request class; a long_* percentile there
        # would read as long-prompt latency when it is just a random
        # subsample of the identical workload
        out["long_p50_ms"] = pct("long", 50)
        out["long_p90_ms"] = pct("long", 90)
    else:
        out["prefix_tokens"] = PFX
    if cache is not None:
        out["cache_occupancy_peak"] = round(float(occ_peak[0]), 3)
        out["prefix_hit_rate"] = round(cache["prefix_hit_rate"], 3)
        out["max_coresident"] = cache["peak_resident"]
        out["preemptions"] = cache["preemptions"]
        out["evictions"] = cache["evictions"]
    return out


def run_chunked_scenario(slots: int = 6) -> dict:
    """Mixed-workload head-to-head for the chunked-prefill scheduler at
    equal HBM (same arena geometry, so identical cache bytes by
    construction — the knob changes SCHEDULING, not memory) and equal
    WORK: both engines serve the identical closed-loop request
    sequence (``slots - 1`` short streamers held in flight, long
    prompts injected at fixed completion thresholds), so the req/s
    column is the same end-to-end completion rate over the same
    requests and the comparison is purely about how each engine
    schedules them.

    The workload that motivates chunking: short prompts are streaming
    tokens when a ~1024-token prompt arrives.  Monolithic admission
    prefills it in ONE device call, so every streaming client observes
    an inter-token gap the size of the whole prefill — a p99 TPOT
    spike.  The chunked scheduler spreads the same prefill over fused
    ticks bounded by ``tick_token_budget``, so decoders advance every
    tick and p99 stays near p50.  The closed loop keeps streamers
    decoding through every prefill (the steady-traffic worst case
    chunking exists for), and long prompts are ~8x the chunk budget,
    so the stall gaps are both far above one fused tick AND numerous
    enough to sit safely above the pooled p99 index.  The row reports
    off/on TTFT + TPOT percentiles and their p99 inter-token ratio
    (the ISSUE acceptance bar is >= 2x at equal-or-higher req/s)."""
    import jax

    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import ContinuousEngine

    model = TransformerLM(vocab_size=8192, hidden_size=256, num_layers=4,
                          num_heads=4, intermediate_size=1024,
                          max_position=1056)
    variables = model.init(jax.random.key(0), np.zeros((1, 32), np.int32))
    rng = np.random.default_rng(23)
    shorts = [rng.integers(1, 8192, int(rng.integers(8, 15))).astype(
        np.int32) for _ in range(16)]
    # every long prompt in every pass is UNIQUE: the paged pool's
    # prefix index would otherwise recognize a repeated long from the
    # warm pass (or an earlier injection) and skip the very prefill
    # stall this scenario measures
    longs = [rng.integers(1, 8192, int(rng.integers(960, 1025))).astype(
        np.int32) for _ in range(25)]
    n_shorts = 32
    inject_at = (4, 10, 16, 22, 28)     # long j submits when the j-th
    # threshold of short completions is crossed: 5 prefill collisions
    # spread across the run, each against a full set of streamers

    n_stream = slots - 1            # streaming decoder count; 1 slot
    # stays free so a long admits immediately

    def drive_closed(eng, tag, long_base):
        """One closed-loop pass: ``n_stream`` shorts kept in flight,
        longs (``longs[long_base:long_base + 5]``, fresh per pass)
        injected at short-completion thresholds.  The submission
        sequence is a deterministic function of completion order, so a
        warm pass replays the measured pass tick-for-tick in SHAPE
        (prompt lengths differ, buckets don't)."""
        done_s: list = []
        done_l: list = []
        issued = 0
        li = 0
        t0 = time.perf_counter()
        for _ in range(200_000):
            while issued < n_shorts and issued - len(done_s) < n_stream:
                eng.submit(f"{tag}-s{issued}",
                           shorts[issued % len(shorts)],
                           on_done=lambda u, t: done_s.append(u))
                issued += 1
            while li < len(inject_at) and len(done_s) >= inject_at[li]:
                eng.submit(f"{tag}-l{li}", longs[long_base + li],
                           on_done=lambda u, t: done_l.append(u))
                li += 1
            eng.step()
            if (issued >= n_shorts and li == len(inject_at)
                    and len(done_s) == n_shorts
                    and len(done_l) == len(inject_at)
                    and eng.n_active == 0):
                return (len(done_s) + len(done_l),
                        time.perf_counter() - t0)
        raise RuntimeError(f"chunked bench stalled: {tag}")

    def run(chunked):
        from analytics_zoo_tpu.lint import RetraceError, trace_guard

        # paged allocator on BOTH sides: chunks write K/V through block
        # tables in place, so a fused tick costs compute + dispatch
        # only — the arena path would re-gather/scatter the long's
        # whole cache window every tick (O(L^2/budget) copies), taxing
        # the chunked engine's throughput for no scheduling reason
        kw = dict(max_new_tokens=24, max_slots=slots,
                  prompt_buckets=(16, 128, 1024), paged=True,
                  block_size=16)
        if chunked:
            # a full 128-token chunk + every decode row fits each
            # tick (134 = 128 + max_slots), so one long needs exactly
            # 8 fused ticks instead of one monolithic 1024-token
            # prefill; each tick's latency stays budget-bounded and
            # the chunk is wide enough to amortize per-tick dispatch
            # overhead (throughput headroom)
            kw.update(chunked=True, tick_token_budget=134)
        eng = ContinuousEngine(model, variables, **kw)
        # warmup, then a GUARANTEED zero-compile measurement: the
        # chunked engine eagerly compiles its entire fused shape grid,
        # a warm pass exactly replays the deterministic closed loop
        # (covering the monolithic engine's bucketed prefill + decode
        # programs too), and the measured pass runs under the repo's
        # own trace_guard — if a compile still slips through, the
        # guard trips, the compile lands in the cache, and the pass is
        # re-run
        if chunked:
            eng.precompile_chunked()
        drive_closed(eng, "warm", 0)
        for attempt in range(4):
            # raw per-uri stamps (the telemetry keep_request_stamps
            # shim): the short/long TPOT split below needs per-request
            # attribution that the pooled always-on histograms don't
            # keep — this scenario is the reason the shim exists
            eng.record_timings = True
            eng.pop_request_timings()       # drop warm/aborted stamps
            try:
                with trace_guard(eng, name="chunked-bench"):
                    n, wall = drive_closed(eng, f"run{attempt}",
                                           5 * (attempt + 1))
                break
            except RetraceError:
                eng.drain()                 # finish the aborted pass
        else:
            raise RuntimeError("fused shapes did not converge")
        tm = eng.pop_request_timings()
        lp = _latency_percentiles(
            {u: t for u, t in tm.items() if "-s" in u})
        ttft_long = _latency_percentiles(
            {u: t for u, t in tm.items() if "-l" in u})
        m = eng.cache_metrics()
        col = {"requests": n, "req_per_sec": round(n / wall, 1), **lp,
               "ttft_long_p50_ms": ttft_long["ttft_p50_ms"]}
        if chunked:
            col["budget_utilization"] = round(m["budget_utilization"], 3)
            col["prefill_stall_ticks"] = m["prefill_stall_ticks"]
        return col, eng.capacity_report()["arena_bytes"]

    off, bytes_off = run(False)
    on, bytes_on = run(True)
    assert bytes_off == bytes_on, (bytes_off, bytes_on)
    ratio = round(off["tpot_p99_ms"] / on["tpot_p99_ms"], 2) \
        if off["tpot_p99_ms"] and on["tpot_p99_ms"] else None
    return {
        "model": "lm-chunked",
        "mode": "chunked-vs-monolithic",
        "slots": slots,
        "tick_token_budget": 134,
        "arena_bytes": int(bytes_off),
        "off": off,
        "on": on,
        "tpot_p99_ratio": ratio,
        "note": (f"equal paged-pool HBM, identical closed-loop workload "
                 f"({n_stream} streaming shorts held in flight, "
                 f"960-1024 token prompts injected at fixed completion "
                 f"thresholds); req/s is end-to-end completion rate; "
                 f"TPOT percentiles are short-request inter-token "
                 f"gaps"),
    }


def run_capacity_scenario(slots: int = 4) -> dict:
    """Equal-HBM co-residency head-to-head (no wire protocol — the claim
    is about KV memory, not RESP throughput).  The arena pays worst-case
    length L for every slot; the paged pool pays actual length in
    block_size-token quanta.  Give the paged engine a pool NO BIGGER
    than the arena's cache bytes and drive short-prompt traffic: it
    sustains >= 2x the arena's co-resident requests (ISSUE acceptance
    bar), measured as the engine's own peak_resident counter with zero
    preemptions (genuine co-residency, not admit/evict thrash)."""
    import jax

    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import ContinuousEngine

    model = TransformerLM(vocab_size=8192, hidden_size=256, num_layers=4,
                          num_heads=4, intermediate_size=1024,
                          max_position=128)
    variables = model.init(jax.random.key(0), np.zeros((1, 32), np.int32))
    kw = dict(max_new_tokens=32, prompt_buckets=(8, 64), ticks_per_step=4)
    arena = ContinuousEngine(model, variables, max_slots=slots, **kw)
    rep = arena.capacity_report()
    arena_bytes = rep["arena_bytes"]
    # L = 64+32 = 96 tokens; bs=8 -> 12 blocks/row; the arena's
    # slots*96 token slots buy slots*12 blocks (sink included, so one
    # block LESS than the arena's bytes).  A short request needs only
    # ceil((8+32)/8) = 5 blocks, so the same bytes hold
    # (slots*12 - 1)//5 residents — 2.3x at slots=4.
    bs = 8
    n_blocks = (slots * 96) // bs
    paged_slots = ((n_blocks - 1) * bs) // 40
    eng = ContinuousEngine(model, variables, max_slots=paged_slots,
                           paged=True, block_size=bs, n_blocks=n_blocks,
                           enable_prefix_cache=False, **kw)
    paged_bytes = eng.capacity_report()["arena_bytes"]
    assert paged_bytes <= arena_bytes, (paged_bytes, arena_bytes)
    rng = np.random.default_rng(13)
    done = []
    for i in range(3 * paged_slots):
        eng.submit(f"c{i}", rng.integers(1, 8192, int(rng.integers(
            4, 9))).astype(np.int32), on_done=lambda u, t: done.append(u))
    t0 = time.perf_counter()
    eng.drain()
    wall = time.perf_counter() - t0
    m = eng.cache_metrics()
    return {
        "model": "lm-capacity",
        "mode": "paged-vs-arena",
        "requests": len(done),
        "req_per_sec": round(len(done) / wall, 1),
        # composite HBM-efficiency column (32 greedy tokens/request):
        # comparable against the lm-kernel rows' same-named figure
        "tok_per_sec_per_kv_gib": round(
            (len(done) * 32 / wall) / (paged_bytes / 2**30), 1),
        "arena_slots": slots,
        "arena_bytes": int(arena_bytes),
        "paged_bytes": int(paged_bytes),
        "block_size": bs,
        "n_blocks": n_blocks,
        "max_coresident": m["peak_resident"],
        "coresident_ratio": round(m["peak_resident"] / slots, 2),
        "preemptions": m["preemptions"],
        "note": ("equal cache HBM; short prompts; arena pays worst-case "
                 "L per slot, paged pays actual length in blocks"),
    }


def run_spec_scenario(chunked: bool = False, slots: int = 2) -> dict:
    """Speculative decoding over the paged pool (and, for the second
    row, under the chunked scheduler) at EQUAL TOTAL KV HBM: the
    baseline engine gets the speculative engine's two tenants'
    combined block budget (off: n_blocks = 2N, no draft; on: N target
    + N draft), so the row answers "given these cache bytes, does
    spending half of them on a draft tenant buy decode throughput?".
    The draft is the TARGET MODEL ITSELF (the ``lm-spec`` batch row's
    precedent): greedy self-drafting accepts every proposal, so the
    acceptance rate — and the tokens/s uplift — is the k+1 UPPER
    BOUND; real drafts sit between this row and the plain one, at a
    FRACTION of the draft-tenant bytes (``split_block_budget`` charges
    per-block cost, and ``models/distill.py`` trains exactly that
    draft).  Self-draft also makes equal-HBM exact: both tenants'
    per-block bytes are identical, so halving the block budget halves
    the bytes.

    The workload is the LOW-BATCH decode-bound traffic speculation
    exists for: ``slots`` (few!) short-prompt streams held in flight,
    each decoding ``max_new`` greedy tokens, so wall time is decode
    rounds (prefill is a rounding error) and the column is decode
    tokens/s.  Few streams is the point, not a simplification: a spec
    round is ONE fused device call (k+1 draft feeds + one decode_k
    verify) emitting up to k+1 tokens per row, vs one call per token
    plain — but plain decode already amortises its dispatch across
    every co-resident row, so at high batch the batch dimension buys
    what speculation would have.  Speculation monetises when the
    device is under-fed per call — exactly the latency-bound
    few-streams regime accelerator decode lives in (dispatch + weight
    streaming, not FLOPs; measured here: the uplift at ``slots=2``
    inverts by ``slots=6`` on this host).  The self-draft also pays
    the FULL target forward per proposal — a real 5-10x-smaller draft
    widens every number here.

    The measured passes run under ``trace_guard`` — a steady-state
    retrace would bill compile time to one side and invalidate the
    ratio."""
    import jax

    from analytics_zoo_tpu.lint import RetraceError, trace_guard
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import ContinuousEngine

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=128)
    variables = model.init(jax.random.key(0), np.zeros((1, 32), np.int32))
    rng = np.random.default_rng(29)
    prompts = [rng.integers(1, 8192, int(rng.integers(8, 29))).astype(
        np.int32) for _ in range(24)]
    n_requests = 24 * slots
    max_new, k, bs = 32, 4, 8
    # spec verify writes through pos + k, so the speculative engine's
    # rows are ceil((32 + 32 + k+1)/8) = 9 blocks vs the baseline's 8;
    # the BUDGETS are what equal-HBM fixes: N blocks per tenant for
    # the speculative engine, 2N for the baseline
    N = slots * 12

    def drive(eng, tag):
        done: list = []
        issued = 0
        t0 = time.perf_counter()
        for _ in range(200_000):
            while issued < n_requests and issued - len(done) < slots:
                eng.submit(f"{tag}-r{issued}",
                           prompts[issued % len(prompts)],
                           on_done=lambda u, t: done.append(u))
                issued += 1
            eng.step()
            if len(done) == n_requests and eng.n_active == 0:
                return time.perf_counter() - t0
        raise RuntimeError(f"spec bench stalled: {tag}")

    def run(spec):
        # prefix cache off on BOTH sides: these prompts repeat across
        # the warm and measured passes, and a block-index hit would
        # skip prefill work asymmetrically between runs — the claim
        # here is about decode rounds, not sharing
        kw = dict(max_new_tokens=max_new, max_slots=slots,
                  prompt_buckets=(32,), paged=True, block_size=bs,
                  enable_prefix_cache=False)
        if spec:
            kw.update(draft_model=model, draft_variables=variables,
                      speculation_k=k, n_blocks=N, draft_n_blocks=N)
        else:
            kw.update(n_blocks=2 * N)
        if chunked:
            # one smallest-bucket chunk plus every decode row's
            # worst-case tick cost (a speculative row bills k+1 verify
            # positions against the budget) fits each fused tick
            kw.update(chunked=True,
                      tick_token_budget=32 + slots * (k + 1))
        eng = ContinuousEngine(model, variables, **kw)
        if chunked:
            eng.precompile_chunked()
        drive(eng, "warm")
        # best-of-3 measured passes: each pass is only ~1 s of wall, so
        # a host scheduler hiccup on the shared CPU box can swing one
        # pass more than the effect under measurement; min-wall is the
        # standard de-noiser and both sides get the same treatment
        walls: list = []
        for attempt in range(6):
            try:
                with trace_guard(eng, name="spec-bench"):
                    walls.append(drive(eng, f"run{attempt}"))
                if len(walls) == 3:
                    break
            except RetraceError:
                eng.drain()             # finish the aborted pass
        if not walls:
            raise RuntimeError("spec bench shapes did not converge")
        wall = min(walls)
        m = eng.cache_metrics()
        col = {"decode_tok_per_sec":
               round(n_requests * max_new / wall, 1),
               "req_per_sec": round(n_requests / wall, 1)}
        if spec:
            col["accept_rate"] = round(
                m["spec_accepted"] / max(1, m["spec_proposed"]), 3)
            col["spec_rounds"] = m["spec_rounds"]
        rep = eng.capacity_report()
        return col, rep["arena_bytes"] + rep.get("draft_arena_bytes", 0)

    off, bytes_off = run(False)
    on, bytes_on = run(True)
    assert bytes_off == bytes_on, (bytes_off, bytes_on)
    return {
        "model": "lm-spec-ck-pg" if chunked else "lm-spec-pg",
        "mode": "spec-vs-plain" + ("-chunked" if chunked else ""),
        "slots": slots,
        "speculation_k": k,
        "kv_bytes": int(bytes_off),
        "off": off,
        "on": on,
        "tok_per_sec_ratio": round(
            on["decode_tok_per_sec"] / off["decode_tok_per_sec"], 2),
        "note": ("equal TOTAL KV HBM (the baseline gets both tenants' "
                 "blocks); few streams by design — speculation's "
                 "regime is latency-bound low-batch decode (at high "
                 "batch the batch dimension already amortises "
                 "dispatch); self-draft => acceptance ~1.0, the k+1 "
                 "upper bound, AND full target compute per proposal — "
                 "a distilled 5-10x-smaller draft widens the ratio at "
                 "a fraction of the draft-tenant bytes"),
    }


def run_kernel_scenario(slots: int = 4) -> dict:
    """Paged-attention read path head-to-head at EQUAL TOTAL KV HBM:
    {gather, fused} x {bf16, int8} x tp∈{1, 2} on the same closed-loop
    greedy workload.  The figure of merit is ``tok_per_sec_per_kv_gib``
    — decode tokens/sec per GiB of KV pool — because the levers attack
    different factors: the fused kernel raises tokens/sec (no
    materialised ``[B, M*bs, KH, D]`` gather on the tick), int8
    roughly doubles the blocks the same bytes buy (rows cost D+2
    bytes vs 2D; at D=64 that is ~1.94x ``n_blocks``, asserted
    here >= 1.9).  Every row's pool is sized to the bf16 row's byte
    budget, so the int8 rows really do hold ~2x the blocks rather
    than just billing fewer bytes.  The tp=2 rows keep the same TOTAL
    pool bytes (the sharded layout halves the per-chip arena instead)
    and read it through the shard_map-wrapped fused kernel — the
    composite column is directly comparable down the whole matrix.

    A row that fails (a Mosaic refusal, a model build error) fails the
    scenario; tp=2 rows on a host with fewer than 2 devices say so in
    the row — they are not applicable there, not broken.  Measured
    passes run under ``trace_guard`` — the acceptance bar is zero
    steady-state retraces in every mode."""
    import jax

    from analytics_zoo_tpu.lint import RetraceError, trace_guard
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import ContinuousEngine
    from analytics_zoo_tpu.serving.paged_cache import block_bytes

    # hidden 256 / 4 heads -> head_dim 64: the geometry the ~1.9x
    # int8 claim is stated at ((2*64)/(64+2) = 1.94)
    model = TransformerLM(vocab_size=8192, hidden_size=256,
                          num_layers=2, num_heads=4,
                          intermediate_size=512, max_position=128)
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 32), np.int32))
    H = getattr(model, "kv_heads", model.num_heads)
    D = model.hidden_size // model.num_heads
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 8192, int(rng.integers(8, 29))).astype(
        np.int32) for _ in range(24)]
    n_requests = 12 * slots
    max_new, bs = 32, 8
    # equal HBM: the bf16 row's pool bytes are THE budget; each mode
    # gets however many blocks those bytes buy at its per-block cost
    bf16_blocks = slots * 12
    budget = bf16_blocks * block_bytes(model.num_layers, bs, H, D,
                                       "bf16")

    def drive(eng, tag):
        done: list = []
        issued = 0
        t0 = time.perf_counter()
        for _ in range(200_000):
            while issued < n_requests and issued - len(done) < slots:
                eng.submit(f"{tag}-r{issued}",
                           prompts[issued % len(prompts)],
                           on_done=lambda u, t: done.append(u))
                issued += 1
            eng.step()
            if len(done) == n_requests and eng.n_active == 0:
                return time.perf_counter() - t0
        raise RuntimeError(f"kernel bench stalled: {tag}")

    def run(kernel, kv_dtype, tp=1):
        mesh = None
        if tp > 1:
            from analytics_zoo_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(axes={"dp": -1, "tp": tp})
        n_blocks = budget // block_bytes(model.num_layers, bs, H, D,
                                         kv_dtype)
        eng = ContinuousEngine(
            model, variables, max_new_tokens=max_new, max_slots=slots,
            prompt_buckets=(32,), paged=True, block_size=bs,
            n_blocks=n_blocks, enable_prefix_cache=False,
            cache_dtype="bfloat16", kernel=kernel, kv_dtype=kv_dtype,
            mesh=mesh)
        pool_bytes = eng._per_block_bytes * n_blocks
        assert pool_bytes <= budget, (pool_bytes, budget)
        drive(eng, "warm")
        walls: list = []
        for attempt in range(6):
            try:
                with trace_guard(eng, name="kernel-bench"):
                    walls.append(drive(eng, f"run{attempt}"))
                if len(walls) == 3:
                    break
            except RetraceError:
                eng.drain()             # finish the aborted pass
        if not walls:
            raise RuntimeError("kernel bench shapes did not converge")
        wall = min(walls)
        tok_s = n_requests * max_new / wall
        return {"kernel": kernel, "kv_dtype": kv_dtype, "tp": tp,
                "n_blocks": int(n_blocks),
                "kv_pool_bytes": int(pool_bytes),
                "kv_pool_bytes_per_chip": int(
                    eng.capacity_report()["arena_bytes_per_chip"]),
                "kv_bytes_per_token": int(eng._kv_bytes_per_token),
                "decode_tok_per_sec": round(tok_s, 1),
                "tok_per_sec_per_kv_gib": round(
                    tok_s / (pool_bytes / 2**30), 1)}

    # the tp axis: equal TOTAL KV HBM — same n_blocks/bytes as the
    # tp=1 twin, per-chip arena halved by the kv-heads sharding; the
    # fused rows read the sharded pool through shard_map
    matrix = [("gather", "bf16", 1), ("fused", "bf16", 1),
              ("gather", "int8", 1), ("fused", "int8", 1),
              ("gather", "bf16", 2), ("fused", "bf16", 2),
              ("fused", "int8", 2)]
    rows = []
    for kernel, kv_dtype, tp in matrix:
        if tp > 1 and len(jax.devices()) < tp:
            rows.append({"kernel": kernel, "kv_dtype": kv_dtype,
                         "tp": tp,
                         "skipped": f"tp={tp} needs >= {tp} devices"})
            continue
        rows.append(run(kernel, kv_dtype, tp))

    def live(key):
        r = by.get(key)
        return r is not None and "skipped" not in r

    by = {(r["kernel"], r["kv_dtype"], r["tp"]): r for r in rows}
    ratio = None
    if live(("gather", "int8", 1)):
        ratio = round(by[("gather", "int8", 1)]["n_blocks"]
                      / bf16_blocks, 2)
        assert ratio >= 1.9, f"int8 blocks ratio {ratio} < 1.9"
    return {
        "model": "lm-kernel",
        "mode": "fused-vs-gather-x-bf16-vs-int8-x-tp",
        "slots": slots,
        "kv_budget_bytes": int(budget),
        "rows": rows,
        "int8_blocks_ratio": ratio,
        "fused_tok_per_sec_ratio": (round(
            by[("fused", "bf16", 1)]["decode_tok_per_sec"]
            / by[("gather", "bf16", 1)]["decode_tok_per_sec"], 2)
            if live(("fused", "bf16", 1))
            and live(("gather", "bf16", 1)) else None),
        # the fused-under-tp acceptance figure: fused vs gather on the
        # composite column at tp=2, equal total KV HBM
        "fused_tp_per_kv_gib_ratio": (round(
            by[("fused", "bf16", 2)]["tok_per_sec_per_kv_gib"]
            / by[("gather", "bf16", 2)]["tok_per_sec_per_kv_gib"], 2)
            if live(("fused", "bf16", 2))
            and live(("gather", "bf16", 2)) else None),
        "note": ("equal total KV HBM per row (pool sized to the bf16 "
                 "budget at each mode's per-block cost; tp=2 keeps "
                 "TOTAL bytes and halves the per-chip arena); greedy "
                 "closed-loop shorts; tok_per_sec_per_kv_gib is the "
                 "composite figure — kernel choice moves the "
                 "numerator, int8 moves the denominator, tp moves "
                 "neither (a memory layout)"),
    }


# scenario plan, most-informative-first (int8-mxu head-to-head,
# continuous-vs-convoy, generative load); (kind, clients, rpc, bs)
def run_qos_scenario(slots: int = 4, n_requests: int = 80) -> dict:
    """Heavy-traffic QoS front-door scenario (docs/serving_qos.md): a
    saturating mixed interactive/batch burst through the full wire
    protocol with per-tenant fair share on, a bounded admission queue
    rejecting the overflow, and mid-stream client aborts freeing KV
    blocks live.

    Reported per class: p50/p99 TTFT and TPOT from the engine's
    per-request stamps (the admission reorder IS the product — under
    saturation interactive p99 TTFT must sit well below batch), plus
    the rejected-request count (client-side ``BacklogFull`` and HTTP
    429s, whose finite ``Retry-After`` is asserted here), mid-stream
    aborts, and a ``starved_batch`` column that must be 0 — aging
    bounds how long weight-1 work can wait."""
    import http.client as _http
    import queue as _q

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        BacklogFull, ClusterServing, HttpFrontend, InputQueue,
        OutputQueue, ServingConfig)
    from analytics_zoo_tpu.serving.frontdoor import (
        encode_priority, encode_str_field)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, slots))
    im.load_flax_generator(model, variables, max_new_tokens=16,
                           prompt_buckets=(16,))
    max_backlog = max(8, n_requests // 3)
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=slots, engine_ticks=2,
                        engine_paged=True, engine_block_size=8,
                        engine_chunked=True, qos_enabled=True,
                        max_backlog=max_backlog)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port, max_backlog=max_backlog)
    wq = OutputQueue(port=serving.port)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 8192, int(rng.integers(6, 14))).astype(
        np.int32) for _ in range(16)]
    inq.enqueue("warm", tokens=prompts[0])
    assert wq.query("warm", timeout=600) is not None
    serving.engine.telemetry.reset_windows()
    serving.engine.record_timings = True

    lock = threading.Lock()
    served: set = set()
    aborted: set = set()
    uris_q: "_q.Queue" = _q.Queue()

    def waiter():
        outq = OutputQueue(port=serving.port)
        try:
            while True:
                u = uris_q.get()
                if u is None:
                    return
                r = outq.query(u, timeout=300, poll_interval=0.001)
                if r is not None:
                    with lock:
                        served.add(u)
        except Exception:
            pass
        finally:
            outq.close()

    def abort_after_first_token(u):
        # a streaming client that hangs up one token in: live cancel,
        # blocks must come back without waiting for the TTL prune
        my_inq = InputQueue(port=serving.port)
        outq = OutputQueue(port=serving.port)
        try:
            for ev in outq.stream_events(u, timeout=300):
                if "token" in ev:
                    my_inq.cancel(u)
                if any(k in ev for k in ("done", "cancelled", "error")):
                    with lock:
                        aborted.add(u)
                    return
        except TimeoutError:
            pass
        finally:
            my_inq.close()
            outq.close()

    waiters = [threading.Thread(target=waiter) for _ in range(12)]
    for w in waiters:
        w.start()
    abort_threads = []
    offered = rejected = 0
    enqueued: list = []
    t_start = time.perf_counter()
    for i in range(n_requests):
        # batch-heavy mix: 1 interactive per 3 batch — the regime
        # where the weights matter
        cls = "interactive" if i % 4 == 0 else "batch"
        uri = f"{cls[0]}{i}"
        streaming = len(abort_threads) < 6 and i % 10 == 5
        kw = dict(tokens=prompts[int(rng.integers(16))],
                  priority=encode_priority(cls),
                  tenant=encode_str_field(f"t{i % 2}"))
        if streaming:
            kw["stream"] = np.int32(1)
        offered += 1
        try:
            inq.enqueue(uri, **kw)
        except BacklogFull:
            rejected += 1
            continue
        enqueued.append((uri, cls))
        if streaming:
            th = threading.Thread(target=abort_after_first_token,
                                  args=(uri,))
            th.start()
            abort_threads.append(th)
        else:
            uris_q.put(uri)
        time.sleep(0.01)            # ~100 req/s offered: saturating
    # the queue is deep right now: a 429 + finite Retry-After must be
    # observable over HTTP while the backlog stands
    retry_after = None
    for _ in range(5):
        conn = _http.HTTPConnection("127.0.0.1", fe.port, timeout=60)
        conn.request("POST", "/v1/generate", json.dumps(
            {"tokens": prompts[0].tolist(), "stream": True,
             "priority": "batch"}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status == 429:
            rejected += 1
            retry_after = int(resp.getheader("Retry-After", "0"))
            assert 1 <= retry_after <= 120, retry_after
            resp.read()
            conn.close()
            break
        resp.close()
        conn.close()
    for _ in waiters:
        uris_q.put(None)
    for w in waiters:
        w.join()
    for th in abort_threads:
        th.join()
    wall = time.perf_counter() - t_start
    timings = serving.engine.pop_request_timings()
    cache = serving.engine.cache_metrics()
    fe.stop()
    serving.stop()
    inq.close()
    wq.close()

    def pct(cls, vals, q):
        a = np.asarray(vals.get(cls, []))
        return round(float(np.percentile(a, q)) * 1e3, 2) if a.size \
            else None

    ttft: dict = {"i": [], "b": []}
    tpot: dict = {"i": [], "b": []}
    for u, t in timings.items():
        if u[0] not in ttft or u in aborted or not t["token_times"]:
            continue
        ttft[u[0]].append(t["token_times"][0] - t["arrival"])
        tpot[u[0]].extend(np.diff(t["token_times"]).tolist())
    starved_batch = sum(1 for u, cls in enqueued
                        if cls == "batch" and u not in served
                        and u not in aborted)
    return {
        "model": "lm-qos",
        "mode": "continuous-qos",
        "slots": slots,
        "max_backlog": max_backlog,
        "offered": offered,
        "served": len(served),
        "rejected": rejected,
        "retry_after_s": retry_after,
        "aborted_midstream": len(aborted),
        "starved_batch": starved_batch,
        "req_per_sec": round(len(served) / wall, 1),
        "ttft_p50_interactive_ms": pct("i", ttft, 50),
        "ttft_p99_interactive_ms": pct("i", ttft, 99),
        "ttft_p50_batch_ms": pct("b", ttft, 50),
        "ttft_p99_batch_ms": pct("b", ttft, 99),
        "tpot_p50_interactive_ms": pct("i", tpot, 50),
        "tpot_p99_interactive_ms": pct("i", tpot, 99),
        "tpot_p50_batch_ms": pct("b", tpot, 50),
        "tpot_p99_batch_ms": pct("b", tpot, 99),
        "preemptions": cache["preemptions"],
        "max_coresident": cache["peak_resident"],
    }


def run_tiered_scenario(slots: int = 3, n_requests: int = 60) -> dict:
    """Tiered-KV host-store head-to-head (docs/serving_memory.md
    "Tiered KV"): the SAME prefix-heavy diurnal workload served twice
    at equal device KV HBM — once with the host-DRAM spill store OFF
    (an evicted prefix chain is recomputed on its next repeat) and
    once ON (evicted chains spill to host RAM and re-admit) — so the
    delta is recompute bought back by the second tier, never extra
    device memory.

    The workload is the honest worst case for a device-only prefix
    cache: more live shared system prompts than the block pool keeps
    resident, arriving on a diurnal rate curve so repeats cluster at
    the peaks.  Reported per pass: TTFT p50/p99 from the engine's
    always-on telemetry, prefix hit rate, evictions; the ON pass adds
    the kv_spill/kv_readmit counters — ``recompute_tokens_saved``
    (the engine's ``kv_readmit_tokens_saved``) is the claim column
    and is structurally 0 for the OFF pass."""
    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, InputQueue, OutputQueue, ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=128,
                          num_layers=2, num_heads=4,
                          intermediate_size=512, max_position=128)
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, slots))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16, 32, 80))

    rng = np.random.default_rng(31)
    n_prefixes = 6
    PFX = 64                        # 8 full blocks per shared prefix
    prefixes = [rng.integers(1, 8192, PFX).astype(np.int32)
                for _ in range(n_prefixes)]
    # prefix-heavy diurnal arrivals: the rate swings base..peak over
    # one period; both passes replay the SAME (time, prompt) list
    base_rps, peak_rps, period_s = 4.0, 16.0, 6.0
    reqs = []
    t = 0.0
    for _ in range(n_requests):
        rate = base_rps + (peak_rps - base_rps) * (
            1.0 - np.cos(2.0 * np.pi * t / period_s)) / 2.0
        t += float(rng.exponential(1.0 / rate))
        p = prefixes[int(rng.integers(n_prefixes))]
        suffix = rng.integers(
            1, 8192, int(rng.integers(4, 9))).astype(np.int32)
        reqs.append((t, np.concatenate([p, suffix])))

    def one_pass(store_bytes: int) -> dict:
        # 40 usable blocks cannot keep 6 x 8-block prefix chains
        # resident — the pool evicts, which is the tier's feedstock
        cfg = ServingConfig(prompt_col="tokens",
                            continuous_batching=True,
                            engine_slots=slots, engine_ticks=2,
                            engine_paged=True, engine_block_size=8,
                            engine_blocks=41, engine_chunked=True,
                            engine_kv_host_store_bytes=store_bytes)
        serving = ClusterServing(im, cfg, embedded_broker=True).start()
        inq = InputQueue(port=serving.port)
        outq = OutputQueue(port=serving.port)
        try:
            inq.enqueue("warm", tokens=reqs[0][1])
            assert outq.query("warm", timeout=600) is not None
            serving.engine.telemetry.reset_windows()
            t0 = time.perf_counter()
            for i, (at, toks) in enumerate(reqs):
                now = time.perf_counter() - t0
                if at > now:
                    time.sleep(at - now)
                inq.enqueue(f"t{i}", tokens=toks)
            for i in range(len(reqs)):
                assert outq.query(f"t{i}", timeout=600) is not None, \
                    f"t{i} lost"
            cache = serving.engine.cache_metrics()
            stream = _stream_percentiles(serving.engine.telemetry)
            return {
                "ttft_p50_ms": stream.get("ttft_p50_ms"),
                "ttft_p99_ms": stream.get("ttft_p99_ms"),
                "prefix_hit_rate": round(cache["prefix_hit_rate"], 3),
                "evictions": cache["evictions"],
                "kv_spills": cache["kv_spills"],
                "kv_readmits": cache["kv_readmits"],
                "recompute_tokens_saved":
                    cache["kv_readmit_tokens_saved"],
            }
        finally:
            serving.stop()
            inq.close()
            outq.close()

    off = one_pass(0)
    on = one_pass(1 << 20)          # 1 MiB host tier ~= 128 blocks
    return {"model": "lm-tiered", "requests": n_requests,
            "prefix_tokens": PFX, "n_prefixes": n_prefixes,
            "host_store_off": off, "host_store_on": on}


PLAN = [("resnet18", 64, 10, 64),
        ("resnet18-int8mxu", 64, 10, 64),
        ("resnet18-int8", 64, 10, 64),
        # open-loop Poisson mixed workload: clients = rate (req/s),
        # rpc = total requests; convoy vs continuous head-to-head
        ("lm-poisson", 12, 150, 8), ("lm-poisson-cb", 12, 150, 8),
        # system-prompt workload: concatenated-every-time vs prefix
        # cache (the delta = per-request prefill amortised away).  NOTE:
        # at toy scale on a CPU host the cached row can read SLOWER
        # (per-admission dispatch overhead dominates the tiny prefill it
        # saves); the claim is for real prefill costs — judge on TPU.
        ("lm-prefix-full", 12, 120, 8), ("lm-prefix-cached", 12, 120, 8),
        # paged KV cache: same mixed workload on the block pool, the
        # shared-system-prompt workload where the block-level prefix
        # index dedups automatically (hit-rate column), and the
        # equal-HBM co-residency head-to-head (>= 2x claim)
        ("lm-poisson-pg", 12, 150, 8), ("lm-sysprompt-pg", 12, 120, 8),
        ("lm-capacity", 4, 0, 8),
        # paged-attention read path: {gather, fused} x {bf16, int8} at
        # equal KV HBM — tokens/sec/HBM-byte composite column, ~1.9x
        # int8 block-count claim, trace-guard pinned
        ("lm-kernel", 4, 0, 8),
        # chunked-prefill scheduler off-vs-on at equal HBM (>= 2x lower
        # p99 inter-token latency claim); clients = engine slots
        ("lm-chunked", 6, 0, 8),
        # speculative decoding over the paged pool, plain and chunked,
        # at equal TOTAL KV HBM (self-draft upper bound; acceptance
        # rate column); clients = engine slots — FEW by design,
        # speculation's regime is latency-bound low-batch decode
        ("lm-spec-pg", 2, 0, 8), ("lm-spec-ck-pg", 2, 0, 8),
        # QoS front door under heavy mixed traffic: weighted fair-share
        # admission (interactive p99 TTFT < batch under saturation),
        # bounded backlog with 429 + Retry-After, mid-stream aborts
        # freeing blocks live; clients = engine slots, rpc = offered
        ("lm-qos", 4, 80, 8),
        # multi-replica scale-out at fixed TOTAL KV HBM: aggregate
        # req/s + per-class p99 TTFT vs n_replicas in {1,2,4} behind
        # one broker/router, plus the tp=2 paged-vs-arena bitwise
        # parity row; clients = engine slots per replica, rpc = burst
        ("lm-scale", 4, 96, 8),
        # tiered KV memory: host-DRAM spill store off-vs-on at equal
        # device KV HBM on a prefix-heavy diurnal workload — the
        # recompute_tokens_saved column is the claim; clients = engine
        # slots, rpc = total requests
        ("lm-tiered", 3, 60, 8),
        ("lm", 16, 10, 32), ("lm-spec", 16, 10, 32),
        ("lm", 64, 5, 32), ("lm", 1, 20, 32),
        ("mlp", 256, 50, 128), ("mlp", 64, 50, 128),
        ("mlp", 1, 100, 128),
        ("resnet18", 16, 20, 64), ("resnet18", 1, 50, 64)]



def run_scale_scenario(slots: int = 4, n_requests: int = 96) -> dict:
    """Multi-replica scale-out at FIXED total KV HBM: one saturating
    interactive/batch burst served by ``n_replicas`` in {1, 2, 4},
    every fleet splitting the SAME block budget across its replicas —
    so the delta is router + pump parallelism, never extra memory.

    Reported per fleet size: aggregate req/s, per-class p99 TTFT
    (merged from every replica's request stamps), and the router's
    placement counters (multi-replica fleets must show traffic on
    EVERY replica).  A final row serves the same prompts through a
    tp=2 mesh engine paged AND arena and asserts bitwise parity —
    the tensor-parallel paged pool must be a memory layout, never a
    numerics change.  NOTE: on a CPU host the engines share cores, so
    the req/s column is flat-to-down with R; the scale-out claim is
    for real fleets where each replica owns devices — judge the
    ROUTING (spread, per-class p99) here and the throughput on TPU."""
    import queue as _q

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, InputQueue, OutputQueue, ServingConfig)
    from analytics_zoo_tpu.serving.frontdoor import encode_priority

    total_blocks = 96
    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 8192, int(rng.integers(6, 14))).astype(
        np.int32) for _ in range(16)]

    def pct(cls, vals, q):
        a = np.asarray(vals.get(cls, []))
        return round(float(np.percentile(a, q)) * 1e3, 2) if a.size \
            else None

    def serve_fleet(n_replicas: int, roles=None) -> dict:
        im = InferenceModel(batch_buckets=(1, slots))
        im.load_flax_generator(model, variables, max_new_tokens=16,
                               prompt_buckets=(16,))
        cfg = ServingConfig(
            prompt_col="tokens", continuous_batching=True,
            engine_slots=slots, engine_ticks=2, engine_paged=True,
            engine_block_size=8,
            engine_blocks=max(slots * 4, total_blocks // n_replicas),
            n_replicas=n_replicas, replica_roles=roles)
        serving = ClusterServing(im, cfg, embedded_broker=True).start()
        inq = InputQueue(port=serving.port)
        wq = OutputQueue(port=serving.port)
        # warm every replica (round-robin spreads equal-depth warmups)
        for r in range(n_replicas):
            inq.enqueue(f"warm{r}", tokens=prompts[0])
        for r in range(n_replicas):
            assert wq.query(f"warm{r}", timeout=600) is not None
        for e in serving.engines:
            e.telemetry.reset_windows()
            e.record_timings = True

        served: set = set()
        lock = threading.Lock()
        uris_q: "_q.Queue" = _q.Queue()

        def waiter():
            outq = OutputQueue(port=serving.port)
            try:
                while True:
                    u = uris_q.get()
                    if u is None:
                        return
                    r = outq.query(u, timeout=600, poll_interval=0.001)
                    if r is not None:
                        with lock:
                            served.add(u)
            finally:
                outq.close()

        waiters = [threading.Thread(target=waiter) for _ in range(12)]
        for w in waiters:
            w.start()
        t_start = time.perf_counter()
        for i in range(n_requests):
            cls = "interactive" if i % 4 == 0 else "batch"
            uri = f"{cls[0]}{i}"
            inq.enqueue(uri, tokens=prompts[int(rng.integers(16))],
                        priority=encode_priority(cls))
            uris_q.put(uri)
        for _ in waiters:
            uris_q.put(None)
        for w in waiters:
            w.join()
        wall = time.perf_counter() - t_start
        timings = {}
        for e in serving.engines:
            timings.update(e.pop_request_timings())
        router = (serving.router_status() if n_replicas > 1 else None)
        serving.stop()
        inq.close()
        wq.close()
        ttft: dict = {"i": [], "b": []}
        for u, t in timings.items():
            if u[0] in ttft and t["token_times"]:
                ttft[u[0]].append(t["token_times"][0] - t["arrival"])
        row = {
            "n_replicas": n_replicas,
            "blocks_per_replica": cfg.engine_blocks,
            "served": len(served),
            "req_per_sec": round(len(served) / wall, 1),
            "ttft_p99_interactive_ms": pct("i", ttft, 99),
            "ttft_p99_batch_ms": pct("b", ttft, 99),
        }
        if router is not None:
            row["routed"] = router["routed"]
            row["rerouted"] = router["rerouted"]
            if roles is not None:
                # disaggregated fleet: new prompts all land on prefill
                # replicas, so the every-replica-routed spread check
                # becomes a handoff check instead
                row["roles"] = list(roles)
                row["handoffs"] = router["handoffs"]
                assert router["handoffs"] >= 1, \
                    f"disaggregated fleet recorded no handoff: {router}"
            else:
                assert all(c > 0 for c in router["routed"]), \
                    f"replica starved by the router: {router}"
        assert len(served) == n_requests, \
            f"lost requests: {n_requests - len(served)}"
        return row

    fleets = [serve_fleet(r) for r in (1, 2, 4)]
    # role-split fleet at the SAME total HBM as the symmetric 2-replica
    # row: prefill on replica 0, KV-chain handoff, decode on replica 1
    # (docs/serving_memory.md).  Judge per-class p99 TTFT against the
    # symmetric row — prompts never queue behind long decodes — plus
    # the recorded handoff count.
    fleets.append(serve_fleet(2, roles=["prefill", "decode"]))

    # ---- tp=2 parity row (the tentpole claim): for BOTH allocators
    # the mesh is a memory layout, never a numerics change — paged and
    # arena alike must emit bitwise the single-chip engine's tokens.
    # Judged at f32 compute (same weights), like every bitwise bar in
    # tests/: under bf16 a tp-split matmul's different reduction order
    # can legitimately flip a near-tied argmax, which would make the
    # row flaky without saying anything about the layout.
    def tp_parity_row() -> dict:
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel.mesh import make_mesh
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        if len(jax.devices()) < 2:
            return {"skipped": "tp=2 needs >= 2 devices"}
        mesh = make_mesh(axes={"dp": -1, "tp": 2})
        f32_model = model.clone(dtype=jnp.float32)
        row = {"tp": 2}
        for mode in ("arena", "paged"):
            kw = dict(paged=True, block_size=8) if mode == "paged" \
                else {}
            outs, walls = {}, {}
            for name, m in (("tp1", None), ("tp2", mesh)):
                eng = ContinuousEngine(f32_model, variables, mesh=m,
                                       max_new_tokens=8,
                                       max_slots=slots,
                                       prompt_buckets=(16,), **kw)
                got = {}
                t0 = time.perf_counter()
                for i in range(8):
                    eng.submit(f"u{i}", prompts[i % len(prompts)],
                               on_done=lambda u, t:
                               got.__setitem__(u, t))
                eng.drain()
                walls[name] = time.perf_counter() - t0
                outs[name] = got
            match = all(np.array_equal(outs["tp1"][u], outs["tp2"][u])
                        for u in outs["tp1"])
            assert match, f"tp=2 {mode} diverged from single-chip"
            row[f"{mode}_matches_tp1"] = match
            row[f"{mode}_tp2_wall_s"] = round(walls["tp2"], 2)
        return row

    return {
        "model": "lm-scale",
        "mode": "continuous-paged-replicas",
        "slots": slots,
        "total_blocks": total_blocks,
        "offered": n_requests,
        "fleets": fleets,
        "tp2_parity": tp_parity_row(),
    }


def main():
    """Each scenario runs in its OWN subprocess, one at a time.  A TPU
    chip belongs to one process at a time and a parent that has touched
    JAX holds it, so this parent stays off JAX and each ``--one`` child
    takes the chip, measures with fresh HBM, and gives it back on exit;
    a hung scenario also times out alone instead of stalling the run.

    SERVING_BENCH.json is rewritten after every scenario.  A scenario
    that fails, times out or prints no JSON row makes the run exit
    non-zero, naming it, after the rest of the plan has run."""
    import subprocess
    import sys

    out = {"scenarios": []}
    failed = []
    for kind, clients, rpc, bs in PLAN:
        phase = f"{kind}x{clients}"
        cmd = [sys.executable, os.path.abspath(__file__), "--one",
               kind, str(clients), str(rpc), str(bs)]
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=900)
        except subprocess.TimeoutExpired:
            failed.append(phase)
            print(f"scenario {phase} timed out", file=sys.stderr)
            continue
        r = None
        # the result is the LAST valid JSON line: a library/log line
        # that happens to start with '{' earlier in stdout must not
        # be mistaken for the benchmark result
        for line in reversed(p.stdout.splitlines()):
            if line.startswith("{"):
                try:
                    r = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue        # stray '{'-line; keep looking
        if p.returncode != 0 or r is None:
            failed.append(phase)
            print(f"scenario {phase} failed (rc={p.returncode}):\n"
                  f"{p.stderr[-1500:]}", file=sys.stderr)
            continue
        print(json.dumps(r))
        out["scenarios"].append(r)
        with open("SERVING_BENCH.json", "w") as f:
            json.dump(out, f, indent=1)
    if failed:
        sys.exit(f"{len(failed)} scenario(s) failed: {', '.join(failed)}")


def _one():
    """One scenario, in this process, on the chip: no TPU -> non-zero
    exit (a CPU run of a chip scenario is not a row)."""
    import sys

    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"bench_serving scenarios are chip measurements; found "
                 f"backend {jax.default_backend()!r}, not 'tpu'")
    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    kind, clients, rpc, bs = (sys.argv[2], int(sys.argv[3]),
                              int(sys.argv[4]), int(sys.argv[5]))
    if kind == "lm-capacity":
        r = run_capacity_scenario(slots=clients)
    elif kind == "lm-kernel":
        r = run_kernel_scenario(slots=clients)
    elif kind == "lm-chunked":
        r = run_chunked_scenario(slots=clients)
    elif kind == "lm-spec-pg":
        r = run_spec_scenario(chunked=False, slots=clients)
    elif kind == "lm-spec-ck-pg":
        r = run_spec_scenario(chunked=True, slots=clients)
    elif kind == "lm-qos":
        r = run_qos_scenario(slots=clients, n_requests=rpc)
    elif kind == "lm-scale":
        r = run_scale_scenario(slots=clients, n_requests=rpc)
    elif kind == "lm-tiered":
        r = run_tiered_scenario(slots=clients, n_requests=rpc)
    elif kind == "lm-poisson-pg":
        r = run_poisson_scenario(True, rate_per_s=clients,
                                 n_requests=rpc, slots=bs, paged=True)
    elif kind == "lm-sysprompt-pg":
        r = run_poisson_scenario(True, rate_per_s=clients,
                                 n_requests=rpc, slots=bs,
                                 prefix_mode="full", paged=True)
    elif kind.startswith("lm-prefix"):
        r = run_poisson_scenario(True, rate_per_s=clients,
                                 n_requests=rpc, slots=bs,
                                 prefix_mode=kind.split("-")[-1])
    elif kind.startswith("lm-poisson"):
        r = run_poisson_scenario(kind.endswith("-cb"), rate_per_s=clients,
                                 n_requests=rpc, slots=bs)
    else:
        r = run_scenario(kind, clients, requests_per_client=rpc,
                         batch_size=bs)
    d = jax.devices()[0]
    r["device"] = {"platform": d.platform, "kind": d.device_kind,
                   "count": len(jax.devices())}
    print(json.dumps(r))


def _smoke_scrape():
    """serve-smoke observability leg: a live SPECULATIVE paged+chunked
    continuous stack behind ``HttpFrontend`` (all three engine modes
    composed — the draft rides the Python API, ``engine_speculation_k``
    rides config, exercising the YAML override path), real
    wire-protocol traffic, then assert the export surfaces —
    ``GET /healthz``, ``GET /metrics`` (Prometheus text carrying the
    engine's TTFT quantiles, queue/pool/draft-pool gauges, spec
    counters, and the serving job's counters), the legacy
    ``?format=json`` dict, and a ``GET /trace`` body that passes the
    Chrome trace-event schema check."""
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig, validate_chrome_trace)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 4))
    im.load_flax_generator(model, variables, max_new_tokens=8,
                           prompt_buckets=(16,),
                           draft_model=model, draft_variables=variables)
    cfg = ServingConfig(prompt_col="tokens", batch_size=4,
                        continuous_batching=True, engine_slots=4,
                        engine_paged=True, engine_block_size=8,
                        engine_chunked=True, engine_speculation_k=2)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    frontend = HttpFrontend(redis_host=serving.config.redis_host,
                            redis_port=serving.port, http_port=0,
                            serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    rng = np.random.default_rng(3)
    try:
        for i in range(6):
            inq.enqueue(f"sm{i}", tokens=rng.integers(
                1, 8192, 12).astype(np.int32))
        for i in range(6):
            assert outq.query(f"sm{i}", timeout=600) is not None, i

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{frontend.port}{path}",
                    timeout=30) as r:
                return r.headers.get("Content-Type", ""), r.read()

        _, body = get("/healthz")
        h = json.loads(body)
        assert h["status"] == "ok", h
        assert h["accepting"] is True and "backlog" in h, h
        assert h["engine"]["paged"] and h["engine"]["chunked"] \
            and h["engine"]["speculative"], h
        ct, body = get("/metrics")
        assert ct.startswith("text/plain"), ct
        text = body.decode()
        for needle in ('zoo_engine_ttft_seconds{quantile="0.5"}',
                       "zoo_engine_ttft_seconds_count",
                       "zoo_engine_tpot_seconds_count",
                       "zoo_engine_queue_depth",
                       "zoo_engine_free_blocks",
                       "zoo_engine_prefix_hit_rate",
                       "zoo_engine_requests_finished_total 6",
                       "zoo_engine_spec_proposed_total",
                       "zoo_engine_spec_accepted_total",
                       "zoo_engine_spec_accept_len",
                       "zoo_engine_draft_free_blocks",
                       "zoo_serving_requests_total",
                       "zoo_http_request_seconds_count"):
            assert needle in text, f"{needle!r} missing from /metrics"
        _, body = get("/metrics?format=json")
        assert "latency" in json.loads(body), body
        _, body = get("/trace")
        trace = json.loads(body)
        validate_chrome_trace(trace)
        names = {e.get("name") for e in trace["traceEvents"]}
        assert {"queue_wait", "first_token", "request",
                "spec_round"} <= names, names
    finally:
        inq.close()
        outq.close()
        frontend.stop()
        serving.stop()
    print("SCRAPE_OK")


def _smoke_frontdoor():
    """serve-smoke front-door leg (docs/serving_qos.md): the QoS engine
    behind ``HttpFrontend`` with speculation + paged + chunked composed.
    Asserts the three wire-level contracts end to end: (1) an SSE
    stream delivers >= 2 per-token chunks and a ``done`` terminal;
    (2) a client that drops its socket mid-stream frees BOTH the
    target and draft block pools immediately (no waiting on the TTL
    prune) and bumps the disconnect counters; (3) a saturated
    admission queue answers 429 with a finite ``Retry-After``."""
    import http.client as _http
    import socket

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, ServingConfig)
    from analytics_zoo_tpu.serving.resp import RespServer

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 4))
    im.load_flax_generator(model, variables, max_new_tokens=24,
                           prompt_buckets=(16,),
                           draft_model=model, draft_variables=variables)
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=4, engine_ticks=2,
                        engine_paged=True, engine_block_size=8,
                        engine_chunked=True, engine_speculation_k=2,
                        qos_enabled=True)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 8192, 10).astype(np.int32).tolist()
    try:
        # --- SSE streaming e2e: >= 2 token chunks, then done ---
        conn = _http.HTTPConnection("127.0.0.1", fe.port, timeout=600)
        conn.request("POST", "/v1/generate", json.dumps(
            {"tokens": prompt, "stream": True,
             "priority": "interactive", "tenant": "smoke"}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.status
        assert resp.getheader("Content-Type", "").startswith(
            "text/event-stream")
        raw = resp.read().decode()
        conn.close()
        events = [c for c in raw.split("\n\n") if c.strip()
                  and not c.startswith(":")]
        n_tok = sum(1 for c in events if c.startswith("event: token"))
        assert n_tok >= 2, events
        assert any(c.startswith("event: done") for c in events), events

        # --- mid-stream disconnect reclaims both pools ---
        s = socket.create_connection(("127.0.0.1", fe.port), timeout=600)
        body = json.dumps({"tokens": prompt, "stream": True}).encode()
        s.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        buf = b""
        while b"event: token" not in buf:
            chunk = s.recv(4096)
            assert chunk, "stream closed before first token"
            buf += chunk
        # hard close (RST via SO_LINGER 0): the write side must see the
        # broken pipe and cancel into the engine
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     b"\x01\x00\x00\x00\x00\x00\x00\x00")
        s.close()
        deadline = time.monotonic() + 60
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
        assert fe.c_disconnects.value >= 1, fe.c_disconnects.value
    finally:
        fe.stop()
        serving.stop()

    # --- 429 under a saturated queue: broker with no consumer ---
    broker = RespServer(port=0).start()
    fe2 = HttpFrontend(redis_port=broker.port, timeout=5,
                       max_backlog=2).start()
    try:
        saw_429 = False
        for _ in range(4):
            conn = _http.HTTPConnection("127.0.0.1", fe2.port,
                                        timeout=30)
            conn.request("POST", "/v1/generate", json.dumps(
                {"prompt": [1, 2, 3], "stream": True}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status == 429:
                ra = resp.getheader("Retry-After")
                payload = json.loads(resp.read())
                assert ra is not None and 1 <= int(ra) <= 120, ra
                assert payload["retry_after_s"] == int(ra), payload
                saw_429 = True
                conn.close()
                break
            resp.close()
            conn.close()
        assert saw_429, "no 429 from saturated admission queue"
    finally:
        fe2.stop()
        broker.stop()
    print("FRONTDOOR_OK")


def _smoke_flight():
    """serve-smoke flight-recorder overhead leg (docs/debugging.md):
    the recorder is ALWAYS ON in production, so its cost must be noise.
    One paged+chunked engine, alternating reps with the ring attached
    vs detached (``engine.flight = None`` is the disable lever), best
    ticks/sec per mode — asserts the recorder costs < 2% and prints the
    comparison column."""
    import jax

    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import ContinuousEngine

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    eng = ContinuousEngine(model, variables, max_new_tokens=32,
                           max_slots=4, prompt_buckets=(16,),
                           paged=True, block_size=8, chunked=True,
                           tick_token_budget=32)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 8192, 12).astype(np.int32)
               for _ in range(16)]
    recorder = eng.flight
    assert recorder is not None
    seq = iter(range(10 ** 6))

    def rep() -> float:
        t0 = eng.telemetry.c_ticks.value
        start = time.monotonic()
        for p in prompts:
            eng.submit(f"fl{next(seq)}", p)
        eng.drain()
        dur = time.monotonic() - start
        return (eng.telemetry.c_ticks.value - t0) / dur

    rep()                                   # warm the jit caches
    # Best-of-N on a 1-core box is noise-dominated: a single lucky-fast
    # "off" rep fakes several points of overhead.  Accumulate more reps
    # (up to 15) until the bar holds — a REAL recorder cost fails every
    # round, because best-on can never catch best-off then.
    best = {"on": 0.0, "off": 0.0}
    overhead = 1.0
    for _ in range(3):
        for _ in range(5):                  # alternate to decorrelate
            eng.flight = recorder
            best["on"] = max(best["on"], rep())
            eng.flight = None
            best["off"] = max(best["off"], rep())
        overhead = max(0.0, 1.0 - best["on"] / best["off"])
        if overhead < 0.02:
            break
    eng.flight = recorder
    print(f"flight recorder overhead: on={best['on']:.1f} ticks/s "
          f"off={best['off']:.1f} ticks/s overhead={overhead * 100:.2f}%")
    assert overhead < 0.02, (best, overhead)
    assert len(recorder) > 0, "recorder captured no ticks"
    print("FLIGHT_OK")


def _smoke_anomaly():
    """serve-smoke anomaly leg (docs/debugging.md): a live spec+paged+
    chunked ``ClusterServing`` stack given a block pool far too small
    for its concurrency, so every tick fights the allocator — the
    alloc-failure streak must fire the ``AnomalyMonitor``, the bundle
    on disk must hold the triggering ticks in its flight ring, and the
    stdlib debug CLI must render it (including one affected request's
    history by uri) with exit code 0."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, InputQueue, OutputQueue, ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 4))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16,),
                           draft_model=model, draft_variables=variables)
    diag_dir = tempfile.mkdtemp(prefix="zoo-diag-")
    # 10 blocks of 4 at ~6 blocks/request: concurrency > pool, so
    # growth preempts + the allocator fails on consecutive ticks.  The
    # SLO/retrace triggers are pushed out of reach so the one bundle is
    # unambiguously the alloc streak.
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=4, engine_paged=True,
                        engine_block_size=4, engine_blocks=10,
                        engine_chunked=True, engine_speculation_k=2,
                        diag_dir=diag_dir, diag_min_interval_s=0.0,
                        anomaly_alloc_streak=3,
                        anomaly_breach_burst=10 ** 9,
                        anomaly_steady_ticks=10 ** 9)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    rng = np.random.default_rng(5)
    try:
        for i in range(6):
            inq.enqueue(f"an{i}", tokens=rng.integers(
                1, 8192, 12).astype(np.int32))
        # earliest admissions keep forward progress, so the contended
        # pool still finishes every request — after the streak fired
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
    finally:
        inq.close()
        outq.close()
        serving.stop()
    try:
        with open(os.path.join(bundle, "flight.json")) as f:
            flight = json.load(f)
        streaks = [t.get("alloc_fail_streak", 0) for t in flight["ticks"]]
        assert max(streaks) >= 3, streaks
        assert any(t.get("alloc_failures", 0) > 0
                   for t in flight["ticks"]), flight["ticks"][-3:]
        # the debug CLI renders the bundle — and one affected request's
        # history by its uri — from a bare python, rc 0
        proc = subprocess.run(
            [_sys.executable, "-m", "analytics_zoo_tpu.serving.debug",
             bundle], capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "tick timeline" in proc.stdout, proc.stdout
        with open(os.path.join(bundle, "trace.json")) as f:
            trace = json.load(f)
        uris = {e.get("args", {}).get("uri")
                for e in trace.get("traceEvents", [])}
        uri = next(u for u in sorted(u for u in uris if u)
                   if u.startswith("an"))
        proc = subprocess.run(
            [_sys.executable, "-m", "analytics_zoo_tpu.serving.debug",
             bundle, "--uri", uri], capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert uri in proc.stdout, proc.stdout
    finally:
        shutil.rmtree(diag_dir, ignore_errors=True)
    print("ANOMALY_OK")



def _smoke_replicas():
    """serve-smoke scale-out leg (docs/serving_memory.md "Scale-out"):
    a 2-replica fleet behind ONE embedded broker + HTTP frontend.  A
    burst must spread over BOTH replicas — asserted on the
    ``zoo_router_routed_total_r{r}`` counters through a real /metrics
    scrape, not internals — then one pump is killed gracefully and the
    survivor finishes the whole backlog without losing a request."""
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16,))
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, n_replicas=2)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        rng = np.random.default_rng(17)
        n = 12
        for i in range(n):
            inq.enqueue(f"s{i}", tokens=rng.integers(
                1, 8192, int(rng.integers(6, 14))).astype(np.int32))
        # both replicas must take traffic before the kill lands
        deadline = time.time() + 300
        while True:
            routed = serving.router_status()["routed"]
            if all(c > 0 for c in routed):
                break
            assert time.time() < deadline, \
                f"burst never spread over both replicas: {routed}"
            time.sleep(0.02)
        # the spread is visible on the SCRAPE surface, per-replica
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/metrics", timeout=30
        ).read().decode()
        scraped = {}
        for line in body.splitlines():
            if line.startswith("zoo_router_routed_total_r"):
                name, val = line.split()
                scraped[name] = float(val)
        assert scraped.get("zoo_router_routed_total_r0", 0) > 0, scraped
        assert scraped.get("zoo_router_routed_total_r1", 0) > 0, scraped
        assert "zoo_router_replicas_live 2" in body, "liveness gauge"
        # graceful kill mid-backlog: replica 1 finishes what it
        # admitted, its unclaimed queue moves, nothing is lost
        serving.kill_pump(1)
        for i in range(n):
            r = outq.query(f"s{i}", timeout=600)
            assert r is not None, f"s{i} lost in the kill"
        status = serving.router_status()
        assert status["live"] == [True, False], status
        e1 = serving.engines[1]
        assert e1.n_active == 0 and e1.n_waiting == 0, \
            "killed replica exited with admitted work resident"
        print(json.dumps({"leg": "replicas", "served": n,
                          "routed": status["routed"],
                          "rerouted": status["rerouted"]}))
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    print("REPLICAS_OK")


def _smoke_disagg():
    """serve-smoke disaggregation leg (docs/serving_memory.md
    "Disaggregation & elastic pools"): a 2-replica prefill/decode
    fleet behind one embedded broker.  Every greedy request prefills
    on replica 0, hands its KV-block chain off, and decodes on
    replica 1 — asserted on the ``zoo_router_role_handoffs_total``
    counter through a real /metrics scrape, not internals — then the
    PREFILL pump is killed gracefully and the whole backlog still
    completes with zero dropped admitted requests (new prompts fall
    through the role preference to the decode replica)."""
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16,))
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, engine_blocks=48,
                        n_replicas=2,
                        replica_roles=["prefill", "decode"])
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        rng = np.random.default_rng(23)
        n = 8
        for i in range(n):
            inq.enqueue(f"d{i}", tokens=rng.integers(
                1, 8192, int(rng.integers(6, 14))).astype(np.int32))
        for i in range(n):
            r = outq.query(f"d{i}", timeout=600)
            assert r is not None, f"d{i} lost"
        # the handoff is visible on the SCRAPE surface
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/metrics", timeout=30
        ).read().decode()
        scraped = {}
        for line in body.splitlines():
            if line.startswith("zoo_router_role_"):
                name, val = line.split()
                scraped[name] = float(val)
        assert scraped.get("zoo_router_role_handoffs_total", 0) >= 1, \
            scraped
        assert scraped.get(
            "zoo_router_role_prefill_routed_total", 0) >= n, scraped
        # graceful kill of the PREFILL pump mid-backlog: admitted work
        # drains, new prompts fall through to the decode replica
        serving.kill_pump(0)
        for i in range(n, n + 4):
            inq.enqueue(f"d{i}", tokens=rng.integers(
                1, 8192, int(rng.integers(6, 14))).astype(np.int32))
        for i in range(n, n + 4):
            r = outq.query(f"d{i}", timeout=600)
            assert r is not None, f"d{i} lost in the prefill kill"
        status = serving.router_status()
        assert status["live"] == [False, True], status
        e0 = serving.engines[0]
        assert e0.n_active == 0 and e0.n_waiting == 0, \
            "killed prefill replica exited with admitted work resident"
        print(json.dumps({"leg": "disagg", "served": n + 4,
                          "handoffs": status["handoffs"],
                          "routed": status["routed"]}))
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    print("DISAGG_OK")


def _smoke_chaos():
    """chaos-smoke leg (docs/debugging.md "Crash recovery runbook"): a
    3-replica prefill/decode fleet under a deterministic fault
    schedule — one decode pump CRASHES mid-backlog (unplanned death,
    not a graceful kill) and the first KV handoff is DROPPED in
    flight.  Every request must still reach a terminal result, the
    redispatched ones with their ``attempts`` counter recorded, and
    the recovery must be visible on the real /metrics scrape: at
    least one supervisor-declared death, one at-least-once
    redispatch, and one handoff ack-timeout retry."""
    import tempfile
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16,))
    cfg = ServingConfig(
        prompt_col="tokens", continuous_batching=True,
        engine_slots=2, engine_paged=True, engine_block_size=8,
        engine_blocks=48, n_replicas=3,
        replica_roles=["prefill", "decode", "decode"],
        retry_budget=3,
        # generous: a cold adoption jit-compiles its scatter, which
        # must not read as a dropped delivery to the sweep
        handoff_ack_timeout_s=3.0,
        # the injected crash dumps a flight bundle: not into the checkout
        diag_dir=tempfile.mkdtemp(prefix="zoo-diag-"),
        fault_injection=[
            {"kind": "crash_pump", "replica": 1, "at_tick": 2},
            {"kind": "drop_handoff", "at_handoff": 0},
        ])
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        rng = np.random.default_rng(29)
        n = 8
        uris = [f"c{i}" for i in range(n)]
        for u in uris:
            inq.enqueue(u, tokens=rng.integers(
                1, 8192, int(rng.integers(6, 14))).astype(np.int32))
        # every request must go TERMINAL — poll the raw result hashes
        # (not outq.query, which consumes them) so the per-request
        # `attempts` stamp is still observable
        deadline = time.time() + 300
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
                assert time.time() < deadline, \
                    f"{u} stranded — never reached a terminal result"
                time.sleep(0.02)
        errors = 0
        for u in uris:
            try:
                r = outq.query(u, timeout=60)
                assert r is not None, f"{u} vanished after landing"
            except RuntimeError:
                errors += 1   # terminal error IS a terminal outcome
        # the crash redispatch must have bumped at least one request
        # past its first placement
        assert attempts and all(a >= 2 for a in attempts.values()), \
            f"no at-least-once attempts recorded: {attempts}"
        # recovery is visible on the SCRAPE surface, not internals
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/metrics", timeout=30
        ).read().decode()
        scraped = {}
        for line in body.splitlines():
            if line.startswith(("zoo_router_replica_deaths_total",
                                "zoo_router_requests_redispatched_total",
                                "zoo_engine_handoff_")):
                name, val = line.split()
                scraped[name] = float(val)
        assert scraped.get("zoo_router_replica_deaths_total", 0) >= 1, \
            scraped
        assert scraped.get(
            "zoo_router_requests_redispatched_total", 0) >= 1, scraped
        assert scraped.get(
            "zoo_engine_handoff_timeouts_total", 0) >= 1, scraped
        assert scraped.get(
            "zoo_engine_handoff_retries_total", 0) >= 1, scraped
        status = serving.router_status()
        assert status["deaths"] == 1, status
        assert status["death_reasons"][1] == "pump_exception", status
        print(json.dumps({
            "leg": "chaos", "served": n, "errors": errors,
            "attempts": attempts, "deaths": status["deaths"],
            "redispatched": status["redispatched"],
            "handoff_timeouts": status["handoff_timeouts"],
            "handoff_retries": status["handoff_retries"]}))
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    print("CHAOS_OK")


def _smoke_overload():
    """overload-smoke leg (docs/serving_qos.md "Overload & brownout"):
    a live 2-replica fleet under a saturating mixed-class burst with a
    deliberately tiny brownout ladder (queue_high=4, 50ms controller
    interval) plus a handful of batch requests whose deadline already
    passed at enqueue.  Asserts on the real /metrics scrape that the
    ladder ascended AND fully unwound (transitions >= 2, final level
    0 — no stuck-degraded end-state), that the expired requests were
    shed at admission (deadline_shed counter, terminal
    ``deadline_exceeded`` errors on the wire), and that every
    interactive request finished normally through the spike."""
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)
    from analytics_zoo_tpu.serving.frontdoor import (encode_deadline,
                                                     encode_priority)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16,))
    cfg = ServingConfig(
        prompt_col="tokens", continuous_batching=True,
        engine_slots=2, n_replicas=2,
        brownout=True, brownout_queue_high=4,
        brownout_enter_ticks=2, brownout_exit_ticks=2,
        brownout_interval_s=0.05, brownout_standard_max_new=6,
        # generous SLO targets: a cold jit compile's TTFT must not
        # pin windowed goodput at 0 and hold the ladder up — this
        # smoke exercises the queue-depth axis deterministically
        slo_ttft_s_interactive=600.0, slo_ttft_s_standard=600.0,
        slo_ttft_s_batch=600.0, slo_tpot_s_interactive=600.0,
        slo_tpot_s_standard=600.0, slo_tpot_s_batch=600.0,
        slo_queue_wait_s_interactive=600.0,
        slo_queue_wait_s_standard=600.0, slo_queue_wait_s_batch=600.0)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)

    def scrape():
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/metrics", timeout=30
        ).read().decode()
        out = {}
        for line in body.splitlines():
            if line.startswith(("zoo_brownout_",
                                "zoo_engine_deadline_")):
                name, val = line.split()
                out[name] = float(val)
        return out

    try:
        rng = np.random.default_rng(37)
        burst = ([("interactive", f"i{k}") for k in range(6)]
                 + [("standard", f"s{k}") for k in range(6)]
                 + [("batch", f"b{k}") for k in range(6)])
        for cls, u in burst:
            inq.enqueue(u, tokens=rng.integers(
                1, 8192, int(rng.integers(6, 14))).astype(np.int32),
                priority=encode_priority(cls))
        # already expired at enqueue: must shed at ADMISSION — before
        # prefill, before a slot — as terminal deadline_exceeded
        dead = [f"d{k}" for k in range(3)]
        for u in dead:
            inq.enqueue(u, tokens=rng.integers(
                1, 8192, 8).astype(np.int32),
                priority=encode_priority("batch"),
                deadline=encode_deadline(1))
        # every non-expired request must finish normally — including
        # the batch class the ladder held during the spike
        for cls, u in burst:
            r = outq.query(u, timeout=600)
            assert r is not None, f"{u} ({cls}) lost"
        shed_errors = 0
        for u in dead:
            try:
                outq.query(u, timeout=600)
            except RuntimeError as e:
                assert "deadline_exceeded" in str(e), (u, e)
                shed_errors += 1
        assert shed_errors == len(dead), \
            f"only {shed_errors}/{len(dead)} expired requests shed"
        # the ladder must have ascended AND fully unwound — poll the
        # scrape until the controller walks back to level 0
        deadline = time.time() + 120
        while True:
            m = scrape()
            if m.get("zoo_brownout_level", -1) == 0 and \
                    m.get("zoo_brownout_transitions_total", 0) >= 2:
                break
            assert time.time() < deadline, \
                f"ladder never unwound to level 0: {m}"
            time.sleep(0.1)
        assert m.get("zoo_brownout_deadline_shed_total", 0) >= \
            len(dead), m
        print(json.dumps({
            "leg": "overload", "served": len(burst),
            "deadline_shed": len(dead),
            "transitions": m["zoo_brownout_transitions_total"],
            "final_level": m["zoo_brownout_level"],
            "sheds": {k: v for k, v in sorted(m.items())
                      if k.startswith("zoo_brownout_shed_total")}}))
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    print("OVERLOAD_OK")


def _smoke_tiered():
    """serve-smoke tiered-KV leg (docs/serving_memory.md "Tiered KV"):
    a paged engine with a deliberately tiny block pool plus a host-DRAM
    spill store.  A first prompt's KV chain is cached, churned out of
    the pool by other traffic (eviction -> spill to host RAM), then the
    SAME prompt repeats and must re-admit its chain from the store —
    asserted on the ``zoo_engine_kv_readmit_chains_total`` counter
    through a real /metrics scrape, not internals."""
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)

    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16, 32))
    # 12 usable blocks: one resident request needs up to 5, so cached
    # chains are evicted (and spilled) within a few churn prompts
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, engine_blocks=13,
                        engine_kv_host_store_bytes=1 << 20)
    serving = ClusterServing(im, cfg, embedded_broker=True).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        rng = np.random.default_rng(29)
        # the repeat prompt: 17 tokens = 2 publishable full blocks
        repeat = rng.integers(1, 8192, 17).astype(np.int32)
        inq.enqueue("a0", tokens=repeat)
        assert outq.query("a0", timeout=600) is not None, "a0 lost"
        # churn: distinct prompts roll the tiny pool over so a0's
        # cached chain is evicted and offered to the host store
        for i in range(4):
            inq.enqueue(f"c{i}", tokens=rng.integers(
                1, 8192, 24).astype(np.int32))
            assert outq.query(f"c{i}", timeout=600) is not None, \
                f"c{i} lost"
        # the repeat must re-admit at least one spilled block
        inq.enqueue("a1", tokens=repeat)
        assert outq.query("a1", timeout=600) is not None, "a1 lost"
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/metrics", timeout=30
        ).read().decode()
        scraped = {}
        for line in body.splitlines():
            if line.startswith("zoo_engine_kv_"):
                name, val = line.split()
                scraped[name] = float(val)
        assert scraped.get("zoo_engine_kv_spill_chains_total", 0) >= 1, \
            scraped
        assert scraped.get(
            "zoo_engine_kv_readmit_chains_total", 0) >= 1, scraped
        assert scraped.get(
            "zoo_engine_kv_readmit_tokens_saved_total", 0) >= 8, scraped
        print(json.dumps({"leg": "tiered", "served": 6,
                          "kv": {k: v for k, v in sorted(
                              scraped.items())}}))
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    print("TIERED_OK")


def _fused_tp_child():
    """Child half of ``_smoke_fused_tp`` (run as ``--fused-tp`` in its
    own subprocess so the parent's JAX device topology — 1 CPU device
    under plain ``JAX_PLATFORMS=cpu`` — does not decide whether a tp=2
    mesh can exist).  Serves a live tp=2 PAGED fleet with the fused
    Pallas read kernel on an int8 pool: the exact configuration the
    pre-PR engine rejected with an eager ValueError.  Asserts through
    the public surfaces only — the /metrics scrape for the
    ``zoo_engine_kv_*`` gauges and ``capacity_report()`` for the
    billing: ``tp == 2`` and ``arena_bytes_per_chip * 2 ==
    arena_bytes`` (kv-heads-sharded pool halves per-chip HBM), with
    the fused kernel + int8 dtype recorded on the same report."""
    import urllib.request

    import jax

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.parallel.mesh import make_mesh
    from analytics_zoo_tpu.serving import (
        ClusterServing, HttpFrontend, InputQueue, OutputQueue,
        ServingConfig)

    if len(jax.devices()) < 2:
        raise SystemExit("fused-tp leg: tp=2 needs >= 2 devices, found "
                         f"{len(jax.devices())}")
    mesh = make_mesh(axes={"dp": -1, "tp": 2})
    # 4 kv heads / tp=2: each chip owns 2 contiguous kv heads and the
    # query heads folded onto them — the per-chip fused grid
    model = TransformerLM(vocab_size=8192, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=512,
                          max_position=64)
    variables = model.init(jax.random.key(0), np.zeros((1, 16), np.int32))
    im = InferenceModel(batch_buckets=(1, 2))
    im.load_flax_generator(model, variables, max_new_tokens=12,
                           prompt_buckets=(16, 32))
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        engine_slots=2, engine_paged=True,
                        engine_block_size=8, engine_blocks=25,
                        engine_kernel="fused", engine_kv_dtype="int8")
    serving = ClusterServing(im, cfg, embedded_broker=True,
                             engine_mesh=mesh).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=600,
                      serving=serving).start()
    inq = InputQueue(port=serving.port)
    outq = OutputQueue(port=serving.port)
    try:
        rng = np.random.default_rng(41)
        for i in range(4):
            inq.enqueue(f"f{i}", tokens=rng.integers(
                1, 8192, 10 + 3 * i).astype(np.int32))
        for i in range(4):
            assert outq.query(f"f{i}", timeout=600) is not None, \
                f"f{i} lost"
        rep = serving.engines[0].capacity_report()
        assert rep["kernel"] == "fused", rep
        assert rep["kv_dtype"] == "int8", rep
        assert rep["tp"] == 2, rep
        # the sharded billing claim: tp splits the pool over chips
        assert rep["arena_bytes_per_chip"] * 2 == rep["arena_bytes"], \
            rep
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/metrics", timeout=30
        ).read().decode()
        scraped = {}
        for line in body.splitlines():
            if line.startswith("zoo_engine_kv_"):
                name, val = line.split()
                scraped[name] = float(val)
        # the pool gauge must agree with what capacity_report bills
        assert scraped.get("zoo_engine_kv_pool_bytes") == \
            rep["arena_bytes"], (scraped, rep["arena_bytes"])
        assert scraped.get("zoo_engine_kv_bytes_per_token", 0) > 0, \
            scraped
        print(json.dumps({"leg": "fused-tp", "served": 4,
                          "tp": rep["tp"],
                          "arena_bytes": rep["arena_bytes"],
                          "arena_bytes_per_chip":
                              rep["arena_bytes_per_chip"],
                          "kv": {k: v for k, v in sorted(
                              scraped.items())}}))
    finally:
        fe.stop()
        serving.stop()
        inq.close()
        outq.close()
    print("FUSED_TP_OK")


def _smoke_fused_tp():
    """serve-smoke fused-under-tp leg (ISSUE 18 tentpole, live): runs
    ``_fused_tp_child`` in a subprocess whose XLA_FLAGS force 8 host
    devices, because `make serve-smoke` runs the parent under plain
    ``JAX_PLATFORMS=cpu`` (1 device) and a JAX process cannot change
    its device count after backend init.  CPU-only by construction:
    this parent has touched JAX, so on a chip the child could never
    have the device — the child is pinned to the CPU platform (the
    chip's tp=2 fused proof is ``chip_smoke.py --chips 4``)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--fused-tp"],
        timeout=900, capture_output=True, text=True, env=env)
    sys.stdout.write(p.stdout)
    if p.returncode != 0 or "FUSED_TP_OK" not in p.stdout:
        raise AssertionError(
            f"fused-tp leg failed (rc={p.returncode}):\n"
            f"{p.stdout[-2000:]}\n{p.stderr[-2000:]}")


def _smoke():
    """``python bench_serving.py --smoke``: the `make serve-smoke` e2e
    leg — 20 requests through the full wire protocol on the PAGED
    engine behind the CHUNKED token-budget scheduler with a shared
    system prompt, small enough for the CPU test box.  Asserts the
    paged + chunked plumbing end to end: every request served, the
    prefix cache actually hit, cache columns present, the engine's
    always-on TTFT/TPOT histograms flowing — then the observability
    surfaces (/healthz, Prometheus /metrics, /trace) on a live stack
    via ``_smoke_scrape``, the front-door wire contracts via
    ``_smoke_frontdoor``, the flight-recorder overhead bound via
    ``_smoke_flight``, the anomaly-to-bundle-to-CLI path via
    ``_smoke_anomaly``, the 2-replica router spread + graceful
    pump-kill drain via ``_smoke_replicas``, the prefill/decode
    KV-handoff fleet via ``_smoke_disagg``, the host-DRAM spill-store
    eviction/re-admission loop via ``_smoke_tiered``, the fused
    Pallas kernel reading a tp=2-sharded int8 pool via
    ``_smoke_fused_tp``, the crash-tolerance chaos leg (pump
    crash + dropped handoff under fault injection) via
    ``_smoke_chaos`` (also standalone: ``make chaos-smoke``), and the
    brownout-ladder overload leg (saturating mixed-class burst with
    expired deadlines sheds at admission, ladder ascends and fully
    unwinds) via ``_smoke_overload`` (also standalone:
    ``make overload-smoke``).

    A CPU dry run, explicitly: the legs time nothing, and the fused-tp
    leg spawns a child that needs the device this process holds."""
    import jax

    if jax.default_backend() != "cpu":
        raise SystemExit(
            "bench_serving.py --smoke is a CPU dry run of the serving "
            "wire protocol (run it with JAX_PLATFORMS=cpu, as `make "
            "serve-smoke` does); the chip's smoke is chip_smoke.py")
    r = run_poisson_scenario(True, rate_per_s=20.0, n_requests=20,
                             slots=4, prefix_mode="full", paged=True,
                             chunked=True)
    print(json.dumps(r))
    assert r["requests"] == 20, r
    assert r["model"].endswith("-ck"), r
    assert r["prefix_hit_rate"] > 0.0, r
    assert r["max_coresident"] >= 1, r
    assert r["ttft_p50_ms"] is not None, r
    assert r["tpot_p50_ms"] is not None, r
    _smoke_scrape()
    _smoke_frontdoor()
    _smoke_flight()
    _smoke_anomaly()
    _smoke_replicas()
    _smoke_disagg()
    _smoke_tiered()
    _smoke_fused_tp()
    _smoke_chaos()
    _smoke_overload()
    print("SMOKE_OK")


if __name__ == "__main__":
    import sys

    if "--chaos-smoke" in sys.argv:
        _smoke_chaos()
    elif "--overload-smoke" in sys.argv:
        _smoke_overload()
    elif "--smoke" in sys.argv:
        _smoke()
    elif "--fused-tp" in sys.argv:
        _fused_tp_child()
    elif "--one" in sys.argv:
        _one()
    else:
        main()
