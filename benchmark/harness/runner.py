"""One run of one cell: set-up, ramp, window, drain, metrics, ``correct``.

``Runner.setup`` builds the served stack and warms it; ``Runner.window``
drives one measured window through the load generator child and returns
everything the metrics read; ``Runner.check`` frees the program and runs the
plain reference.  run.py makes one window; sweep.py makes several on one
set-up.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time

from . import cells
from . import metrics as M
from . import schedule

HERE = os.path.dirname(os.path.abspath(__file__))


CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")


def process_env(tiny: bool) -> None:
    """What run.py and sweep.py set before JAX is imported.

    The compile cache is the benchmark's own, at one fixed path inside the
    checkout and with no size cap: a cell's programs are some hundreds of
    MiB, and a capped cache evicts them before the next run reads them (a
    machine's 192 MiB cap did: PERF.md).  JAX reads both variables when it
    is imported, and the program's own rule (common/compile_cache.py) keeps
    to a directory named in the environment."""
    os.environ.setdefault("ZOO_TPU_LOGLEVEL", "WARNING")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def say(msg: str, tag: str = "") -> None:
    print(f"{tag}{msg}", file=sys.stderr, flush=True)


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys laid on top, one level into dicts."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**base[k], **v} if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


class Runner:
    def __init__(self, cell, *, seed: int, tiny: bool, t_proc0: float):
        self.cell, self.seed, self.tiny = cell, int(seed), tiny
        self.t_proc0 = t_proc0
        self.tag = "[CPU dry run] " if tiny else ""
        self.cfg, self.traffic, self.workload = (
            cell.config, cell.traffic, cell.workload)
        if tiny:
            self.cfg = overlay(self.cfg, self.cfg["tiny"])
            self.traffic = overlay(self.traffic, self.traffic["tiny"])
            self.workload = overlay(self.workload, self.workload["tiny"])
        self.tmp = tempfile.mkdtemp(prefix="zoo_bench_")
        self.stack = None

    def say(self, msg: str) -> None:
        say(msg, self.tag)

    # ---- set-up --------------------------------------------------------

    def setup(self) -> None:
        import jax

        from . import server

        platform = "cpu" if self.tiny else "tpu"
        if jax.default_backend() != platform:
            raise SystemExit(
                f"benchmark: the default JAX backend is "
                f"{jax.default_backend()!r}, not {platform!r}: no "
                f"accelerator, no result (run.py --tiny is the CPU dry "
                f"run)")
        devs = jax.devices()
        if len(devs) < self.cell.chips:
            raise SystemExit(
                f"benchmark: the cell asks for {self.cell.chips} chip(s), "
                f"JAX found {len(devs)}")
        self.devices = devs
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        # every program of the engine comes from the cache after the first
        # run of a cell in a checkout, the sub-second ones too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cache = None
        if not self.tiny:
            cache = jax.config.jax_compilation_cache_dir
            if cache != CACHE_DIR:
                raise SystemExit(
                    f"benchmark: JAX keeps its compile cache in {cache!r}, "
                    f"not in {CACHE_DIR!r}: call process_env() before "
                    f"anything imports jax")
            os.makedirs(cache, exist_ok=True)
            jax.config.update("jax_compilation_cache_max_size", -1)
        self.say(f"device {self.device}, compile cache "
                 f"{cache or 'off (CPU)'}")
        self.compiles = server.CompileCounter()
        t0 = time.monotonic()
        self.stack = server.Stack(self.cfg, self.seed,
                                  os.path.join(self.tmp, "diagnostics"))
        t1 = time.monotonic()
        n = self.stack.warm(
            self.workload["warm_lengths"],
            (self.cfg.get("warm") or {}).get("max_chunk_rows"))
        self.say(f"set-up: weights + stack {t1 - t0:.1f} s, warm-up "
                 f"{time.monotonic() - t1:.1f} s ({self.stack.grid_s:.1f} s "
                 f"in the {n} chunk-grid programs, the rest in warm-up "
                 f"requests); {self.compiles.n} compiled "
                 f"({self.compiles.seconds:.1f} s), {self.compiles.loaded} "
                 f"from the cache")

    # ---- one window ----------------------------------------------------

    def window(self, seconds: float, *, trace: bool,
               rate_rps: float | None = None) -> dict:
        from analytics_zoo_tpu.lint import trace_guard

        tr = self.traffic
        rate = rate_rps or self.workload.get("rate_rps")
        rows = schedule.make(
            tr, seed=self.seed, seconds=seconds, rate_rps=rate,
            vocab=cells.family(self.cfg).leaves.vocab(self.cfg))
        sched = os.path.join(self.tmp, "schedule.jsonl")
        recs = os.path.join(self.tmp, "records.jsonl")
        schedule.write(rows, sched)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             "--port", str(self.stack.port), "--schedule", sched,
             "--records", recs, "--loop", tr["loop"],
             "--timeout", str(tr["drain_limit_s"])],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env)
        try:
            if child.stdout.readline().strip() != "READY":
                raise RuntimeError("load generator did not come up")
            eng = self.stack.engine
            t_start = time.monotonic() + 0.3
            t_open = t_start + float(tr["ramp_s"])
            t_close = t_open + seconds
            child.stdin.write(f"GO {t_start!r} {t_close!r}\n")
            child.stdin.flush()
            time.sleep(max(0.0, t_open - time.monotonic()))
            c0 = (self.compiles.n, self.compiles.loaded)
            guard = trace_guard(eng, name="benchmark-window").__enter__()
            marks, trace_dir = {}, None
            if trace:
                marks, trace_dir = self._trace(t_open, t_close)
            time.sleep(max(0.0, t_close - time.monotonic()))
            compiled = {"compiled": self.compiles.n - c0[0],
                        "loaded": self.compiles.loaded - c0[1],
                        "programs": guard.counts()}
            out, _ = child.communicate(
                timeout=2 * tr["drain_limit_s"] + 30)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        done = [ln for ln in out.splitlines() if ln.startswith("DONE ")]
        if child.returncode != 0 or not done:
            raise RuntimeError(f"load generator failed: rc "
                               f"{child.returncode}, said {out[-300:]!r}")
        with open(recs) as f:
            records = [json.loads(ln) for ln in f]
        by_id = {r["id"]: r for r in rows}
        for r in records:
            r["prompt"] = by_id[r["id"]]["tokens"]
        w = {"t_open": t_open, "t_close": t_close, "seconds": seconds,
             "rate_rps": rate, "records": records,
             "in_window": M.in_window(records, t_open, t_close),
             "compiled_in_window": compiled,
             "generator": json.loads(done[0][5:]),
             "setup_s": t_open - self.t_proc0, "marks": marks,
             "trace_dir": trace_dir}
        w["ticks"] = [t for t in eng.flight.snapshot()
                      if t_open <= t["ts"] < t_close]
        w["ring_full"] = len(eng.flight) >= eng.flight.capacity
        return w

    def _trace(self, t_open: float, t_close: float):
        """Profile a few seconds inside the window; mark both ends so the
        reduction can cut the trace to them and tie it to the host clock."""
        import jax

        spec = self.workload["trace"]
        start = t_open + float(spec["start_s"])
        stop = min(start + float(spec["seconds"]), t_close - 0.5)
        trace_dir = os.path.join(self.tmp, "trace")
        time.sleep(max(0.0, start - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        marks = {}
        with jax.profiler.TraceAnnotation("bench_mark_open"):
            marks["bench_mark_open"] = time.monotonic()
        time.sleep(max(0.0, stop - time.monotonic()))
        with jax.profiler.TraceAnnotation("bench_mark_close"):
            marks["bench_mark_close"] = time.monotonic()
        jax.profiler.stop_trace()
        return marks, trace_dir

    # ---- after the window ----------------------------------------------

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices[:self.cell.chips]]
        return int(max(peaks))

    def stop(self) -> None:
        if self.stack is not None:
            self.stack.stop()
            self.stack.free()
            self.stack = None

    def check(self, w: dict, *, control: bool = False) -> dict:
        """``correct``: what the window's own requests were answered with,
        against the plain reference.  The numbers compared, each with its
        limit, are returned under ``compared``.  ``control`` puts the fp8
        reference's tokens in the served place, at the same prompts and
        positions: the comparison has to fail them."""
        from . import reference

        spec = self.workload["correct"]
        limits = spec["limits"]
        wrecs = w["in_window"]
        unanswered = [r for r in wrecs if r.get("done") is None
                      or r.get("status") != 200 or r.get("error")]
        wrong_len = [r for r in wrecs if r not in unanswered
                     and len(r["tokens"]) != r["max_new"]]
        good = sorted((r for r in wrecs if not M.request_failed(r)),
                      key=lambda r: r["id"])
        compared = {
            "unanswered": {"value": len(unanswered), "limit": 0},
            "wrong_length": {"value": len(wrong_len), "limit": 0}}
        t0 = time.monotonic()
        if good:
            longest = max(good, key=lambda r: len(r["prompt"])
                          + len(r["tokens"]))
            rest = [r for r in good if r is not longest]
            random.Random(self.seed).shuffle(rest)
            sample = [longest] + rest[:spec["sample_requests"] - 1]
            tr = self.traffic
            t_pad = -(-(tr["prompt_len"]["max"] + tr["max_new"]["max"])
                      // 128) * 128
            gaps = reference.served_gaps(
                self.cfg, self.seed,
                [(r["prompt"], r["tokens"]) for r in sample],
                t_pad=t_pad, p_pad=tr["max_new"]["max"], control=control)
            # with ``control`` the fp8 reference stands in the program's
            # place: the tokens IT puts first are judged as the served
            # ones, and the program's own reading goes to ``info``
            judged = "control" if control else "served"
            compared["served_gap_max"] = {
                "value": float(max(g[judged].max() for g in gaps)),
                "limit": limits["served_gap_max"]}
            n_tok = sum(len(g["served"]) for g in gaps)
            info = {"sampled_requests": len(sample),
                    "served_tokens_compared": int(n_tok),
                    "longest_tokens": len(longest["prompt"])
                    + len(longest["tokens"]),
                    "served_is_reference_argmax_share": float(sum(
                        (g["served"] == 0).sum() for g in gaps) / n_tok)}
            if control:
                info["judged"] = ("the fp8 control's tokens in the served "
                                  "place: correct has to read false")
                info["program_served_gap_max"] = float(
                    max(g["served"].max() for g in gaps))
                info["program_correct"] = bool(
                    not unanswered and not wrong_len
                    and info["program_served_gap_max"]
                    <= limits["served_gap_max"])
                info["control_gap_per_request"] = [
                    float(g["control"].max()) for g in gaps]
                info["served_gap_per_request"] = [
                    float(g["served"].max()) for g in gaps]
        else:
            compared["served_gap_max"] = {
                "value": None, "limit": limits["served_gap_max"]}
            info = {"sampled_requests": 0}
        info["reference_s"] = time.monotonic() - t0
        ok = all(c["value"] is not None and c["value"] <= c["limit"]
                 for c in compared.values())
        return {"correct": bool(ok), "compared": compared, "info": info}

    def cleanup(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)


def tick_summary(w: dict) -> dict:
    """What the window's ticks looked like (an earlier line of every run):
    durations by kind, rows, chunk rows per tick, preemptions."""
    from collections import Counter

    ticks = w["ticks"]
    if not ticks:
        return {"ticks": 0}
    p = lambda xs, q: round(M.percentile(xs, q), 3) if xs else None
    dec = [t["dur_ms"] for t in ticks if not t.get("chunks")]
    chk = [t["dur_ms"] for t in ticks if t.get("chunks")]
    span = ticks[-1]["ts"] - ticks[0]["ts"] + ticks[-1]["dur_ms"] / 1e3
    return {"ticks": len(ticks),
            "decode_only_ms": {"n": len(dec), "p50": p(dec, 50),
                               "p95": p(dec, 95)},
            "chunk_ms": {"n": len(chk), "p50": p(chk, 50),
                         "p95": p(chk, 95)},
            "in_step_share": round(sum(t["dur_ms"] for t in ticks) / 1e3
                                   / span, 4),
            "mean_decode_rows": round(sum(t["decode_rows"] for t in ticks)
                                      / len(ticks), 2),
            "chunk_rows_per_tick": dict(sorted(Counter(
                t.get("chunks", 0) for t in ticks).items())),
            "max_queue_depth": max(t["queue_depth"] for t in ticks),
            "preempted": sum(t.get("preempted", 0) for t in ticks),
            "peak_used_blocks": max(t.get("used_blocks", 0)
                                    for t in ticks),
            "n_blocks": ticks[-1].get("n_blocks")}


def end_to_end(w: dict, traffic: dict) -> dict:
    """Every end-to-end metric the harness knows, by name; a cell reports
    the ones BENCHMARK.json lists it under."""
    wrecs = w["in_window"]
    out = {"setup_s": (w["setup_s"], "s")}
    if wrecs:
        ttft = M.ttft_ms(wrecs, traffic["drain_limit_s"])
        out["ttft_mean_ms"] = (sum(ttft) / len(ttft), "ms")
        out["ttft_p95_ms"] = (M.percentile(ttft, 95), "ms")
        gaps = M.inter_token_ms(wrecs)
        if gaps:
            out["itl_p95_ms"] = (M.percentile(gaps, 95), "ms")
    out["output_tokens_per_s"] = (M.tokens_in_window(
        w["records"], w["t_open"], w["t_close"]) / w["seconds"],
        "tokens/s")
    return out
