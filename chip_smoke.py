#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process (a TPU chip belongs to one process at a time) drives the two
main paths through the entry points a user calls, at the full width of
models the repo supports, with seeded random weights:

  serve   InferenceModel.load_flax_generator -> ClusterServing ->
          HttpFrontend -> POST /v1/generate (one streamed), /healthz,
          /metrics, on a TransformerLM with Qwen2.5-1.5B-Instruct's
          knobs (all 28 layers), three times: the default engine (arena,
          monolithic, gather), then paged + chunked + fused kernel with
          hbm_fraction sizing, KV in bf16 and again in int8.
  kernel  every Pallas kernel COMPILED (Mosaic custom call in the
          lowered text) and compared on the chip with the repo's own
          reference at shapes real models have.
  train   init_orca_context -> Estimator.from_flax -> fit: BERT-base
          (batch 64 x seq 128) and the 111M LM at seq 2048 (flash fwd +
          bwd inside the pjit step), one Orbax checkpoint save + restore.

``--chips 4`` runs the multi-chip legs instead (four one-chip replicas,
a tp=2 engine with the fused kernel under shard_map, BERT-base on
dp=2 x tp=2) and needs >= 4 TPU devices — it does not shrink.

Any leg failing -> non-zero exit naming the leg, and no result line.
Without a TPU the script exits non-zero before running anything.  A pass
ends with two JSON lines on stdout: the summary (mode, versions, per-leg
results and compile accounting, ``"claim": null``; also written to
``chiprun_out/chip_smoke/summary.json``), then, as the LAST line, the
result line with exactly these keys and nothing else:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
No rate is printed in any mode: this script measures nothing but set-up
facts (compile seconds, cache hits).

``--tiny`` is the CPU dry run of the same code at toy sizes (kernels in
Pallas interpret mode), so chip time is not spent on typos; every line
it prints says so.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import http.client
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import traceback

# crash bundles and summary.json land here (chiprun_out/ is what the chip
# tool copies back, and is git-ignored)
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "chip_smoke")
HBM_FRACTION = 0.1   # of the chip's bytes_limit, for every paged pool
TAG = ""             # "[CPU DRY RUN] " under --tiny


def say(msg: str) -> None:
    print(f"{TAG}{msg}", flush=True)


# ---------------------------------------------------------------------------
# compile accounting: seconds spent in backend compiles and persistent-
# cache hits, per leg (jax.monitoring is what JAX itself logs through)
# ---------------------------------------------------------------------------

class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.hits = 0
        self.requests = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self):
        return (self.compile_s, self.hits, self.requests)

    def since(self, snap) -> dict:
        return {"compile_s": round(self.compile_s - snap[0], 1),
                "cache_hits": self.hits - snap[1],
                "cache_requests": self.requests - snap[2]}


# ---------------------------------------------------------------------------
# models (exactly the knobs the importers set)
# ---------------------------------------------------------------------------

def qwen_lm(tiny: bool):
    """TransformerLM as net/hf_net.py:_from_llama_family builds it for
    Qwen2.5-1.5B-Instruct (config.json: vocab 151936, hidden 1536,
    28 layers, 12 heads / 2 KV heads, intermediate 8960, rope_theta 1e6,
    rms_norm_eps 1e-6, tied embeddings, 32768 positions), in bf16."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.models import TransformerLM

    family = dict(dropout=0.0, dtype=jnp.bfloat16, pos_encoding="rope",
                  rope_base=1e6, norm="rmsnorm", ln_eps=1e-6,
                  mlp="swiglu", use_bias=False, qkv_bias=True,
                  tied_head=True)
    if tiny:
        return TransformerLM(vocab_size=512, hidden_size=64, num_layers=2,
                             num_heads=4, num_kv_heads=2,
                             intermediate_size=128, max_position=512,
                             **family)
    return TransformerLM(vocab_size=151936, hidden_size=1536,
                         num_layers=28, num_heads=12, num_kv_heads=2,
                         intermediate_size=8960, max_position=32768,
                         **family)


def seeded_bf16_variables(model, seed: int = 0):
    """The model's own initialisers from a seed, cast to bf16 inside the
    jit so the f32 tree never sits in HBM beside its bf16 copy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def init(key):
        v = model.init(key, np.zeros((1, 8), np.int32))
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), v)

    return jax.jit(init)(jax.random.key(seed))


def on_platform(tree, platform: str) -> bool:
    import jax

    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) if isinstance(leaf, jax.Array)
               for d in leaf.devices())


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------

def _post_generate(port: int, body: dict, timeout: float = 900.0):
    """POST /v1/generate.  Returns the token list (JSON) or, for
    ``stream: true``, (n_token_events, saw_done)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"/v1/generate -> {resp.status}: {raw[:300]}")
        if not body.get("stream"):
            return json.loads(raw)["tokens"]
        if not resp.getheader("Content-Type", "").startswith(
                "text/event-stream"):
            raise RuntimeError("stream: true did not answer SSE")
        events = [c for c in raw.split("\n\n")
                  if c.strip() and not c.startswith(":")]
        if any(c.startswith("event: error") for c in events):
            raise RuntimeError(f"SSE error event: {events}")
        n_tok = sum(1 for c in events if c.startswith("event: token"))
        return n_tok, any(c.startswith("event: done") for c in events)
    finally:
        conn.close()


def _get(port: int, path: str) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"GET {path} -> {resp.status}")
        return body
    finally:
        conn.close()


@contextlib.contextmanager
def _serving(im, cfg_kwargs, engine_mesh=None):
    """One ClusterServing (embedded broker) + HttpFrontend, stopped on
    exit; whatever an earlier stack left in reference cycles (its KV
    pool) is collected before this one allocates."""
    from analytics_zoo_tpu.serving import (ClusterServing, HttpFrontend,
                                           ServingConfig)

    gc.collect()
    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        diag_dir=os.path.join(OUT_DIR, "diagnostics"),
                        **cfg_kwargs)
    serving = ClusterServing(im, cfg, embedded_broker=True,
                             engine_mesh=engine_mesh).start()
    fe = HttpFrontend(redis_port=serving.port, timeout=900,
                      serving=serving).start()
    try:
        yield serving, fe
    finally:
        fe.stop()
        serving.stop()


def _serve_once(name, im, cfg_kwargs, prompts, fresh_prompts, max_new,
                platform, engine_mesh=None):
    """Start one serving stack, answer ``prompts`` (the compile pass),
    then ``fresh_prompts`` — same lengths, new tokens — under
    trace_guard (zero compiles), then /healthz and /metrics."""
    from analytics_zoo_tpu.lint import trace_guard

    with _serving(im, cfg_kwargs, engine_mesh) as (serving, fe):
        outs = []
        for i, p in enumerate(prompts):
            if i == 1:      # one request streams
                n_tok, done = _post_generate(
                    fe.port, {"tokens": p, "stream": True})
                if n_tok < 2 or not done:
                    raise AssertionError(
                        f"{name}: SSE gave {n_tok} token events, "
                        f"done={done}")
                outs.append(None)
                continue
            toks = _post_generate(fe.port, {"tokens": p})
            if len(toks) != max_new:
                raise AssertionError(
                    f"{name}: request {i} returned {len(toks)} tokens, "
                    f"expected {max_new}")
            outs.append(toks)
        # second pass: same prompt SHAPES, nothing may compile
        with trace_guard(*serving.engines, name=f"chip-smoke-{name}"):
            for p in fresh_prompts:
                toks = _post_generate(fe.port, {"tokens": p})
                if len(toks) != max_new:
                    raise AssertionError(f"{name}: steady pass short")
        health = json.loads(_get(fe.port, "/healthz"))
        if health.get("status") != "ok" or not health.get("accepting"):
            raise AssertionError(f"{name}: /healthz {health}")
        metrics = _get(fe.port, "/metrics")
        if "zoo_engine_" not in metrics:
            raise AssertionError(f"{name}: /metrics has no engine family")
        step_memory = _step_memory(name, serving.engines[0], platform)
        placed = []
        for eng in serving.engines:
            kv = (eng._pk, eng._pv) if eng.paged else (eng._ck, eng._cv)
            if not (on_platform(eng._variables, platform)
                    and on_platform(kv, platform)):
                raise AssertionError(
                    f"{name}: engine weights / KV not on {platform}")
            placed.append(sorted(d.id for d in eng._devices))
        report = serving.engines[0].capacity_report()
        return {"outs": outs, "engine_devices": placed,
                "kv_bytes": int(report["arena_bytes"]),
                "n_blocks": report.get("n_blocks"),
                "step_memory": step_memory,
                "requests": len(prompts) + len(fresh_prompts)}


def _step_memory(name, eng, platform) -> dict:
    """The paged step's in-place contract, read off the programs as
    compiled for this device: the decode program and one chunk program
    hold temporaries far under the pool they update (they held 1.3-3 x
    the pool while the step sliced layers out of it).  Fails at half a
    pool on the chip; the CPU dry run only prints (its compiler widens
    bf16 around a scatter and interprets the kernel, temporaries and
    all: tests/test_paged_inplace.py holds the contract there, in
    float32)."""
    if not (eng.paged and eng.chunked):
        return {}
    out = {}
    for program in ("decode", "chunk"):
        mem = eng.paged_step_memory(program)
        say(f"serve[{name}]: {program} program temporaries "
            f"{mem['temp_bytes'] / 2**20:.1f} MiB beside a pool of "
            f"{mem['pool_bytes'] / 2**20:.1f} MiB per device (K; V is "
            f"the same), {mem['alias_bytes'] / 2**20:.1f} MiB aliased "
            f"in place")
        out[program] = mem
        if platform != "tpu":
            continue
        if mem["alias_bytes"] < 2 * mem["pool_bytes"]:
            raise AssertionError(
                f"{name}: the {program} program does not return the "
                f"donated pools in place: {mem}")
        if mem["temp_bytes"] >= mem["pool_bytes"] / 2:
            raise AssertionError(
                f"{name}: the {program} program's temporaries reach "
                f"half a pool, so something copies the pool inside a "
                f"step: {mem}")
    return out


def _prompts(rng, lengths, vocab):
    return [rng.integers(1, vocab, n).astype("int32").tolist()
            for n in lengths]


def leg_serve(tiny: bool, platform: str) -> dict:
    import jax
    import numpy as np

    from analytics_zoo_tpu.learn.inference_model import InferenceModel

    model = qwen_lm(tiny)
    t0 = time.time()
    variables = seeded_bf16_variables(model)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(variables))
    say(f"serve: {n_params / 1e6:.1f} M parameters initialised in bf16 "
        f"({time.time() - t0:.0f} s incl. compile)")
    buckets, max_new, slots = (32, 128), 16, 4
    # two prompt buckets, and lengths chosen so the chunked engine's
    # narrow-table widths stay at two values: few programs to compile
    lengths = (20, 28, 31, 100, 120)
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, lengths, model.vocab_size)
    fresh = _prompts(rng, lengths, model.vocab_size)
    im = InferenceModel(batch_buckets=(1, slots))
    im.load_flax_generator(model, variables, max_new_tokens=max_new,
                           prompt_buckets=buckets)
    paged = dict(engine_slots=slots, engine_paged=True,
                 engine_chunked=True, engine_kernel="fused",
                 engine_hbm_fraction=HBM_FRACTION,
                 # one chunk covers a whole bucket: fewer chunk shapes
                 engine_tick_token_budget=buckets[-1] + slots)
    configs = [
        ("default", dict(engine_slots=slots)),
        ("paged-chunked-fused-bf16", dict(paged, engine_kv_dtype="bf16")),
        ("paged-chunked-fused-int8", dict(paged, engine_kv_dtype="int8")),
    ]
    results = {}
    for name, kw in configs:
        t0 = time.time()
        r = _serve_once(name, im, kw, prompts, fresh, max_new, platform)
        results[name] = r
        say(f"serve[{name}]: {r['requests']} requests answered "
            f"(1 streamed), 0 steady-state compiles, KV "
            f"{r['kv_bytes'] / 2**20:.0f} MiB"
            + (f" in {r['n_blocks']} blocks" if r["n_blocks"] else "")
            + f", engine on device(s) {r['engine_devices'][0]}, "
            f"{time.time() - t0:.0f} s")
    # printed, not gated: with random weights a near-tie flips on
    # rounding — the numerics bar is the kernel leg's value comparison
    ref = results["default"]["outs"]
    for name in ("paged-chunked-fused-bf16", "paged-chunked-fused-int8"):
        pairs = [(a, b) for a, b in zip(ref, results[name]["outs"])
                 if a is not None]
        same = sum(x == y for a, b in pairs for x, y in zip(a, b))
        total = sum(len(a) for a, _ in pairs)
        say(f"serve: greedy tokens of {name} agreeing with default: "
            f"{same}/{total}")
        results[name]["greedy_agree"] = [same, total]
    return {n: {k: v for k, v in r.items() if k != "outs"}
            for n, r in results.items()}


# ---------------------------------------------------------------------------
# kernel leg
# ---------------------------------------------------------------------------

# Tolerances, with their reason.  Operands are bf16 (8 mantissa bits,
# eps = 2^-8 = 0.0039) and every reduction accumulates in f32 on both
# sides, so kernel and reference differ only where each rounds an
# intermediate to bf16 before an MXU pass (the softmax weights p, and in
# the backward ds) and in summation order.  That is a few bf16 ulps of
# the largest element: errors are measured relative to max|reference|
# and must stay under 8 ulps.  Observed values are printed.
REL_TOL = 8 * 2.0 ** -8


def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


def _assert_compiled(jitted, args, what: str, tiny: bool) -> None:
    """The lowered text of the calling jit holds the Mosaic custom call
    (on the chip); the CPU dry run interprets and says so."""
    text = jitted.lower(*args).as_text()
    if tiny:
        return
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no Mosaic custom call in the "
                             f"lowered program — kernel not compiled")


def _kernel_flash(tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.flash_attention import (_interpret_default,
                                                       flash_attention)
    from analytics_zoo_tpu.parallel.ring_attention import full_attention

    if _interpret_default() != tiny:
        raise AssertionError("flash_attention would run interpreted on "
                             "the chip (or compiled in the dry run)")
    B, T, H = (1, 128, 2) if tiny else (2, 2048, 4)
    worst = {}
    for D in ((16,) if tiny else (64, 128)):
        ks = jax.random.split(jax.random.key(D), 4)
        q, k, v, w = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                      for kk in ks)

        def loss(fn, q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

        flash = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(lambda *a: flash_attention(
                *a, causal=True), q, k, v), argnums=(0, 1, 2)))
        ref = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(lambda *a: full_attention(
                *a, None, causal=True), q, k, v), argnums=(0, 1, 2)))
        fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        _assert_compiled(fwd, (q, k, v), f"flash fwd D={D}", tiny)
        _assert_compiled(flash, (q, k, v), f"flash fwd+bwd D={D}", tiny)
        errs = {"fwd": _rel_err(fwd(q, k, v),
                                full_attention(q, k, v, None, causal=True))}
        (_, gf), (_, gr) = flash(q, k, v), ref(q, k, v)
        for nm, a, b in zip(("dq", "dk", "dv"), gf, gr):
            errs[nm] = _rel_err(a, b)
        for nm, e in errs.items():
            if not e <= REL_TOL:
                raise AssertionError(
                    f"flash_attention {nm} T={T} D={D}: rel err {e:.4f} "
                    f"> {REL_TOL:.4f}")
        worst[f"D{D}"] = round(max(errs.values()), 5)
        say(f"kernel: flash_attention fwd+bwd T={T} D={D} causal "
            f"{'interpreted' if tiny else 'compiled'}, max rel err "
            f"{max(errs.values()):.5f} (tol {REL_TOL:.4f})")
    return worst


def _paged_case(rng, quant, D, G, S, bs, KH, mesh=None):
    """Random pool + tables + positions; returns (q, pk, pv, tables, pos)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.ops.flash_attention import QuantKV, quantize_kv

    B, M = 4, 12
    N = B * M + 1
    H = KH * G
    kq, kk, kv = jax.random.split(jax.random.key(rng.integers(1 << 30)), 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    pk = jax.random.normal(kk, (N, KH, bs, D), jnp.bfloat16)
    pv = jax.random.normal(kv, (N, KH, bs, D), jnp.bfloat16)
    if quant:
        pk, pv = QuantKV(*quantize_kv(pk)), QuantKV(*quantize_kv(pv))
    # every row owns M private blocks (block 0 is the sink), shuffled
    tables = (rng.permutation(N - 1)[:B * M] + 1).reshape(B, M)
    # queries at pos .. pos+S-1 must stay inside the M*bs positions
    pos = rng.integers(0, M * bs - S + 1, B)
    pos[0] = M * bs - S         # one row at the very end of its table
    pos[1] = 0                  # and one at the very start
    return (q, pk, pv, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32))


def _kernel_paged(tiny: bool) -> dict:
    import itertools

    import jax
    import numpy as np

    from analytics_zoo_tpu.ops.flash_attention import paged_attention

    rng = np.random.default_rng(7)
    if tiny:
        grid = [(q, 16, g, s, 8) for q in (False, True)
                for g in (1, 2) for s in (1, 5)]
    else:
        # head_dim 64/128, GQA fold 1/6, decode / verify (k+1=5) / one
        # chunk width, block sizes 16/32 — bf16 and int8 pools
        grid = list(itertools.product((False, True), (64, 128), (1, 6),
                                      (1, 5, 128), (16, 32)))
    worst = 0.0
    for quant, D, G, S, bs in grid:
        args = _paged_case(rng, quant, D, G, S, bs, KH=2)
        fused = jax.jit(lambda *a: paged_attention(*a, kernel="fused"))
        gather = jax.jit(lambda *a: paged_attention(*a, kernel="gather"))
        what = (f"paged fused {'int8' if quant else 'bf16'} D={D} G={G} "
                f"S={S} bs={bs}")
        _assert_compiled(fused, args, what, tiny)
        e = _rel_err(fused(*args), gather(*args))
        if not e <= REL_TOL:
            raise AssertionError(f"{what}: rel err {e:.4f} vs gather "
                                 f"> {REL_TOL:.4f}")
        worst = max(worst, e)
    say(f"kernel: paged_attention fused vs gather, {len(grid)} shapes "
        f"(bf16+int8 pools, D/G/S/block-size grid) "
        f"{'interpreted' if tiny else 'compiled'}, max rel err "
        f"{worst:.5f} (tol {REL_TOL:.4f})")
    return {"shapes": len(grid), "max_rel_err": round(worst, 5)}


def leg_kernel(tiny: bool, platform: str) -> dict:
    return {"flash": _kernel_flash(tiny), "paged": _kernel_paged(tiny)}


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------

class PrefetchSpy:
    """Records which thread stages batches (data/loader.py): the
    threaded-prefetch branch is taken on every backend but the CPU."""

    def __enter__(self):
        from analytics_zoo_tpu.data import loader

        self._loader, self._orig = loader, loader.make_global_batch
        self.threads = []

        def spy(*a, **kw):
            self.threads.append(threading.current_thread().name)
            return self._orig(*a, **kw)

        loader.make_global_batch = spy
        return self

    def __exit__(self, *exc):
        self._loader.make_global_batch = self._orig


def _fit_checked(name, est, data, batch, tiny, platform, epochs=2):
    import numpy as np

    with PrefetchSpy() as spy:
        hist = est.fit(data, epochs=epochs, batch_size=batch)
    losses = [float(h["loss"]) for h in hist]
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss {losses}")
    # random labels: the loss need not fall, but it must stay the order
    # of magnitude it started at
    if not losses[-1] <= 1.5 * losses[0]:
        raise AssertionError(f"{name}: loss grew {losses}")
    if not on_platform(est.state.params, platform):
        raise AssertionError(f"{name}: parameters not on {platform}")
    staged = set(spy.threads)
    threaded = "zoo-device-prefetch" in staged
    if threaded == tiny:
        raise AssertionError(
            f"{name}: batches staged on {sorted(staged)} — the "
            f"{'inline' if tiny else 'threaded-prefetch'} branch of "
            f"data/loader.device_prefetch was expected")
    steps = sum(int(h["num_samples"]) for h in hist) // batch
    say(f"train[{name}]: {steps} steps through Estimator.fit, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, params on {platform}, "
        f"batches staged by "
        f"{'the prefetch thread' if threaded else 'the caller (CPU)'}")
    return {"steps": steps, "loss": [round(x, 4) for x in losses]}


def _bert_estimator(tiny: bool, mesh=None):
    """BERT-base (the model's own defaults), or a toy for the dry run."""
    import optax

    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import (BERT, BERT_PARTITION_RULES,
                                          BERTForSequenceClassification)

    kw = dict(mesh=mesh) if mesh is not None else {}
    bert = (BERT(vocab_size=512, hidden_size=32, num_layers=2, num_heads=2,
                 intermediate_size=64, max_position=32, **kw)
            if tiny else BERT(**kw))       # real BERT-base config (~110M)
    est = Estimator.from_flax(
        model=BERTForSequenceClassification(num_classes=2, bert=bert),
        loss="sparse_categorical_crossentropy",
        optimizer=optax.adamw(2e-5),
        feature_cols=("input_ids",), label_cols=("label",),
        partition_rules=BERT_PARTITION_RULES, **kw)
    est.config.log_every_steps = 1000
    return est, (512 if tiny else 30522)


def _bert_data(tiny: bool, vocab: int, steps: int):
    import numpy as np

    batch, seq = (8, 16) if tiny else (64, 128)
    rng = np.random.default_rng(0)
    n = batch * steps
    return batch, {
        "input_ids": rng.integers(0, vocab, (n, seq)).astype(np.int32),
        "label": rng.integers(0, 2, n).astype(np.int32)}


def leg_train(tiny: bool, platform: str) -> dict:
    import jax
    import numpy as np
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import (LM_PARTITION_RULES, TransformerLM,
                                          lm_loss)

    out = {}
    init_orca_context("local")
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        est, vocab = _bert_estimator(tiny)
        batch, data = _bert_data(tiny, vocab, steps=4)
        out["bert-base"] = _fit_checked("bert-base", est, data, batch, tiny,
                                        platform)
        # one Orbax save + restore of the full train state
        leaf0 = np.asarray(jax.tree.leaves(est.state.params)[0])
        est.save_checkpoint(ckpt_dir)
        est.state = est.state.replace(params=jax.tree.map(
            lambda x: x * 0, est.state.params))
        est.load_checkpoint(ckpt_dir)
        leaf1 = np.asarray(jax.tree.leaves(est.state.params)[0])
        if not (np.array_equal(leaf0, leaf1)
                and on_platform(est.state.params, platform)):
            raise AssertionError("checkpoint restore did not return the "
                                 "saved parameters to the device")
        say(f"train: Orbax checkpoint of the BERT train state saved and "
            f"restored bit-equal on {platform}")
        out["checkpoint"] = "ok"
        del est
        gc.collect()

        # the 111M LM at seq 2048 — the
        # flash kernel's forward and backward inside the pjit train step
        B, T, V = (8, 64, 512) if tiny else (8, 2048, 32000)
        rng = np.random.default_rng(0)
        data = {"tokens": rng.integers(0, V, (B * 3, T)).astype(np.int32)}
        model = (TransformerLM(vocab_size=V, hidden_size=32, num_layers=2,
                               num_heads=2, intermediate_size=64,
                               max_position=T) if tiny else
                 TransformerLM(vocab_size=32000, hidden_size=768,
                               num_layers=12, num_heads=12,
                               intermediate_size=3072, max_position=T))
        est = Estimator.from_flax(
            model=model, loss=lm_loss, optimizer=optax.adamw(1e-4),
            feature_cols=("tokens",), label_cols=("tokens",),
            partition_rules=LM_PARTITION_RULES)
        est.config.log_every_steps = 1000
        out["lm-111m-seq2048"] = _fit_checked(
            "lm-111m-seq2048", est, data, B, tiny, platform)
        if not tiny:
            est._build_jits()
            from analytics_zoo_tpu.data.loader import make_global_batch
            g = make_global_batch(est.mesh, {k: v[:B] for k, v in
                                             data.items()},
                                  est._data_sharding)
            text = est._jit_train_step.lower(est.state, g).as_text()
            if text.count("tpu_custom_call") < 3:
                raise AssertionError(
                    "LM train step does not hold the flash forward and "
                    "both backward Mosaic kernels")
            say("train: the LM train step's lowered program holds the "
                "flash forward + two backward Mosaic custom calls")
        del est
    finally:
        stop_orca_context()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        gc.collect()
    return out


# ---------------------------------------------------------------------------
# --chips 4 legs
# ---------------------------------------------------------------------------

def leg_replicas(tiny: bool, platform: str) -> dict:
    """Four one-chip replicas behind one broker: replica r on chip r,
    every chip serving."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.learn.inference_model import InferenceModel

    model = qwen_lm(tiny)
    variables = seeded_bf16_variables(model)
    slots, max_new, n_rep = 2, 8, 4
    im = InferenceModel(batch_buckets=(1, slots))
    im.load_flax_generator(model, variables, max_new_tokens=max_new,
                           prompt_buckets=(32,))
    cfg = dict(n_replicas=n_rep, engine_slots=slots, engine_paged=True,
               engine_chunked=True, engine_kernel="fused",
               engine_kv_dtype="bf16", engine_hbm_fraction=HBM_FRACTION,
               engine_tick_token_budget=32 + slots)
    chips = jax.devices()[:n_rep]
    before = [d.memory_stats() for d in chips]
    with _serving(im, cfg) as (serving, fe):
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, [20] * 16, model.vocab_size)
        errors, lock = [], threading.Lock()

        def client(p):
            try:
                toks = _post_generate(fe.port, {"tokens": p})
                if len(toks) != max_new:
                    raise AssertionError(f"{len(toks)} tokens")
            except BaseException as e:      # surfaced below
                with lock:
                    errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1100)
        if errors or any(t.is_alive() for t in threads):
            raise AssertionError(f"burst failed: {errors[:3]}")
        homes = []
        for r, eng in enumerate(serving.engines):
            ids = {d.id for leaf in jax.tree.leaves(
                (eng._variables, eng._pk, eng._pv))
                for d in leaf.devices()}
            if ids != {chips[r].id} or \
                    [d.id for d in eng._devices] != [chips[r].id]:
                raise AssertionError(
                    f"replica {r}: weights / pool on devices {ids}, "
                    f"engine owns {[d.id for d in eng._devices]}, "
                    f"expected chip {chips[r].id} alone")
            homes.append(chips[r].id)
        routed = list(serving._routed_counts)
        if min(routed) < 1:
            raise AssertionError(f"a replica served nothing: {routed}")
        pool = serving.engines[0].capacity_report()["arena_bytes"]
        grew = []
        for d, b in zip(chips, before):
            if b is None:           # CPU dry run: no device memory
                grew.append(None)
                continue
            grew.append(int(d.memory_stats()["bytes_in_use"])
                        - int(b["bytes_in_use"]))
            if grew[-1] < 0.9 * pool:
                raise AssertionError(
                    f"device {d.id} memory grew {grew[-1]} bytes — less "
                    f"than one replica's KV pool ({pool})")
        say(f"replicas: {len(prompts)} requests over {n_rep} replicas on "
            f"devices {homes}, routed {routed}, every replica's weights "
            f"and pool on its own device")
        return {"devices": homes, "routed": routed,
                "bytes_in_use_growth": grew}


def leg_tp2(tiny: bool, platform: str) -> dict:
    """One tp=2 engine with the fused kernel under shard_map, and the
    kernel's output on a tp-sharded pool against the one-chip kernel."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.ops.flash_attention import (QuantKV,
                                                       paged_attention)
    from analytics_zoo_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axes={"tp": 2}, devices=jax.devices()[:2])
    # ---- op level: same pool, one chip vs tp=2 -------------------------
    rng = np.random.default_rng(11)
    worst = 0.0
    D, G, bs = (16, 2, 8) if tiny else (128, 6, 16)
    for quant in (False, True):
        for S in (1, 5):
            q, pk, pv, tables, pos = _paged_case(rng, quant, D, G, S, bs,
                                                 KH=2)
            one = jax.jit(lambda *a: paged_attention(
                *a, kernel="fused"))(q, pk, pv, tables, pos)

            def shard(pool):
                sh = NamedSharding(mesh, P(None, "tp", None, None))
                if isinstance(pool, QuantKV):
                    return QuantKV(
                        jax.device_put(pool.data, sh),
                        jax.device_put(pool.scale, NamedSharding(
                            mesh, P(None, "tp", None))))
                return jax.device_put(pool, sh)

            tp_fn = jax.jit(lambda *a: paged_attention(
                *a, kernel="fused", mesh=mesh))
            targs = (jax.device_put(q, NamedSharding(
                mesh, P(None, None, "tp", None))), shard(pk), shard(pv),
                tables, pos)
            _assert_compiled(tp_fn, targs, "paged fused tp=2", tiny)
            e = _rel_err(tp_fn(*targs), one)
            if not e <= REL_TOL:
                raise AssertionError(
                    f"tp=2 fused {'int8' if quant else 'bf16'} S={S}: rel "
                    f"err {e:.5f} vs one-chip fused > {REL_TOL:.4f}")
            worst = max(worst, e)
    say(f"tp2: fused kernel under shard_map on a tp-sharded pool vs the "
        f"one-chip kernel, bf16+int8, max rel err {worst:.6f} "
        f"({'bitwise equal' if worst == 0.0 else 'not bitwise'})")
    # ---- engine level ---------------------------------------------------
    model = qwen_lm(tiny)
    variables = seeded_bf16_variables(model)
    slots, max_new = 2, 8
    im = InferenceModel(batch_buckets=(1, slots))
    im.load_flax_generator(model, variables, max_new_tokens=max_new,
                           prompt_buckets=(32,))
    prompts = _prompts(rng, (20, 28), model.vocab_size)
    fresh = _prompts(rng, (20, 28), model.vocab_size)
    r = _serve_once(
        "tp2", im, dict(engine_slots=slots, engine_paged=True,
                        engine_chunked=True, engine_kernel="fused",
                        engine_kv_dtype="bf16", engine_hbm_fraction=HBM_FRACTION,
                        engine_tick_token_budget=32 + slots),
        prompts, fresh, max_new, platform, engine_mesh=mesh)
    if r["engine_devices"][0] != sorted(d.id for d in jax.devices()[:2]):
        raise AssertionError(f"tp=2 engine on {r['engine_devices']}")
    say(f"tp2: one tp=2 engine (paged + chunked + fused) answered "
        f"{r['requests']} requests on devices {r['engine_devices'][0]}, "
        f"0 steady-state compiles")
    return {"op_max_rel_err": worst, "engine_devices": r["engine_devices"]}


def leg_bert_dp2tp2(tiny: bool, platform: str) -> dict:
    """BERT-base on mesh_axes={"dp": 2, "tp": 2} with the sharding
    assertions __graft_entry__.py makes on the CPU mesh."""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context

    ctx = init_orca_context("local", mesh_axes={"dp": 2, "tp": 2},
                            num_devices=4)
    try:
        est, vocab = _bert_estimator(tiny, mesh=ctx.mesh)
        batch, data = _bert_data(tiny, vocab, steps=3)
        out = _fit_checked("bert-base dp=2 x tp=2", est, data, batch, tiny,
                           platform)
        params = est.state.params
        k = params["bert"]["layer_0"]["attention"]["query"]["kernel"]
        emb = params["bert"]["word_embeddings"]["embedding"]
        for nm, arr in (("qkv kernel", k), ("vocab embedding", emb)):
            if "tp" not in str(arr.sharding.spec):
                raise AssertionError(
                    f"{nm} not tp-sharded: {arr.sharding.spec}")
        if len(k.sharding.device_set) != 4:
            raise AssertionError("train state does not span 4 devices")
        say("bert dp2tp2: qkv kernel and vocab embedding tp-sharded over "
            "a 4-device mesh")
        return out
    finally:
        stop_orca_context()


def result_line(device) -> str:
    """The last stdout line of a pass: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count`` as JAX reports them).  Whoever runs
    this script as a check reads that line and accepts no other key, so
    everything else rides on the summary line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


LEGS = {
    1: (("serve", leg_serve), ("kernel", leg_kernel), ("train", leg_train)),
    4: (("replicas", leg_replicas), ("tp2", leg_tp2),
        ("bert-dp2tp2", leg_bert_dp2tp2)),
}


def main() -> int:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at toy sizes (no chip needed)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve/kernel/train legs; 4: the multi-chip "
                         "legs (needs >= 4 TPU devices)")
    ap.add_argument("--legs", default=None,
                    help="comma-separated subset of the legs (debugging)")
    args = ap.parse_args()
    # the package logs epoch rates at INFO; this script reports none
    os.environ.setdefault("ZOO_TPU_LOGLEVEL", "WARNING")
    if args.tiny:
        TAG = "[CPU DRY RUN] "
        # as many virtual CPU devices as the run would have chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.chips}").strip()

    import jax

    platform = "cpu" if args.tiny else "tpu"
    if jax.default_backend() != platform:
        print(f"chip_smoke: FAILED before any leg — the default JAX "
              f"backend is {jax.default_backend()!r}, not {platform!r}"
              + ("" if args.tiny else
                 " (no accelerator found; `--tiny` is the CPU dry run)"),
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: FAILED before any leg — --chips {args.chips} "
              f"needs {args.chips} {platform} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    import jaxlib

    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:           # no libtpu wheel on a CPU-only box
        libtpu = "n/a"
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile cache: {cache_dir or 'off (explicit CPU platform)'}")
    os.makedirs(OUT_DIR, exist_ok=True)

    want = args.legs.split(",") if args.legs else None
    meter = CompileMeter()
    summary = {}
    for name, fn in LEGS[args.chips]:
        if want is not None and name not in want:
            continue
        snap, t0 = meter.snapshot(), time.time()
        try:
            result = fn(args.tiny, platform)
        except BaseException:
            traceback.print_exc()
            print(f"chip_smoke: FAILED leg={name}", file=sys.stderr)
            return 1
        stats = meter.since(snap)
        say(f"leg {name} passed in {time.time() - t0:.0f} s wall: "
            f"{stats['compile_s']} s in backend compiles, "
            f"{stats['cache_hits']} of {stats['cache_requests']} "
            f"cacheable compiles were persistent-cache hits")
        summary[name] = {"result": result, "wall_s": round(time.time() - t0),
                         **stats}
    line = {"ok": True, "device": device, "mode":
            "cpu-dry-run" if args.tiny else "chip", "chips": args.chips,
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu},
            "legs": summary, "claim": None}
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(line, f, indent=1)
    if args.tiny:
        say("all legs passed (CPU dry run: this proves the code path, "
            "not the chip)")
    # under --tiny both lines carry the tag, so neither parses as a chip
    # result; on the chip the result line is bare and last
    say(json.dumps(line))
    say(result_line(device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
