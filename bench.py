#!/usr/bin/env python
"""Benchmark: BERT-base fine-tune throughput through Estimator.fit()
(BASELINE.md config #3 — the north star), plus NCF (config #1) and
ResNet-50 (config #2).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

All models are measured through the REAL training path — ``fit()`` with
host batching, shuffling, and double-buffered device_put prefetch in the
measured window — not a bare pre-staged step function.

One process per chip: a TPU chip belongs to one process at a time, and a
parent that has touched JAX holds it, so a child that needs it then fails
or hangs.  The parent here therefore never imports JAX; it runs one child
at a time (``--bench NAME``), each of which takes the chip, measures one
model with fresh HBM, and gives the chip back when it exits.

This is a chip benchmark: a child that finds no TPU exits non-zero, and so
does the parent when any child fails — nothing is reported as skipped and
nothing falls back to the CPU.  ``vs_baseline`` compares BERT against the
same fit() loop on this host's CPU in a child forced onto the CPU (the
reference stack is CPU-only — Xeon/MKL — so TPU-vs-host-CPU is the
capability-parity ratio measurable here; BASELINE.md: no published
reference numbers exist).  ``extra.*_mfu`` is measured step FLOPs (XLA
cost analysis of the compiled train step) over the chip's peak.  Every
row names the device it ran on (platform, device_kind, device count).
"""

import json
import os
import subprocess
import sys
import time

BERT_SEQ = 128
BERT_BATCH = 64
BERT_STEPS_PER_EPOCH = 20
NCF_BATCH = 32768
N_USERS, N_ITEMS = 6040, 3706      # MovieLens-1M cardinalities

# Per-chip peaks by device_kind: dense bf16 FLOP/s and HBM bytes/s.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s).  Only devices this repo is run on are listed; any other
# device_kind is an error, never a 0.0 peak.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def _peak_for(device) -> dict:
    kind = getattr(device, "device_kind", "")
    if kind not in PEAKS:
        raise ValueError(
            f"no peak FLOP/s / bytes/s on record for device_kind "
            f"{kind!r} (known: {sorted(PEAKS)}); add it to bench.PEAKS "
            f"with its source")
    return PEAKS[kind]


def _device_row() -> dict:
    """The device a child ran on, as JAX reports it."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_tpu(name: str) -> None:
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"bench '{name}' is a chip benchmark and found backend "
                 f"{jax.default_backend()!r}, not 'tpu'")


def _warm_compile(est, data, batch_size):
    """Populate the jit cache before the measured window."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.data.loader import make_global_batch

    batch = {k: np.asarray(v[:batch_size]) for k, v in data.items()}
    est._ensure_state(batch)
    est._build_jits()
    g = make_global_batch(est.mesh, batch, est._data_sharding)
    state, mets = est._jit_train_step(est.state, g)
    jax.block_until_ready(mets)
    est.state = state


def _fit_throughput(est, data, batch_size, epochs=2):
    """Steady-state samples/sec through fit() — host batching, shuffling,
    H2D prefetch and the epoch metric fetch all inside the measured
    window; compile excluded via warmup.  fit's epoch barrier is a value
    fetch (estimator.py)."""
    _warm_compile(est, data, batch_size)
    hist = est.fit(data, epochs=epochs, batch_size=batch_size)
    return max(h["samples_per_sec"] for h in hist)


def _compute_throughput(est, data, batch_size, steps=20, n_buf=4):
    """Pure per-chip compute rate: batches pre-staged in HBM, no H2D in
    the loop, completion barrier at the end.  This is what the chip
    sustains when the input pipeline keeps up — the number to compare
    against MFU/peak."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.data.loader import make_global_batch

    bufs = []
    for i in range(n_buf):
        lo = (i * batch_size) % (len(next(iter(data.values()))) - batch_size)
        bufs.append(make_global_batch(
            est.mesh, {k: np.asarray(v[lo:lo + batch_size])
                       for k, v in data.items()}, est._data_sharding))
    # drain any queued work so the window starts clean
    state, mets = est._jit_train_step(est.state, bufs[0])
    est.state = state
    jax.block_until_ready(mets)
    t0 = time.perf_counter()
    for i in range(steps):
        est.state, mets = est._jit_train_step(est.state, bufs[i % n_buf])
    jax.block_until_ready(mets)         # completion barrier
    dt = time.perf_counter() - t0
    return steps * batch_size / dt


def _mfu(est, data, batch_size, sps, flops=None):
    """Measured FLOP/s over chip peak for the compiled train step.  Pass
    `flops` when calling more than once — _step_flops re-lowers and
    re-compiles the whole train step each time."""
    import jax

    if flops is None:
        flops = _step_flops(est, data, batch_size)
    peak = _peak_for(jax.devices()[0])["flops_per_s"]
    if not flops or not sps:
        raise RuntimeError(
            f"mfu needs step FLOPs and a rate, got flops={flops!r} "
            f"samples_per_sec={sps!r}")
    return round(flops / (batch_size / sps) / peak, 4)


def _step_flops(est, data, batch_size):
    """FLOPs of one compiled train step (XLA cost analysis)."""
    import numpy as np

    from analytics_zoo_tpu.data.loader import make_global_batch

    batch = {k: np.asarray(v[:batch_size]) for k, v in data.items()}
    gbatch = make_global_batch(est.mesh, batch, est._data_sharding)
    lowered = est._jit_train_step.lower(est.state, gbatch)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost.get("flops", 0.0)) if cost else 0.0


def bench_bert(platform: str):
    if platform == "cpu":
        # the CPU baseline runs on the host CPU whatever the
        # environment names (`--cpu-baseline` is also run directly)
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import (
        BERT, BERTForSequenceClassification, BERT_PARTITION_RULES)

    init_orca_context("local")
    model = BERTForSequenceClassification(
        num_classes=2, bert=BERT())     # real BERT-base config (~110M)
    est = Estimator.from_flax(
        model=model, loss="sparse_categorical_crossentropy",
        optimizer=optax.adamw(2e-5),
        feature_cols=("input_ids",), label_cols=("label",),
        partition_rules=BERT_PARTITION_RULES)
    est.config.log_every_steps = 1000   # keep host syncs out of the window
    rng = np.random.default_rng(0)
    n = BERT_BATCH * BERT_STEPS_PER_EPOCH
    data = {
        "input_ids": rng.integers(0, 30522, (n, BERT_SEQ)).astype(np.int32),
        "label": rng.integers(0, 2, n).astype(np.int32),
    }
    if platform == "cpu":
        data = {k: v[:BERT_BATCH * 2] for k, v in data.items()}
        sps = _fit_throughput(est, data, BERT_BATCH, epochs=1)
        stop_orca_context()
        return {"samples_per_sec": sps, "mfu": None}
    sps = _fit_throughput(est, data, BERT_BATCH)
    comp = _compute_throughput(est, data, BERT_BATCH)
    flops = _step_flops(est, data, BERT_BATCH)
    out = {"samples_per_sec": sps,
           "compute_samples_per_sec": comp,
           "mfu": _mfu(est, data, BERT_BATCH, comp, flops),
           "fit_mfu": _mfu(est, data, BERT_BATCH, sps, flops)}
    stop_orca_context()
    return out


def bench_resnet50():
    """ResNet-50 ImageNet-shape training throughput (config #2) — the
    most transfer-sensitive bench (~18 MB of uint8 pixels per step);
    ``transfer_bound`` flags a fit rate well under the compute rate."""
    import numpy as np
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import resnet50

    import flax.linen as nn
    import jax.numpy as jnp

    init_orca_context("local")
    rng = np.random.default_rng(0)
    bs, steps = 128, 10
    n = bs * steps
    # uint8 pixels over the wire, normalisation on device — the
    # TPU-idiomatic ImageNet input pipeline (decoded JPEGs ARE uint8);
    # shipping f32 would 4x the H2D bytes for zero information
    data = {
        "x": rng.integers(0, 256, (n, 224, 224, 3)).astype(np.uint8),
        "y": rng.integers(0, 1000, n).astype(np.int32),
    }

    class TrainResNet50(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.astype(jnp.float32) / 255.0
            mean = jnp.asarray([0.485, 0.456, 0.406])
            std = jnp.asarray([0.229, 0.224, 0.225])
            return resnet50(1000)((x - mean) / std, train=train)

    est = Estimator.from_flax(
        model=TrainResNet50(), loss="sparse_categorical_crossentropy",
        optimizer=optax.sgd(0.1, momentum=0.9),
        feature_cols=("x",), label_cols=("y",))
    est.config.log_every_steps = 1000
    sps = _fit_throughput(est, data, bs)
    comp = _compute_throughput(est, data, bs, steps=10, n_buf=2)
    mfu = _mfu(est, data, bs, comp)
    stop_orca_context()
    # 128x224x224x3 uint8 = ~18 MB/step
    step_mb = bs * 224 * 224 * 3 / 2**20
    return {"samples_per_sec": sps,
            "compute_samples_per_sec": comp,
            "mfu": mfu,
            "transfer_bound": sps < 0.8 * comp,
            "input_mb_per_step": round(step_mb, 1)}


def bench_ncf():
    import numpy as np
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import NeuralCF, NCF_PARTITION_RULES

    init_orca_context("local")
    rng = np.random.default_rng(0)
    n = NCF_BATCH * 8
    data = {
        "user": rng.integers(1, N_USERS + 1, n).astype(np.int32),
        "item": rng.integers(1, N_ITEMS + 1, n).astype(np.int32),
        "label": rng.integers(0, 2, n).astype(np.int32),
    }
    est = Estimator.from_flax(
        model=NeuralCF(user_count=N_USERS, item_count=N_ITEMS,
                       user_embed=64, item_embed=64, mf_embed=64,
                       hidden_layers=(128, 64, 32)),
        loss="sparse_categorical_crossentropy",
        optimizer=optax.adam(1e-3),
        feature_cols=("user", "item"), label_cols=("label",),
        partition_rules=NCF_PARTITION_RULES)
    est.config.log_every_steps = 1000
    sps = _fit_throughput(est, data, NCF_BATCH, epochs=2)
    comp = _compute_throughput(est, data, NCF_BATCH)
    stop_orca_context()
    return {"samples_per_sec": sps, "compute_samples_per_sec": comp}


def bench_wide_and_deep():
    """Wide&Deep recommendation throughput (config #5)."""
    import numpy as np
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import (
        ColumnFeatureInfo, WideAndDeep, WND_PARTITION_RULES)

    init_orca_context("local")
    info = ColumnFeatureInfo(
        wide_base_cols=("b0", "b1"), wide_base_dims=(100, 100),
        indicator_cols=("gender",), indicator_dims=(3,),
        embed_cols=("user", "item"), embed_in_dims=(6040, 3706),
        embed_out_dims=(64, 64), continuous_cols=("age",))
    rng = np.random.default_rng(0)
    bs = 16384
    n = bs * 8
    data = {
        "wide_cols": np.stack([rng.integers(1, 101, n),
                               rng.integers(101, 201, n)], 1).astype(np.int32),
        "indicator_cols": rng.integers(0, 3, (n, 1)).astype(np.int32),
        "embed_cols": np.stack([rng.integers(0, 6040, n),
                                rng.integers(0, 3706, n)], 1).astype(np.int32),
        "continuous_cols": rng.normal(size=(n, 1)).astype(np.float32),
        "label": rng.integers(0, 2, n).astype(np.int32),
    }
    model = WideAndDeep(class_num=2, column_info=info)
    est = Estimator.from_flax(
        model=model, loss="sparse_categorical_crossentropy",
        optimizer=optax.adam(1e-3),
        feature_cols=tuple(model.feature_groups()), label_cols=("label",),
        partition_rules=WND_PARTITION_RULES)
    est.config.log_every_steps = 1000
    sps = _fit_throughput(est, data, bs, epochs=2)
    comp = _compute_throughput(est, data, bs)
    stop_orca_context()
    return {"samples_per_sec": sps, "compute_samples_per_sec": comp}


def bench_forecast():
    """Zouwu LSTM forecaster throughput (config #4) through the
    Forecaster.fit surface on NYC-taxi-shaped windows."""
    import numpy as np

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.zouwu.forecaster import LSTMForecaster
    from analytics_zoo_tpu.zouwu.preprocessing import roll

    init_orca_context("local")
    from analytics_zoo_tpu.zouwu.preprocessing import StandardScaler

    t = np.arange(80_000, dtype=np.float32)
    series = (10 + 3 * np.sin(2 * np.pi * t / 48)
              + 0.3 * np.random.default_rng(0).normal(size=t.size))
    series = StandardScaler().fit_transform(series[:, None].astype(np.float32))
    x, y = roll(series, 96, 1)
    fc = LSTMForecaster(target_dim=1, feature_dim=1, lstm_units=(32, 16))
    fc.estimator.config.log_every_steps = 1000   # no mid-window fetches
    fc.fit(x[:1024], y[:1024], epochs=1, batch_size=512)   # warm compile
    fc.evaluate(x[:512], y[:512])       # ... of the eval program too
    last = fc.fit(x, y, epochs=1, batch_size=512)   # returns last-epoch stats
    sps = last["samples_per_sec"]
    mse = fc.evaluate(x[-2048:], y[-2048:])["mse"]
    stop_orca_context()
    return {"samples_per_sec": sps, "holdout_mse": round(float(mse), 4)}


def bench_lm():
    """Beyond-parity extension: 111M-param causal LM at seq 2048 through
    fit() — long-context throughput via the Pallas flash path (the
    reference has no generative-LM capability at all)."""
    import numpy as np
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import (
        TransformerLM, LM_PARTITION_RULES, LMWithFusedLoss, lm_loss,
        fused_lm_loss)

    init_orca_context("local")
    rng = np.random.default_rng(0)
    B, T = 8, 2048
    data = {"tokens": rng.integers(0, 32000, (B * 8, T)).astype(np.int32)}
    model = TransformerLM(vocab_size=32000, hidden_size=768, num_layers=12,
                          num_heads=12, intermediate_size=3072,
                          max_position=T)

    # plain path: full [B, T, V] logits materialised, then CE
    est = Estimator.from_flax(
        model=model, loss=lm_loss, optimizer=optax.adamw(1e-4),
        feature_cols=("tokens",), label_cols=("tokens",),
        partition_rules=LM_PARTITION_RULES)
    est.config.log_every_steps = 1000
    sps_plain = _fit_throughput(est, data, B)
    # model-math FLOPs from the plain step; the fused step does the SAME
    # model math (its extra head-matmul recompute is a hardware cost, not
    # model FLOPs, so sharing this numerator keeps MFU comparable)
    flops = _step_flops(est, data, B)

    # fused blockwise loss: logits never materialised (models/lm.py
    # LMWithFusedLoss) — trades one head-matmul recompute in backward for
    # several full HBM passes over a 2.1 GB logits tensor.  A failure
    # here fails the bench: both paths are what `lm` measures.
    est_f = Estimator.from_flax(
        model=LMWithFusedLoss(lm=model), loss=fused_lm_loss,
        optimizer=optax.adamw(1e-4),
        feature_cols=("tokens",), label_cols=("tokens",),
        partition_rules=LM_PARTITION_RULES)
    est_f.config.log_every_steps = 1000
    sps_fused = _fit_throughput(est_f, data, B)
    est = est_f

    sps = max(sps_plain, sps_fused)
    out = {"samples_per_sec": sps,
           "tokens_per_sec": sps * T,
           "seq_len": T,
           "mfu": _mfu(est, data, B, sps, flops),
           "samples_per_sec_plain_loss": sps_plain,
           "samples_per_sec_fused_loss": sps_fused,
           "mfu_plain_loss": _mfu(est, data, B, sps_plain, flops)}
    stop_orca_context()
    return out


BENCHES = {
    "bert": lambda: bench_bert("tpu"),
    "ncf": bench_ncf,
    "resnet": bench_resnet50,
    "wnd": bench_wide_and_deep,
    "forecast": bench_forecast,
    "lm": bench_lm,
    "cpu-baseline": lambda: bench_bert("cpu"),
}


def _run_sub(name: str, timeout: int = 1800) -> dict:
    """One bench in its own process (it owns the chip while it runs).
    Any failure — non-zero exit, timeout, no JSON row — ends the run
    non-zero, naming the phase."""
    env = dict(os.environ)
    if name == "cpu-baseline":
        env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--bench", name],
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        sys.exit(f"bench phase '{name}' timed out after {timeout}s")
    if out.returncode != 0:
        sys.exit(f"bench phase '{name}' failed (rc={out.returncode}):\n"
                 f"{out.stderr[-2000:]}")
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    sys.exit(f"bench phase '{name}' printed no JSON row:\n"
             f"{out.stderr[-2000:]}")


def main():
    if "--bench" in sys.argv:
        name = sys.argv[sys.argv.index("--bench") + 1]
        if name != "cpu-baseline":
            _require_tpu(name)
        row = BENCHES[name]()
        row["device"] = _device_row()
        print(json.dumps(row))
        return
    if "--cpu-baseline" in sys.argv:      # CPU-only, runs in-process
        res = bench_bert("cpu")
        res["cpu_samples_per_sec"] = res["samples_per_sec"]  # old key
        res["device"] = _device_row()
        print(json.dumps(res))
        return
    # The parent stays off JAX (see the module docstring) and runs the
    # children strictly one after another.
    results = {name: _run_sub(name) for name in (
        "bert", "ncf", "resnet", "wnd", "forecast", "lm", "cpu-baseline")}
    bert, ncf, resnet = (results[k] for k in ("bert", "ncf", "resnet"))
    wnd, fcst, lm = (results[k] for k in ("wnd", "forecast", "lm"))
    cpu = results["cpu-baseline"]
    bert_sps = bert["samples_per_sec"]
    cpu_sps = cpu["samples_per_sec"]
    # The CPU run is short (2 batches), so the ratio is an
    # order-of-magnitude figure: quote it to 2 significant digits.
    print(json.dumps({
        "metric": "bert_base_ft_samples_per_sec_per_chip",
        "value": round(bert_sps, 1),
        "unit": "samples/sec",
        "vs_baseline": float(f"{bert_sps / cpu_sps:.2g}"),
        "device": bert["device"],
        "extra": {
            "bert_mfu": bert["mfu"],
            "bert_fit_mfu": bert["fit_mfu"],
            "bert_compute_samples_per_sec":
                round(bert["compute_samples_per_sec"], 1),
            "bert_seq_len": BERT_SEQ,
            "bert_global_batch": BERT_BATCH,
            "measured_through":
                "Estimator.fit steady state (host batching + prefetch + "
                "epoch metric fetch); *_compute_* = pre-staged batches, "
                "block_until_ready barrier; mfu uses the compute rate",
            "isolation": "each model benched in its own process (one "
                         "process holds the chip at a time)",
            "ncf_train_samples_per_sec_per_chip":
                round(ncf["samples_per_sec"], 1),
            "ncf_compute_samples_per_sec":
                round(ncf["compute_samples_per_sec"], 1),
            "resnet50_train_samples_per_sec_per_chip":
                round(resnet["samples_per_sec"], 1),
            "resnet50_compute_samples_per_sec":
                round(resnet["compute_samples_per_sec"], 1),
            "resnet50_mfu": resnet["mfu"],
            "resnet50_transfer_bound": resnet["transfer_bound"],
            "resnet50_input_mb_per_step": resnet["input_mb_per_step"],
            "wide_and_deep_train_samples_per_sec_per_chip":
                round(wnd["samples_per_sec"], 1),
            "wide_and_deep_compute_samples_per_sec":
                round(wnd["compute_samples_per_sec"], 1),
            "forecaster_train_samples_per_sec_per_chip":
                round(fcst["samples_per_sec"], 1),
            "forecaster_holdout_mse": fcst["holdout_mse"],
            "lm_111m_seq2048_tokens_per_sec":
                round(lm["tokens_per_sec"], 0),
            "lm_111m_seq2048_mfu": lm["mfu"],
            "cpu_baseline_device": cpu["device"],
        },
    }))


if __name__ == "__main__":
    main()
