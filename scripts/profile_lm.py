#!/usr/bin/env python
"""Ablation profile for the two MFU laggards:
the 111M LM at seq 2048 (bench: 31.8% MFU) and ResNet-50's compute
path (29.2%).  Instead of a trace viewer (no display here), each
suspect is isolated by measuring jitted step-time DELTAS:

  lm.full            train step exactly as bench_lm runs it
  lm.trunk_only      same but loss = mean(hidden) — no head matmul, no CE
                     (delta = logits materialisation + CE + their bwd)
  lm.dot_attention   use_flash=False (delta = flash kernel vs XLA dot)
  lm.fused_loss      LMWithFusedLoss blockwise CE (delta = the cost of
                     materialising [B, T, V] logits, the suspected sink)
  lm.no_remat_check  remat=False asserted at model build
  lm.flops           XLA cost-analysis FLOPs vs analytic FLOPs — pallas
                     kernels are invisible to cost_analysis, so reported
                     MFU undercounts when flash is on; the analytic
                     number is the honest numerator
  resnet.bs{128,256} compute-path samples/sec at both batch sizes

Each timing: compile excluded by one warm step, then N steps ending in
``block_until_ready``.  Prints one JSON dict.  Chip only: an unknown
device_kind raises (bench.PEAKS).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import _peak_for  # noqa: E402  (device-keyed peaks)


def _peak() -> float:
    return _peak_for(jax.devices()[0])["flops_per_s"]


# the ONE profiled LM config — build() and the analytic-FLOPs formula
# must agree on these or mfu_analytic silently measures a different model
LM_B, LM_T, LM_V = 8, 2048, 32000
LM_H, LM_L, LM_F, LM_HEADS = 768, 12, 3072, 12


def _time_steps(step, state, batch, n=10):
    state2, mets = step(state, batch)
    jax.block_until_ready(mets)                     # compile + warm
    t0 = time.perf_counter()
    for _ in range(n):
        state2, mets = step(state2, batch)
    jax.block_until_ready(mets)
    return (time.perf_counter() - t0) / n


def lm_ablations():
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import (
        TransformerLM, LM_PARTITION_RULES, lm_loss)
    from analytics_zoo_tpu.data.loader import make_global_batch

    B, T, V = LM_B, LM_T, LM_V
    rng = np.random.default_rng(0)
    data = {"tokens": rng.integers(0, V, (B * 2, T)).astype(np.int32)}
    out = {}

    def build(loss_fn, use_flash=True, wrap=None):
        model = TransformerLM(vocab_size=V, hidden_size=LM_H,
                              num_layers=LM_L, num_heads=LM_HEADS,
                              intermediate_size=LM_F, max_position=T,
                              use_flash=use_flash)
        assert not model.remat, "bench runs remat=False; profile must too"
        est = Estimator.from_flax(
            model=wrap(model) if wrap else model, loss=loss_fn,
            optimizer=optax.adamw(1e-4),
            feature_cols=("tokens",), label_cols=("tokens",),
            partition_rules=LM_PARTITION_RULES)
        est.config.log_every_steps = 1000
        batch = {k: v[:B] for k, v in data.items()}
        est._ensure_state(batch)
        est._build_jits()
        g = make_global_batch(est.mesh, batch, est._data_sharding)
        return est, g

    def trunk_only_loss(logits, tokens):
        # kills the head+CE: grads still flow through the whole trunk.
        # NOTE logits here IS the head output — to skip the head matmul
        # we need the model-side ablation below; this variant only
        # removes CE.
        return jnp.mean(logits)

    # full step, exactly as bench_lm
    est, g = build(lm_loss)
    out["full_step_s"] = _time_steps(
        lambda s, b: est._jit_train_step(s, b), est.state, g)
    lowered = est._jit_train_step.lower(est.state, g)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    out["xla_cost_flops"] = xla_flops
    del lowered
    # analytic: matmul 6*P_mat*tokens (fwd+bwd) + flash fwd 4BT^2H/layer
    # + flash bwd ~2.5x fwd (recompute) ; head fwd+bwd 3x2BTHV
    p_mat = LM_L * (4 * LM_H * LM_H + 2 * LM_H * LM_F)  # qkvo + ffn weights
    toks = B * T
    mm = 6 * p_mat * toks
    att = LM_L * 4 * B * T * T * LM_H * 3.5
    head = 3 * 2 * B * T * LM_H * V
    out["analytic_flops"] = float(mm + att + head)
    out["mfu_xla"] = xla_flops / out["full_step_s"] / _peak()
    out["mfu_analytic"] = out["analytic_flops"] / out["full_step_s"] / _peak()

    del est, g                      # free 111M params + adam state

    # CE removed (head matmul stays): delta isolates softmax-CE cost
    est2, g2 = build(trunk_only_loss)
    out["no_ce_step_s"] = _time_steps(
        lambda s, b: est2._jit_train_step(s, b), est2.state, g2)
    del est2, g2

    # dot attention instead of the pallas flash kernel
    est3, g3 = build(lm_loss, use_flash=False)
    out["dot_attn_step_s"] = _time_steps(
        lambda s, b: est3._jit_train_step(s, b), est3.state, g3)
    del est3, g3

    # fused blockwise loss (models/lm.py LMWithFusedLoss): [B,T,V] logits
    # never materialised — the HBM-bandwidth fix the full/no_ce delta
    # motivates; delta vs full_step_s is the end-to-end win
    from analytics_zoo_tpu.models import LMWithFusedLoss, fused_lm_loss

    est4, g4 = build(fused_lm_loss, wrap=lambda m: LMWithFusedLoss(lm=m))
    out["fused_loss_step_s"] = _time_steps(
        lambda s, b: est4._jit_train_step(s, b), est4.state, g4)
    out["mfu_analytic_fused"] = (
        out["analytic_flops"] / out["fused_loss_step_s"] / _peak())
    del est4, g4

    out["ce_cost_s"] = out["full_step_s"] - out["no_ce_step_s"]
    out["flash_saving_s"] = out["dot_attn_step_s"] - out["full_step_s"]
    out["fused_loss_saving_s"] = (
        out["full_step_s"] - out["fused_loss_step_s"])
    out["tokens_per_sec"] = toks / out["full_step_s"]
    out["tokens_per_sec_fused"] = toks / out["fused_loss_step_s"]
    stop_orca_context()
    return out


def flash_block_ablation():
    """Standalone flash fwd+bwd at the bench's attention shape across
    block-size configs — the kernel's only tuning knobs.  Cheap (a few
    steps each); informs whether 512x512 (the default) is right for
    v5e's VMEM/MXU balance."""
    from analytics_zoo_tpu.ops import flash_attention

    B, T, H, D = LM_B, LM_T, LM_HEADS, LM_H // LM_HEADS
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    out = {}
    for bq, bk in ((256, 256), (512, 512), (1024, 512), (512, 1024)):
        @jax.jit
        def step(q, k, v, bq=bq, bk=bk):
            def f(q, k, v):
                return flash_attention(q, k, v, causal=True,
                                       block_q=bq, block_k=bk).sum()
            l, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
            return l, grads

        try:
            l, _ = step(q, k, v)
            jax.block_until_ready(l)                # compile + warm
            t0 = time.perf_counter()
            for _ in range(10):
                l, _ = step(q, k, v)
            jax.block_until_ready(l)
            out[f"bq{bq}_bk{bk}_s"] = (time.perf_counter() - t0) / 10
        except Exception as e:                      # VMEM overflow etc.
            out[f"bq{bq}_bk{bk}_s"] = f"failed: {type(e).__name__}"
    return out


def resnet_ablations():
    import flax.linen as nn
    import optax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.learn import Estimator
    from analytics_zoo_tpu.models import resnet50
    from analytics_zoo_tpu.data.loader import make_global_batch

    out = {}
    rng = np.random.default_rng(0)

    class TrainResNet50(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.astype(jnp.float32) / 255.0
            mean = jnp.asarray([0.485, 0.456, 0.406])
            std = jnp.asarray([0.229, 0.224, 0.225])
            return resnet50(1000)((x - mean) / std, train=train)

    est = None
    for bs in (128, 256):
        del est
        data = {
            "x": rng.integers(0, 256, (bs, 224, 224, 3)).astype(np.uint8),
            "y": rng.integers(0, 1000, bs).astype(np.int32),
        }
        est = Estimator.from_flax(
            model=TrainResNet50(), loss="sparse_categorical_crossentropy",
            optimizer=optax.sgd(0.1, momentum=0.9),
            feature_cols=("x",), label_cols=("y",))
        est.config.log_every_steps = 1000
        est._ensure_state(data)
        est._build_jits()
        g = make_global_batch(est.mesh, data, est._data_sharding)
        dt = _time_steps(lambda s, b: est._jit_train_step(s, b),
                         est.state, g, n=8)
        lowered = est._jit_train_step.lower(est.state, g)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        fl = float(cost.get("flops", 0.0)) if cost else 0.0
        out[f"bs{bs}_step_s"] = dt
        out[f"bs{bs}_samples_per_sec"] = bs / dt
        out[f"bs{bs}_mfu"] = fl / dt / _peak()
    return out


def main():
    from analytics_zoo_tpu import init_orca_context, stop_orca_context

    res = {}
    if "--resnet-only" not in sys.argv:
        init_orca_context("local")
        res["lm"] = lm_ablations()      # stops its own context
        res["flash_blocks"] = flash_block_ablation()
    if "--lm-only" not in sys.argv:
        init_orca_context("local")
        res["resnet"] = resnet_ablations()
        stop_orca_context()
    print(json.dumps(res, indent=1, default=float))


if __name__ == "__main__":
    main()
