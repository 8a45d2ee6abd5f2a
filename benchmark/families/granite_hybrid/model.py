"""The granite_hybrid family on the program's normal path: maps a
configuration file onto ``models.hybrid_lm.HybridLM`` and places the
benchmark's seeded leaves in that model's own parameter tree.  The one file
of the family that may import the package."""

from __future__ import annotations

import jax.numpy as jnp

from . import leaves
from .reference import mixer_consts


def build(cfg: dict):
    from analytics_zoo_tpu.models.hybrid_lm import HybridLM

    if (cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias")
            or not cfg["tie_word_embeddings"] or cfg.get("mamba_proj_bias")
            or not cfg["mamba_conv_bias"]
            or cfg["position_embedding_type"] != "nope"
            or cfg.get("normalization_function", "rmsnorm") != "rmsnorm"):
        raise ValueError("configuration outside the granite_hybrid family "
                         "as HybridLM builds it")
    d = leaves.dims(cfg)
    leaves.n_layers(cfg)
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[cfg.get("torch_dtype", "bfloat16")]
    return HybridLM(
        vocab_size=d["V"], hidden_size=d["E"],
        layer_types=tuple(cfg["layer_types"]), num_heads=d["H"],
        num_kv_heads=d["KH"], head_dim=d["D"], ssm_heads=d["HS"],
        ssm_head_dim=d["P"], ssm_state=d["N"], ssm_conv=d["K"],
        ssm_chunk=d["Q"], experts_total=d["X"], experts_held=d["Xh"],
        first_expert=d["X0"], experts_per_token=d["Kx"],
        expert_width=d["F"], shared_width=d["Fs"],
        attention_multiplier=float(cfg["attention_multiplier"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        max_position=cfg["max_position_embeddings"],
        ln_eps=float(cfg["rms_norm_eps"]), dtype=dtype)


def place(cfg: dict, top: dict, layer_of) -> dict:
    """The model's ``params`` tree, filled with ``top`` and with
    ``layer_of(i)`` for every layer ``i``."""
    params = {"embed": {"embedding": top["embed"]},
              "ln_f": {"scale": top["ln_f"]}}
    for i in range(leaves.n_layers(cfg)):
        w = layer_of(i)
        lay = {"ln_mixer": {"scale": w["ln_mixer"]},
               "ln_ffn": {"scale": w["ln_ffn"]},
               "moe": {"router": w["router"], "w_in": w["w_in"],
                       "w_out": w["w_out"]},
               "shared": {"w_in": w["sh_in"], "w_out": w["sh_out"]}}
        if leaves.kind(cfg, i) == "mamba":
            dt_bias, a_log = mixer_consts(w["dt_raw"], w["a_raw"])
            lay["mamba"] = {
                "in_proj": jnp.concatenate(
                    [w["w_z"], w["w_xbc"], w["w_dt"]], axis=1),
                "conv_w": w["conv_w"], "conv_b": w["conv_b"],
                "dt_bias": dt_bias, "A_log": a_log, "D": w["D"],
                "norm": w["norm"], "out_proj": w["w_o"]}
        else:
            lay["attention"] = {
                "query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]},
                "attn_out": {"kernel": w["wo"]}}
        params[f"layer_{i}"] = lay
    return params
