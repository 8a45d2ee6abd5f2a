"""The bailing_hybrid family on the program's normal path: maps a
configuration file onto ``models.hybrid_lm.HybridLM`` (layer kinds ``"kda"``
and ``"mla"``, dense leading layers, the sigmoid router, an untied head) and
places the benchmark's seeded leaves in that model's own parameter tree.
The one file of the family that may import the package."""

from __future__ import annotations

import jax.numpy as jnp

from . import leaves
from .reference import kda_consts

# the published readings of the source's switches that HybridLM builds;
# another value is another architecture
SWITCHES = {
    "hidden_act": "silu", "use_bias": False, "use_qkv_bias": False,
    "q_lora_rank": None, "tie_word_embeddings": False,
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
    "rope_interleave": True, "kda_safe_gate": True, "no_kda_lora": True,
    "use_kda_lora": False, "linear_silu": True, "use_mla_nope": False,
    "use_nGPT": False, "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False, "group_norm_size": 1, "num_shared_experts": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "num_nextn_predict_layers": 0, "rope_scaling": None,
}


def program():
    """The package's module that ``build`` takes its model from
    (``flips.py`` wraps its ``sigmoid_route`` to see and force the
    program's expert choices)."""
    from analytics_zoo_tpu.models import hybrid_lm

    return hybrid_lm


def build(cfg: dict):
    HybridLM = program().HybridLM

    off = {k: cfg.get(k) for k, v in SWITCHES.items() if cfg.get(k) != v}
    L = leaves.n_layers(cfg)
    clamps = [x for key in ("expert_swiglu_limit_list",
                            "share_expert_swiglu_limit_list")
              for x in cfg[key][:L]]
    if off or any(clamps):
        raise ValueError(f"configuration outside the bailing_hybrid family "
                         f"as HybridLM builds it: {off or 'a SwiGLU clamp'}")
    d = leaves.dims(cfg)
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[cfg.get("torch_dtype", "bfloat16")]
    return HybridLM(
        vocab_size=d["V"], hidden_size=d["E"],
        layer_types=tuple(leaves.mixer(cfg, i) for i in range(L)),
        num_heads=d["H"], num_kv_heads=d["H"], head_dim=d["nope"],
        ssm_heads=0, ssm_head_dim=0, ssm_state=0, ssm_conv=d["K"],
        experts_total=d["X"], experts_held=d["Xh"], first_expert=d["X0"],
        experts_per_token=d["Kx"], expert_width=d["F"],
        shared_width=d["Fs"], max_position=cfg["max_position_embeddings"],
        ln_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        kda_heads=d["H"], kda_head_dim=d["dk"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        latent_rank=d["Lr"], rope_dim=d["rope"], value_dim=d["vd"],
        rope_theta=float(cfg["rope_theta"]), dense_layers=d["dense"],
        dense_width=d["Fd"], router_score="sigmoid",
        expert_groups=d["G"], expert_groups_kept=d["Gk"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        tie_embeddings=False)


def place(cfg: dict, top: dict, layer_of) -> dict:
    """The model's ``params`` tree, filled with ``top`` and with
    ``layer_of(i)`` for every layer ``i``."""
    params = {"embed": {"embedding": top["embed"]},
              "ln_f": {"scale": top["ln_f"]}, "lm_head": top["head"]}
    for i in range(leaves.n_layers(cfg)):
        w = layer_of(i)
        lay = {"ln_mixer": {"scale": w["ln_mixer"]},
               "ln_ffn": {"scale": w["ln_ffn"]}}
        if "mlp_in" in w:
            lay["mlp"] = {"w_in": w["mlp_in"], "w_out": w["mlp_out"]}
        else:
            lay["moe"] = {"router": w["router"], "bias": w["router_bias"],
                          "w_in": w["w_in"], "w_out": w["w_out"]}
            lay["shared"] = {"w_in": w["sh_in"], "w_out": w["sh_out"]}
        if leaves.mixer(cfg, i) == "kda":
            dt_bias, a_log = kda_consts(cfg, w["f_raw"], w["a_raw"])
            lay["kda"] = {
                "in_proj": jnp.concatenate(
                    [w["w_qkv"], w["w_f"], w["w_b"], w["w_g"]], axis=1),
                "conv_w": w["conv_w"], "dt_bias": dt_bias, "A_log": a_log,
                "norm": w["norm"], "out_proj": w["w_o"]}
        else:
            lay["attention"] = {
                "wq": w["wq"], "wkva": w["wkva"], "kv_norm": w["kv_norm"],
                "wkvb": w["wkvb"], "gate": w["w_gate"], "wo": w["wo"]}
        params[f"layer_{i}"] = lay
    return params
