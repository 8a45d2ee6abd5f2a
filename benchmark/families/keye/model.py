"""The keye family on the program's normal path: maps a configuration file
onto ``TransformerLM`` with its sparse-expert and sparse-attention knobs,
and places the benchmark's seeded leaves in that model's own parameter
tree.  The one file of the family that may import the package."""

from __future__ import annotations

import jax.numpy as jnp


def build(cfg: dict):
    from analytics_zoo_tpu.models import TransformerLM

    if (cfg.get("hidden_act", "silu") != "silu"
            or cfg.get("attention_bias") or cfg["tie_word_embeddings"]
            or cfg.get("decoder_sparse_step", 1) != 1
            or cfg.get("mlp_only_layers") or not cfg["norm_topk_prob"]
            or cfg["rope_scaling"].get("rope_type", "default") != "default"
            or cfg["sa_config"]["indexer_num_kv_heads"] != 1):
        raise ValueError("configuration outside the keye family as "
                         "TransformerLM builds it")
    sa = cfg["sa_config"]
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[cfg.get("torch_dtype", "bfloat16")]
    return TransformerLM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], qk_norm=True,
        intermediate_size=cfg["moe_intermediate_size"],
        experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_topk=sa["topk"],
        max_position=cfg["max_position_embeddings"], dropout=0.0,
        dtype=dtype, pos_encoding="rope",
        rope_base=float(cfg["rope_theta"]), norm="rmsnorm", mlp="swiglu",
        use_bias=False, tied_head=False,
        ln_eps=float(cfg["rms_norm_eps"]))


def place(cfg: dict, top: dict, layer_of) -> dict:
    """The model's ``params`` tree, filled with ``top`` and with
    ``layer_of(i)`` for every layer ``i``."""
    params = {"embed": {"embedding": top["embed"]},
              "ln_f": {"scale": top["ln_f"]},
              "lm_head": {"kernel": top["head"]}}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_of(i)
        params[f"layer_{i}"] = {
            "ln_attn": {"scale": w["ln_attn"]},
            "attention": {
                "query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]},
                "attn_out": {"kernel": w["wo"]},
                "q_norm": {"scale": w["q_norm"]},
                "k_norm": {"scale": w["k_norm"]},
                "idx_query": {"kernel": w["idx_wq"]},
                "idx_key": {"kernel": w["idx_wk"]},
                "idx_key_norm": {"scale": w["idx_k_scale"],
                                 "bias": w["idx_k_bias"]},
                "idx_weight": {"kernel": w["idx_ww"]}},
            "ln_ffn": {"scale": w["ln_ffn"]},
            "moe": {"router": w["router"], "w_gate": w["w_gate"],
                    "w_up": w["w_up"], "w_down": w["w_down"]}}
    return params
