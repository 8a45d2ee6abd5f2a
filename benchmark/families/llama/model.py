"""The llama family on the program's normal path: maps a configuration file
onto ``TransformerLM`` the way ``net/hf_net.py:_from_llama_family`` maps a
Hugging Face ``config.json``, and places the benchmark's seeded leaves in
that model's own parameter tree.  The one file of a family that may import
the package."""

from __future__ import annotations

import jax.numpy as jnp


def build(cfg: dict):
    from analytics_zoo_tpu.models import TransformerLM

    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("rope_scaling"):
        raise ValueError("configuration outside the llama family as "
                         "TransformerLM builds it")
    return TransformerLM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"], dropout=0.0,
        dtype=jnp.bfloat16, pos_encoding="rope",
        rope_base=float(cfg["rope_theta"]), norm="rmsnorm", mlp="swiglu",
        use_bias=False, qkv_bias=bool(cfg.get("attention_bias", False)),
        tied_head=bool(cfg["tie_word_embeddings"]),
        ln_eps=float(cfg["rms_norm_eps"]))


def place(cfg: dict, top: dict, layer_of) -> dict:
    """The model's ``params`` tree, filled with ``top`` and with
    ``layer_of(i)`` for every layer ``i``."""
    params = {"embed": {"embedding": top["embed"]},
              "ln_f": {"scale": top["ln_f"]}}
    if "head" in top:
        params["lm_head"] = {"kernel": top["head"]}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_of(i)
        attn = {"query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]},
                "attn_out": {"kernel": w["wo"]}}
        if "bq" in w:
            attn["query"]["bias"] = w["bq"]
            attn["key"]["bias"] = w["bk"]
            attn["value"]["bias"] = w["bv"]
        params[f"layer_{i}"] = {
            "ln_attn": {"scale": w["ln_attn"]}, "attention": attn,
            "ln_ffn": {"scale": w["ln_ffn"]},
            "ffn_gate": {"kernel": w["w_gate"]},
            "ffn_up": {"kernel": w["w_up"]},
            "ffn_down": {"kernel": w["w_down"]}}
    return params
