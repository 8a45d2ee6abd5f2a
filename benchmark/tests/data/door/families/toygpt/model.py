"""The toy family on the program's path: another setting of
``TransformerLM``'s own knobs.  The model's FFN has one width for every
layer, so a layer with fewer live columns is placed padded with zeros:
gelu(0) = 0, and the padded rows of the down projection multiply it."""

import jax.numpy as jnp


def build(cfg):
    from analytics_zoo_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=cfg["n_vocab"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        num_kv_heads=cfg["n_kv_head"], intermediate_size=cfg["n_inner"],
        max_position=cfg["n_positions"], dropout=0.0, dtype=jnp.bfloat16,
        pos_encoding="rope", rope_base=float(cfg["rope_base"]),
        norm="layernorm", mlp="gelu", use_bias=True, tied_head=True,
        ln_eps=float(cfg["ln_eps"]))


def place(cfg, top, layer_of):
    F = cfg["n_inner"]
    params = {"embed": {"embedding": top["wte"]},
              "ln_f": {"scale": top["lnf_s"], "bias": top["lnf_b"]}}
    for i in range(cfg["n_layer"]):
        w = layer_of(i)
        pad = F - w["w_up"].shape[1]
        kb = lambda k, b: {"kernel": w[k], "bias": w[b]}
        params[f"layer_{i}"] = {
            "ln_attn": {"scale": w["ln1_s"], "bias": w["ln1_b"]},
            "ln_ffn": {"scale": w["ln2_s"], "bias": w["ln2_b"]},
            "attention": {"query": kb("wq", "bq"), "key": kb("wk", "bk"),
                          "value": kb("wv", "bv"),
                          "attn_out": kb("wo", "bo")},
            "ffn_up": {"kernel": jnp.pad(w["w_up"], ((0, 0), (0, pad))),
                       "bias": jnp.pad(w["b_up"], (0, pad))},
            "ffn_down": {"kernel": jnp.pad(w["w_down"], ((0, pad), (0, 0))),
                         "bias": w["b_down"]}}
    return params
