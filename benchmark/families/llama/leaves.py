"""The llama family's leaves: name -> (shape, kind, std) of one decoder layer
and of the top, from the configuration.

Scales: kernels N(0, 1/fan_in), embedding N(0, 1/hidden) (so logits have
unit scale at every width, tied head or not), norm scales 1 + 0.1 N, QKV
biases 0.1 N (never zero: the bias path has to show in the comparison).
"""

from __future__ import annotations

import math


def dims(cfg: dict) -> dict:
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"E": E, "H": H, "KH": cfg["num_key_value_heads"], "D": E // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "bias": bool(cfg.get("attention_bias", False)),
            "tied": bool(cfg["tie_word_embeddings"])}


def n_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def kind(cfg: dict, i: int) -> str:
    """Every layer of a llama-family model is the same kind."""
    return "decoder"


def layer_leaves(cfg: dict, kind: str) -> dict:
    """name -> (shape, kind, std) of one decoder layer."""
    d = dims(cfg)
    E, H, KH, D, F = d["E"], d["H"], d["KH"], d["D"], d["F"]
    k = lambda fan_in: 1.0 / math.sqrt(fan_in)
    leaves = {
        "ln_attn": ((E,), "scale", 0.1), "ln_ffn": ((E,), "scale", 0.1),
        "wq": ((E, H, D), "normal", k(E)),
        "wk": ((E, KH, D), "normal", k(E)),
        "wv": ((E, KH, D), "normal", k(E)),
        "wo": ((H, D, E), "normal", k(E)),
        "w_gate": ((E, F), "normal", k(E)),
        "w_up": ((E, F), "normal", k(E)),
        "w_down": ((F, E), "normal", k(F)),
    }
    if d["bias"]:
        leaves.update(bq=((H, D), "normal", 0.1),
                      bk=((KH, D), "normal", 0.1),
                      bv=((KH, D), "normal", 0.1))
    return leaves


def top_leaves(cfg: dict) -> dict:
    d = dims(cfg)
    leaves = {"embed": ((d["V"], d["E"]), "normal", 1 / math.sqrt(d["E"])),
              "ln_f": ((d["E"],), "scale", 0.1)}
    if not d["tied"]:
        leaves["head"] = ((d["E"], d["V"]), "normal",
                          1 / math.sqrt(d["E"]))
    return leaves
