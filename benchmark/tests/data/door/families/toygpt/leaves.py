"""Leaves of the toy family.  A layer's kind is its entry of the list
``ffn_live``: how many FFN columns it has."""

import math


def n_layers(cfg):
    return cfg["n_layer"]


def vocab(cfg):
    return cfg["n_vocab"]


def kind(cfg, i):
    return int(cfg["ffn_live"][i])


def layer_leaves(cfg, kind):
    E, H, KH = cfg["n_embd"], cfg["n_head"], cfg["n_kv_head"]
    D, f = E // H, kind
    k = lambda fan_in: 1.0 / math.sqrt(fan_in)
    return {
        "ln1_s": ((E,), "scale", 0.1), "ln1_b": ((E,), "normal", 0.1),
        "ln2_s": ((E,), "scale", 0.1), "ln2_b": ((E,), "normal", 0.1),
        "wq": ((E, H, D), "normal", k(E)), "bq": ((H, D), "normal", 0.1),
        "wk": ((E, KH, D), "normal", k(E)), "bk": ((KH, D), "normal", 0.1),
        "wv": ((E, KH, D), "normal", k(E)), "bv": ((KH, D), "normal", 0.1),
        "wo": ((H, D, E), "normal", k(E)), "bo": ((E,), "normal", 0.1),
        "w_up": ((E, f), "normal", k(E)), "b_up": ((f,), "normal", 0.1),
        "w_down": ((f, E), "normal", k(f)), "b_down": ((E,), "normal", 0.1),
    }


def top_leaves(cfg):
    E = cfg["n_embd"]
    return {"wte": ((cfg["n_vocab"], E), "normal", 1 / math.sqrt(E)),
            "lnf_s": ((E,), "scale", 0.1), "lnf_b": ((E,), "normal", 0.1)}
