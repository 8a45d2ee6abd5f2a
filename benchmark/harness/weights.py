"""Seeded weights, made by the benchmark and by nobody else.

One jitted function makes one layer's leaves in bf16 from a key; the served
model gets every layer from it (harness/server.py), and the plain reference
calls the very same function layer by layer (harness/reference.py), so both
sides hold the same bf16 values and neither takes anything the other made.

Scales: kernels N(0, 1/fan_in), embedding N(0, 1/hidden) (so logits have
unit scale at every width, tied head or not), norm scales 1 + 0.1 N, QKV
biases 0.1 N (never zero: the bias path has to show in the comparison).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"E": E, "H": H, "KH": cfg["num_key_value_heads"], "D": E // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "bias": bool(cfg.get("attention_bias", False)),
            "tied": bool(cfg["tie_word_embeddings"])}


def layer_leaves(cfg: dict) -> dict:
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


def _make(leaves: dict, key):
    out = {}
    for j, (name, (shape, kind, std)) in enumerate(sorted(leaves.items())):
        x = jax.random.normal(jax.random.fold_in(key, j), shape,
                              jnp.float32) * std
        out[name] = (1.0 + x if kind == "scale" else x).astype(jnp.bfloat16)
    return out


def seed_key(seed: int):
    """Any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_items: tuple):
    leaves = layer_leaves(dict(cfg_items))
    return jax.jit(lambda key: _make(leaves, key))


@functools.lru_cache(maxsize=None)
def _top_fn(cfg_items: tuple):
    leaves = top_leaves(dict(cfg_items))
    return jax.jit(lambda key: _make(leaves, key))


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s leaves, bf16, on the default device."""
    return _layer_fn(_items(cfg))(jax.random.fold_in(seed_key(seed), i + 1))


def top(cfg: dict, seed: int) -> dict:
    """Embedding, final norm and (untied) head."""
    return _top_fn(_items(cfg))(jax.random.fold_in(seed_key(seed), 0))


def n_params(cfg: dict) -> int:
    count = lambda ls: sum(math.prod(s) for s, _, _ in ls.values())
    return count(top_leaves(cfg)) + cfg["num_hidden_layers"] * count(
        layer_leaves(cfg))
