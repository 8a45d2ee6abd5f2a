"""Seeded weights, made by the benchmark and by nobody else.

One jitted function makes one layer's leaves in bf16 from a key; the served
model gets every layer from it (harness/server.py), and the plain reference
calls the very same function layer by layer (harness/reference.py), so both
sides hold the same bf16 values and neither takes anything the other made.

Which leaves a layer has — name -> (shape, "normal" | "scale", std) — the
configuration's family says (families/<family>/leaves.py).  The maker is
jitted once per (configuration, KIND of layer), so a pattern of 48 layers
compiles two or three programs; its cache key is the WHOLE configuration,
lists and nested keys included (``freeze``).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from . import cells


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


def freeze(cfg: dict) -> str:
    """The whole configuration as one hashable key; ``thaw`` gives it
    back with every list and nested key."""
    return json.dumps(cfg, sort_keys=True)


def thaw(frozen: str) -> dict:
    return json.loads(frozen)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: str, kind):
    cfg = thaw(frozen)
    leaves = cells.family(cfg).leaves.layer_leaves(cfg, kind)
    return jax.jit(lambda key: _make(leaves, key))


@functools.lru_cache(maxsize=None)
def _top_fn(frozen: str):
    cfg = thaw(frozen)
    leaves = cells.family(cfg).leaves.top_leaves(cfg)
    return jax.jit(lambda key: _make(leaves, key))


def layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s leaves, bf16, on the default device."""
    kind = cells.family(cfg).leaves.kind(cfg, i)
    return _layer_fn(freeze(cfg), kind)(
        jax.random.fold_in(seed_key(seed), i + 1))


def top(cfg: dict, seed: int) -> dict:
    """The leaves outside the layers (embedding, final norm, head)."""
    return _top_fn(freeze(cfg))(jax.random.fold_in(seed_key(seed), 0))
