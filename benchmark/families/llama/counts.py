"""Operations and bytes the llama family's mathematics needs, from the
configuration's shapes alone — never from what XLA compiled (recompute in,
custom calls out).  Every layer is the one kind, so a sum over layers is
``L`` times one layer."""

from __future__ import annotations

import math

from . import leaves


def layer_matmul_params(cfg: dict) -> int:
    """Parameters of one layer that a token is multiplied through."""
    d = leaves.dims(cfg)
    E, H, KH, D, F = d["E"], d["H"], d["KH"], d["D"], d["F"]
    return E * H * D + 2 * E * KH * D + H * D * E + 3 * E * F


def token_flops(cfg: dict, ctx: int) -> float:
    """FLOPs of one token's pass through the trunk while it attends ``ctx``
    positions (itself included): 2 per matmul parameter, plus QK^T and PV,
    4 * ctx * heads * head_dim, per layer.  The head is not in it."""
    d = leaves.dims(cfg)
    return d["L"] * (2.0 * layer_matmul_params(cfg)
                     + 4.0 * ctx * d["H"] * d["D"])


def span_flops(cfg: dict, first: int, count: int) -> float:
    """Trunk FLOPs of ``count`` consecutive tokens at positions ``first``,
    ``first + 1``, ... (a token at position p attends p + 1 positions)."""
    d = leaves.dims(cfg)
    ctx_sum = count * first + count * (count + 1) // 2
    return d["L"] * (2.0 * layer_matmul_params(cfg) * count
                     + 4.0 * ctx_sum * d["H"] * d["D"])


def head_flops(cfg: dict) -> float:
    """The vocabulary head, once per sampled position."""
    d = leaves.dims(cfg)
    return 2.0 * d["E"] * d["V"]


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    d = leaves.dims(cfg)
    return 2 * d["L"] * d["KH"] * d["D"] * kv_itemsize


def kv_bytes(cfg: dict, ctx: int, kv_itemsize: int = 2) -> int:
    """Cache bytes one row holds at ``ctx`` positions: every layer keeps
    every position."""
    return ctx * kv_bytes_per_token(cfg, kv_itemsize)


def paged_attn_bytes(cfg: dict, ctx: int, block_size: int,
                     kv_itemsize: int = 2) -> int:
    """Bytes the paged-attention read of one query row must move in one
    step, all layers: every block that holds one of its ``ctx`` positions,
    K and V."""
    blocks = -(-ctx // block_size)
    return blocks * block_size * kv_bytes_per_token(cfg, kv_itemsize)


def n_params(cfg: dict) -> int:
    """Parameters held on this chip."""
    count = lambda ls: sum(math.prod(s) for s, _, _ in ls.values())
    return count(leaves.top_leaves(cfg)) + cfg["num_hidden_layers"] * count(
        leaves.layer_leaves(cfg, leaves.kind(cfg, 0)))


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return n_params(cfg) * itemsize
