"""Operations and bytes the model's mathematics needs, from the
configuration's shapes alone — never from what XLA compiled (recompute in,
custom calls out).  And the table of peaks."""

from __future__ import annotations

import json
import os

from . import weights as W


def peak(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peak for device kind {device_kind!r}: a device "
                       f"that is not in peaks.json is an error, not a "
                       f"default (has: {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    """Parameters of one layer that a token is multiplied through."""
    d = W.dims(cfg)
    E, H, KH, D, F = d["E"], d["H"], d["KH"], d["D"], d["F"]
    return E * H * D + 2 * E * KH * D + H * D * E + 3 * E * F


def token_flops(cfg: dict, ctx: int) -> float:
    """FLOPs of one token's pass through the trunk while it attends ``ctx``
    positions (itself included): 2 per matmul parameter, plus QK^T and PV,
    4 * ctx * heads * head_dim, per layer.  The head is not in it."""
    d = W.dims(cfg)
    return d["L"] * (2.0 * layer_matmul_params(cfg)
                     + 4.0 * ctx * d["H"] * d["D"])


def span_flops(cfg: dict, first: int, count: int) -> float:
    """Trunk FLOPs of ``count`` consecutive tokens at positions ``first``,
    ``first + 1``, ... (a token at position p attends p + 1 positions)."""
    d = W.dims(cfg)
    ctx_sum = count * first + count * (count + 1) // 2
    return d["L"] * (2.0 * layer_matmul_params(cfg) * count
                     + 4.0 * ctx_sum * d["H"] * d["D"])


def head_flops(cfg: dict) -> float:
    """The vocabulary head, once per sampled position."""
    d = W.dims(cfg)
    return 2.0 * d["E"] * d["V"]


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    d = W.dims(cfg)
    return 2 * d["L"] * d["KH"] * d["D"] * kv_itemsize


def paged_attn_bytes(cfg: dict, ctx: int, block_size: int,
                     kv_itemsize: int = 2) -> int:
    """Bytes the paged-attention read of one query row must move in one
    step, all layers: every block that holds one of its ``ctx`` positions,
    K and V."""
    blocks = -(-ctx // block_size)
    return blocks * block_size * kv_bytes_per_token(cfg, kv_itemsize)


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return W.n_params(cfg) * itemsize
