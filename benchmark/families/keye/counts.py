"""Operations and bytes the keye family's mathematics needs, from the
configuration's shapes alone.  Every layer is the one kind.

A token at context c (itself included) counts its projections, the
indexer's projections, the router, its K experts, ``2 * IH * ID * c`` for
its index scores and ``4 * H * D * min(c, topk)`` for the attention over
the positions it selected."""

from __future__ import annotations

import math

from . import leaves


def dense_params(cfg: dict) -> int:
    """Matmul parameters of one layer OUTSIDE its experts that a token is
    multiplied through: q, k, v, o, the indexer's three projections and
    the router (the norms' scales are not in it)."""
    d = leaves.dims(cfg)
    E, H, KH, D = d["E"], d["H"], d["KH"], d["D"]
    return (E * H * D + 2 * E * KH * D + H * D * E
            + E * (d["IH"] * d["ID"] + d["ID"] + d["IH"]) + E * d["X"])


def expert_params(cfg: dict) -> int:
    """Parameters of ONE expert: gate, up and down."""
    d = leaves.dims(cfg)
    return 3 * d["E"] * d["F"]


def _attended(cfg: dict, first: int, count: int) -> tuple:
    """(sum of c, sum of min(c, topk)) over the contexts c = first + 1 ..
    first + count."""
    k = leaves.dims(cfg)["topk"]
    lo, hi = first + 1, first + count
    total = (lo + hi) * count // 2
    under = max(0, min(hi, k) - lo + 1)         # contexts <= topk
    capped = (lo + lo + under - 1) * under // 2 + (count - under) * k
    return total, capped


def span_flops(cfg: dict, first: int, count: int) -> float:
    """Trunk FLOPs of ``count`` consecutive tokens at positions ``first``,
    ``first + 1``, ... (a token at position p has p + 1 in context)."""
    d = leaves.dims(cfg)
    ctx, sel = _attended(cfg, first, count)
    per_token = 2.0 * (dense_params(cfg) + d["K"] * expert_params(cfg))
    return d["L"] * (per_token * count + 2.0 * d["IH"] * d["ID"] * ctx
                     + 4.0 * d["H"] * d["D"] * sel)


def token_flops(cfg: dict, ctx: int) -> float:
    """Trunk FLOPs of one token with ``ctx`` positions in context."""
    return span_flops(cfg, ctx - 1, 1)


def head_flops(cfg: dict) -> float:
    d = leaves.dims(cfg)
    return 2.0 * d["E"] * d["V"]


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes of one token: K and V of KH x D and one index key of
    ID, in every layer."""
    d = leaves.dims(cfg)
    return d["L"] * (2 * d["KH"] * d["D"] + d["ID"]) * kv_itemsize


def kv_bytes(cfg: dict, ctx: int, kv_itemsize: int = 2) -> int:
    return ctx * kv_bytes_per_token(cfg, kv_itemsize)


def dsa_decode_bytes(cfg: dict, ctx: int, kv_itemsize: int = 2) -> int:
    """Cache bytes one decode step of one row has to read, all layers:
    the index keys of its ``ctx`` positions, and K and V of the
    min(ctx, topk) it selects."""
    d = leaves.dims(cfg)
    return d["L"] * kv_itemsize * (
        ctx * d["ID"] + min(ctx, d["topk"]) * 2 * d["KH"] * d["D"])


def paged_attn_bytes(cfg: dict, ctx: int, block_size: int,
                     kv_itemsize: int = 2) -> int:
    """As :func:`dsa_decode_bytes`, the index keys by whole blocks (the
    read goes through the table a block at a time); the selected K/V rows
    are read token by token."""
    d = leaves.dims(cfg)
    held = -(-ctx // block_size) * block_size
    return d["L"] * kv_itemsize * (
        held * d["ID"] + min(ctx, d["topk"]) * 2 * d["KH"] * d["D"])


def moe_weight_bytes(cfg: dict, assignments: int, itemsize: int = 2) -> int:
    """Expert weights one step has to read, all layers, when
    ``assignments`` (tokens x K) fall evenly over the X experts: the
    expected number of experts that get at least one, X * (1 - (1 -
    1/X)^assignments), each read whole."""
    d = leaves.dims(cfg)
    hit = d["X"] * (1.0 - (1.0 - 1.0 / d["X"]) ** assignments)
    return int(d["L"] * hit * expert_params(cfg) * itemsize)


def n_params(cfg: dict) -> int:
    """Parameters held on this chip."""
    count = lambda ls: sum(math.prod(s) for s, _, _ in ls.values())
    return count(leaves.top_leaves(cfg)) + cfg["num_hidden_layers"] * count(
        leaves.layer_leaves(cfg, leaves.kind(cfg, 0)))


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return n_params(cfg) * itemsize
