"""Operations and bytes the granite_hybrid family's mathematics needs, from
the configuration's shapes alone: what THIS chip holds and computes (the
held experts, the slice of the vocabulary), each summed over the layers by
kind.

A token counts, in a state-space layer, its projections (in and out), the
convolution's K taps, and the recurrence: three operations an element of
the state for the update (decay, input, sum) and two for the read ``h . C``;
in an attention layer its four projections and ``4 H D ctx`` for the
attention; in every layer the router over all X experts, the shared expert,
and the held share of its Kx experts (Kx Xh / X of them in the mean)."""

from __future__ import annotations

import math

from . import leaves


def _layers(cfg: dict) -> tuple:
    """(state-space layers, attention layers)"""
    kinds = [leaves.kind(cfg, i) for i in range(leaves.n_layers(cfg))]
    return kinds.count("mamba"), kinds.count("attention")


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert: gate, up and down."""
    d = leaves.dims(cfg)
    return 3 * d["E"] * d["F"]


def held_expert_slots(cfg: dict) -> int:
    """Experts held here, over all layers."""
    return leaves.n_layers(cfg) * leaves.dims(cfg)["Xh"]


def _mixer_params(cfg: dict, kind: str) -> int:
    d = leaves.dims(cfg)
    if kind == "mamba":
        return d["E"] * (d["I"] + d["C"] + d["HS"]) + d["I"] * d["E"]
    return 2 * d["E"] * d["H"] * d["D"] + 2 * d["E"] * d["KH"] * d["D"]


def _ffn_flops(cfg: dict) -> float:
    """Router, shared expert and the held share of a token's experts."""
    d = leaves.dims(cfg)
    held_picks = d["Kx"] * d["Xh"] / d["X"]
    return 2.0 * (d["E"] * d["X"] + 3 * d["E"] * d["Fs"]
                  + held_picks * expert_params(cfg))


def span_flops(cfg: dict, first: int, count: int) -> float:
    """Trunk FLOPs of ``count`` consecutive tokens at positions ``first``,
    ``first + 1``, ... (a token at position p has p + 1 in context)."""
    d = leaves.dims(cfg)
    n_ssm, n_att = _layers(cfg)
    ctx = (2 * first + 1 + count) * count // 2
    ssm = 2.0 * _mixer_params(cfg, "mamba") + 2.0 * d["K"] * d["C"] \
        + 5.0 * d["HS"] * d["P"] * d["N"]
    att = 2.0 * _mixer_params(cfg, "attention")
    return count * (n_ssm * ssm + n_att * att
                    + (n_ssm + n_att) * _ffn_flops(cfg)) \
        + n_att * 4.0 * d["H"] * d["D"] * ctx


def token_flops(cfg: dict, ctx: int) -> float:
    """Trunk FLOPs of one token with ``ctx`` positions in context."""
    return span_flops(cfg, ctx - 1, 1)


def head_flops(cfg: dict) -> float:
    d = leaves.dims(cfg)
    return 2.0 * d["E"] * d["V"]


def kv_bytes(cfg: dict, ctx: int, kv_itemsize: int = 2) -> int:
    """K/V bytes a row holds at ``ctx`` positions: the attention layers'
    alone (the state-space layers' cache does not grow:
    :func:`ssm_state_bytes`)."""
    d = leaves.dims(cfg)
    return ctx * _layers(cfg)[1] * 2 * d["KH"] * d["D"] * kv_itemsize


def paged_attn_bytes(cfg: dict, ctx: int, block_size: int,
                     kv_itemsize: int = 2) -> int:
    """K/V bytes one decode step of one row reads, by whole blocks."""
    return kv_bytes(cfg, -(-ctx // block_size) * block_size, kv_itemsize)


def ssm_state_bytes(cfg: dict, conv_itemsize: int = 2) -> int:
    """Recurrent state a ROW holds, whatever its context: a float32 state
    of HS x P x N and the convolution's last K - 1 inputs, in every
    state-space layer."""
    d = leaves.dims(cfg)
    return _layers(cfg)[0] * (d["HS"] * d["P"] * d["N"] * 4
                              + (d["K"] - 1) * d["C"] * conv_itemsize)


def moe_weight_bytes(cfg: dict, assignments: float,
                     itemsize: int = 2) -> int:
    """Held expert weights one pass has to read, all layers, when
    ``assignments`` picks a layer (tokens x Kx, over ALL X experts) fall
    evenly: the Xh / X of them that land here hit Xh (1 - (1 - 1/Xh)^n)
    held experts in the mean, each read whole."""
    d = leaves.dims(cfg)
    here = assignments * d["Xh"] / d["X"]
    hit = d["Xh"] * (1.0 - (1.0 - 1.0 / d["Xh"]) ** here)
    return int(leaves.n_layers(cfg) * hit * expert_params(cfg) * itemsize)


def n_params(cfg: dict) -> int:
    """Parameters held on this chip."""
    count = lambda ls: sum(math.prod(s) for s, _, _ in ls.values())
    return count(leaves.top_leaves(cfg)) + sum(
        count(leaves.layer_leaves(cfg, leaves.kind(cfg, i)))
        for i in range(leaves.n_layers(cfg)))


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return n_params(cfg) * itemsize


def pass_weight_bytes(cfg: dict, assignments: float,
                      itemsize: int = 2) -> int:
    """Weights ONE pass of the model over a tick's tokens reads: all that
    is not a routed expert (the tied embedding whole, for the head), and
    the held experts that ``assignments`` picks a layer hit."""
    dense = n_params(cfg) - held_expert_slots(cfg) * expert_params(cfg)
    return dense * itemsize + moe_weight_bytes(cfg, assignments, itemsize)
