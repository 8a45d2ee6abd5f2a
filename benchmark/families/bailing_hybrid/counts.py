"""Operations and bytes the bailing_hybrid family's mathematics needs, from
the configuration's shapes alone: what THIS chip holds and computes (the
held experts, the slice of the vocabulary), each summed over the layers by
kind.

A token counts, in a KDA layer, its projections (in and out), the
convolution's K taps, and the delta rule: seven operations an element of
the state (decay; the prediction ``S^T k``, two; the rank-one update, two;
the read ``S^T q``, two); in an MLA layer its projections as the program
runs them ABSORBED (the query into the latent through ``W_kvb,k``, the
latent mean out through ``W_kvb,v``: the same multiplies as the weights
hold) and ``2 H (2 Lr + rope) ctx`` for the scores and the latent mean; in a
dense layer its SwiGLU; in an expert layer the router over all X experts,
the shared expert, and the held share of its Kx experts (Kx Xh / X of them
in the mean)."""

from __future__ import annotations

import math

from . import leaves


def _kinds(cfg: dict) -> list:
    return [leaves.kind(cfg, i) for i in range(leaves.n_layers(cfg))]


def _count(cfg: dict, mixer: str = "", dense=None) -> int:
    """Layers of a mixer (any: ``""``) that are dense or not (any:
    None)."""
    return sum(k.startswith(mixer) and (dense is None
                                        or k.endswith("_dense") == dense)
               for k in _kinds(cfg))


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert: gate, up and down."""
    d = leaves.dims(cfg)
    return 3 * d["E"] * d["F"]


def held_expert_slots(cfg: dict) -> int:
    """Experts held here, over all expert layers."""
    return _count(cfg, dense=False) * leaves.dims(cfg)["Xh"]


def _mixer_params(cfg: dict, mixer: str) -> int:
    d = leaves.dims(cfg)
    E, H, P = d["E"], d["H"], d["P"]
    if mixer == "kda":
        return E * (5 * P + H) + P * E
    nope, rope, vd, Lr = d["nope"], d["rope"], d["vd"], d["Lr"]
    return E * H * (nope + rope) + E * (Lr + rope) \
        + Lr * H * (nope + vd) + E * H + H * vd * E


def _ffn_flops(cfg: dict, dense: bool) -> float:
    d = leaves.dims(cfg)
    if dense:
        return 2.0 * 3 * d["E"] * d["Fd"]
    held_picks = d["Kx"] * d["Xh"] / d["X"]
    return 2.0 * (d["E"] * d["X"] + 3 * d["E"] * d["Fs"]
                  + held_picks * expert_params(cfg))


def span_flops(cfg: dict, first: int, count: int) -> float:
    """Trunk FLOPs of ``count`` consecutive tokens at positions ``first``,
    ``first + 1``, ... (a token at position p has p + 1 in context)."""
    d = leaves.dims(cfg)
    n_kda, n_mla = _count(cfg, "kda"), _count(cfg, "mla")
    ctx = (2 * first + 1 + count) * count // 2
    kda = 2.0 * _mixer_params(cfg, "kda") + 2.0 * d["K"] * 3 * d["P"] \
        + 7.0 * d["H"] * d["dk"] * d["dk"]
    mla = 2.0 * _mixer_params(cfg, "mla")
    ffn = _count(cfg, dense=True) * _ffn_flops(cfg, True) \
        + _count(cfg, dense=False) * _ffn_flops(cfg, False)
    return count * (n_kda * kda + n_mla * mla + ffn) \
        + n_mla * 2.0 * d["H"] * (2 * d["Lr"] + d["rope"]) * ctx


def token_flops(cfg: dict, ctx: int) -> float:
    """Trunk FLOPs of one token with ``ctx`` positions in context."""
    return span_flops(cfg, ctx - 1, 1)


def head_flops(cfg: dict) -> float:
    d = leaves.dims(cfg)
    return 2.0 * d["E"] * d["V"]


def kv_bytes(cfg: dict, ctx: int, kv_itemsize: int = 2) -> int:
    """Latent cache bytes a row holds at ``ctx`` positions: ``c`` and
    ``k_pe`` a token in every MLA layer (the KDA layers' cache does not
    grow: :func:`ssm_state_bytes`)."""
    d = leaves.dims(cfg)
    return ctx * _count(cfg, "mla") * (d["Lr"] + d["rope"]) * kv_itemsize


def paged_attn_bytes(cfg: dict, ctx: int, block_size: int,
                     kv_itemsize: int = 2) -> int:
    """Latent bytes one decode step of one row reads, by whole blocks."""
    return kv_bytes(cfg, -(-ctx // block_size) * block_size, kv_itemsize)


def ssm_state_bytes(cfg: dict, conv_itemsize: int = 2) -> int:
    """Recurrent state a ROW holds, whatever its context: a float32 state
    of H x dk x dk and the convolution's last K - 1 inputs of q, k and v,
    in every KDA layer."""
    d = leaves.dims(cfg)
    return _count(cfg, "kda") * (d["H"] * d["dk"] * d["dk"] * 4
                                 + (d["K"] - 1) * 3 * d["P"] * conv_itemsize)


def moe_weight_bytes(cfg: dict, assignments: float,
                     itemsize: int = 2) -> int:
    """Held expert weights one pass has to read, all layers, when
    ``assignments`` picks fall a layer in the sense of the readers (a
    tick's picks over ALL the model's layers, dense ones included, /
    ``leaves.n_layers``): an expert layer then has ``assignments *
    n_layers / expert layers`` picks over ALL X experts, and the Xh / X of
    them that land here hit Xh (1 - (1 - 1/Xh)^n) held experts in the mean,
    each read whole."""
    d = leaves.dims(cfg)
    n_moe = _count(cfg, dense=False)
    if not n_moe:
        return 0
    here = assignments * leaves.n_layers(cfg) / n_moe * d["Xh"] / d["X"]
    hit = d["Xh"] * (1.0 - (1.0 - 1.0 / d["Xh"]) ** here)
    return int(n_moe * hit * expert_params(cfg) * itemsize)


def n_params(cfg: dict) -> int:
    """Parameters held on this chip."""
    count = lambda ls: sum(math.prod(s) for s, _, _ in ls.values())
    return count(leaves.top_leaves(cfg)) + sum(
        count(leaves.layer_leaves(cfg, k)) for k in _kinds(cfg))


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return n_params(cfg) * itemsize


def pass_weight_bytes(cfg: dict, assignments: float,
                      itemsize: int = 2) -> int:
    """Weights ONE pass of the model over a tick's tokens reads: all that
    is neither a routed expert nor the embedding (the untied head whole;
    the embedding gives a row a token), and the held experts that
    ``assignments`` picks a layer (:func:`moe_weight_bytes`) hit."""
    d = leaves.dims(cfg)
    dense = n_params(cfg) - held_expert_slots(cfg) * expert_params(cfg) \
        - d["V"] * d["E"]
    return dense * itemsize + moe_weight_bytes(cfg, assignments, itemsize)
