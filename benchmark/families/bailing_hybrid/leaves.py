"""The bailing_hybrid family's leaves: name -> (shape, kind, std) of one
decoder layer of each kind and of the top, from the configuration.

A layer's kind is its mixer, ``"kda"`` or ``"mla"`` (layer i is MLA where
``(i + 1) % layer_group_size == 0``), with ``"_dense"`` for the first
``first_k_dense_replace`` layers, whose feed-forward is a dense SwiGLU.

``harness/weights._make`` knows ``normal`` and ``scale`` only, so what the
delta rule's decay needs in a RANGE is a raw N(0, 1) leaf here (``f_raw`` a
channel, ``a_raw`` a head) that ``reference.kda_consts`` maps, identically
for the served model (``model.place``) and for the reference: ``exp(A_log)``
uniform in 1 to 2, and ``dt_bias`` such that a channel's decay rate ``-g =
5 sigmoid(exp(A_log) dt_bias)`` is log-uniform in 0.001 to 0.5 a position:
a channel remembers from two to a thousand positions.  With ``dt_bias`` 0 the
rate would be 2.5 a position for every channel (a state forgotten within a
token) and no comparison could see one lost.  ``w_f`` is a quarter of
1/fan_in, so a token's own part moves the rate by about e^0.25 around its
channel's value.

Scales otherwise, as families/keye/leaves.py has them and for its reasons:
kernels N(0, 1/fan_in); the projections that write into the residual stream
(``w_o``, ``wo``) at half of it and the down projections of the shared
expert's and the dense layers' SwiGLU at a quarter; norm scales 1 + 0.1 N;
an embedding of N(0, 1) a element and an untied head of N(0, 1/hidden), so
the stream is the token's own vector with the layers as corrections and the
logits have about unit spread.

The router is N(0, 1/fan_in): logits of unit spread.  Its scores are
sigmoids and its gates 2.5 s / sum(s) over the chosen 8, about 0.3 each
whatever the router's scale (a larger spread only pushes the chosen scores
towards 1).  The selection bias is N(0, 0.01^2).  The 8th and the 9th
candidate of a token lie within bf16's rounding of the normed input in a
few percent of a layer's tokens (``flips.py``: 3 to 6% of token-layers on
the CPU at hidden 512), and NO scale of the router or the bias makes that
rare: the rounding moves a score in proportion to the scores' own spread
(four times the router's scale: 4.9% against 6.1%; ten times the bias's:
4.4%).  What a scale decides is how far such a flip moves the token: the
routed experts' down projection (``w_out``) is a SIXTEENTH of 1/fan_in, so
one expert swapped at a gate of 0.3 moves the stream by about 1.6% of its
size, not the 6.6% of a quarter, where a flip cascaded into the next
layers' choices (6% of token-layers flipped, 12% in the last) and
``served_gap_max``, a maximum over some 3500 tokens, read the largest
cascade: 0.115 to 0.270 on the chip where the fp8 control read 0.449 and
0.524.  On the CPU at hidden 512 (bf16 program, 2 x 512 and 2 x 1024
positions) the program's own gap against the same program forced to the
reference's choices read 0.163 / 0.006 at a quarter and 0.031 / 0.010 at
a sixteenth, the fp8 control 0.49 and 0.25: the gap is the choices', and
a sixteenth separates the program from the control by eight, a quarter by
three.  A router that drops its bias reads 0.088 there (72% of
token-layers flipped).  PERF.md, section 6, has the chip's readings.
"""

from __future__ import annotations

import math

BIAS_STD = 0.01


def dims(cfg: dict) -> dict:
    H, dk = cfg["num_attention_heads"], cfg["head_dim"]
    KH = cfg["num_kv_heads_for_linear_attn"] or H
    if KH != H:
        raise ValueError("a KDA layer has as many key heads as heads")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"] \
            or cfg["rotary_dim"] != cfg["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is nope + rope, rotary_dim rope")
    pub = cfg.get("published") or {}
    X = pub.get("num_experts", cfg["num_experts"])
    if X % cfg["n_group"]:
        raise ValueError("num_experts is not a whole number of groups")
    return {"E": cfg["hidden_size"], "H": H, "dk": dk, "P": H * dk,
            "K": cfg["short_conv_kernel_size"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "Lr": cfg["kv_lora_rank"],
            "X": X, "Xh": cfg["num_experts"],
            "X0": cfg.get("first_local_expert", 0),
            "Kx": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_shared_expert_intermediate_size"]
            * cfg["num_shared_experts"],
            "Fd": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "G": cfg["n_group"], "Gk": cfg["topk_group"],
            "dense": cfg["first_k_dense_replace"],
            "period": cfg["layer_group_size"],
            "L": cfg["num_hidden_layers"]}


def n_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def mixer(cfg: dict, i: int) -> str:
    """``"mla"`` where ``(i + 1) % layer_group_size == 0``, else
    ``"kda"``."""
    return "mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"


def kind(cfg: dict, i: int) -> str:
    """The mixer, ``_dense`` in the first ``first_k_dense_replace``
    layers."""
    dense = i < cfg["first_k_dense_replace"]
    return mixer(cfg, i) + ("_dense" if dense else "")


def layer_leaves(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    E, H, P = d["E"], d["H"], d["P"]
    k = lambda fan_in: 1.0 / math.sqrt(fan_in)
    leaves = {"ln_mixer": ((E,), "scale", 0.1),
              "ln_ffn": ((E,), "scale", 0.1)}
    mix, _, dense = kind.partition("_")
    if dense:
        leaves.update({
            "mlp_in": ((E, 2 * d["Fd"]), "normal", k(E)),
            "mlp_out": ((d["Fd"], E), "normal", 0.25 * k(d["Fd"]))})
    else:
        leaves.update({
            "router": ((E, d["X"]), "normal", k(E)),
            "router_bias": ((d["X"],), "normal", BIAS_STD),
            "w_in": ((d["Xh"], E, 2 * d["F"]), "normal", k(E)),
            "w_out": ((d["Xh"], d["F"], E), "normal", k(d["F"]) / 16),
            "sh_in": ((E, 2 * d["Fs"]), "normal", k(E)),
            "sh_out": ((d["Fs"], E), "normal", 0.25 * k(d["Fs"]))})
    if mix == "kda":
        leaves.update({
            "w_qkv": ((E, 3 * P), "normal", k(E)),
            "w_f": ((E, P), "normal", 0.25 * k(E)),
            "w_b": ((E, H), "normal", k(E)),
            "w_g": ((E, P), "normal", k(E)),
            "conv_w": ((d["K"], 3 * P), "normal", k(d["K"])),
            "f_raw": ((P,), "normal", 1.0),
            "a_raw": ((H,), "normal", 1.0),
            "norm": ((d["dk"],), "scale", 0.1),
            "w_o": ((P, E), "normal", 0.5 * k(P))})
    elif mix == "mla":
        nope, rope, vd, Lr = d["nope"], d["rope"], d["vd"], d["Lr"]
        leaves.update({
            "wq": ((E, H, nope + rope), "normal", k(E)),
            "wkva": ((E, Lr + rope), "normal", k(E)),
            "kv_norm": ((Lr,), "scale", 0.1),
            "wkvb": ((Lr, H, nope + vd), "normal", k(Lr)),
            "w_gate": ((E, H), "normal", k(E)),
            "wo": ((H, vd, E), "normal", 0.5 * k(H * vd))})
    else:
        raise ValueError(f"layer kind {kind!r}: 'kda' or 'mla', "
                         f"'_dense' or not")
    return leaves


def top_leaves(cfg: dict) -> dict:
    d = dims(cfg)
    return {"embed": ((d["V"], d["E"]), "normal", 1.0),
            "ln_f": ((d["E"],), "scale", 0.1),
            "head": ((d["E"], d["V"]), "normal", 1.0 / math.sqrt(d["E"]))}
