"""The granite_hybrid family's leaves: name -> (shape, kind, std) of one
decoder layer of either kind and of the top, from the configuration.

``harness/weights._make`` knows ``normal`` (N(0, std)) and ``scale`` (1 +
N(0, std)) only, so what the state-space recurrence needs in a RANGE is a
raw N(0, 1) leaf here (``dt_raw``, ``a_raw``) that ``reference.mixer_consts``
maps, identically for the served model (``model.place``) and for the
reference: ``dt_bias`` so that softplus(dt_bias) is log-uniform in the
source's own 0.001 to 0.1 (``time_step_min`` / ``time_step_max``), ``A``
uniform in 1 to 16 (Mamba-2's initial range; the local code's
``arange(1, 129)`` stands in for loaded weights).  A head then forgets by
exp(-dt A) a token: between a step (0.2) and a thousandth of one, so a state
remembers from one to a thousand tokens, most heads tens to hundreds.  With
``dt_bias`` 0 and ``A_log`` 1 a state would forget within three tokens and
the comparison could not see one lost.  The fused input projection is three
leaves (``w_z``, ``w_xbc``, ``w_dt``) so that the ``dt`` columns can be
smaller: at a quarter of 1/fan_in the token's own part moves dt by a
factor of e^0.25 around its head's value, inside the range.

Scales otherwise: kernels N(0, 1/fan_in); norm scales 1 + 0.1 N; the
convolution's taps N(0, 1/K), its bias 0.1 N (never zero: the one bias of
the model has to show); ``D`` 1 + 0.1 N.  The router's kernel is FOUR
times 1/fan_in, as in families/keye/leaves.py and for its reason (the 10th
and the 11th expert of a token lie within bf16's rounding otherwise).

**The embedding is small: N(0, (c / (12 sqrt(E)))^2) with c =
``EMBED_C``.**  The head is tied and the stream starts as 12 x the token's
own row, so the token's OWN logit is (12 |e|^2 / 16 R) where R is the final
stream's RMS, against sqrt(E) sigma_e / 16 for every other row: 12 sqrt(E)
sigma_e / R standard deviations above them.  At E = 4096 and an embedding
as large as the layers' sum (R ~ 12 sigma_e) that is 64: the model would
answer every token with itself, whatever the layers, the state or the
precision did, and no comparison could fail.  With the layers' twenty
updates of 0.22 x (unit RMS) summing to R ~ 1 and c = 2 the token's own
logit stands two deviations over the rest: one candidate among the largest
of 50176, not the winner.  A row's identity reaches the routers through the
first layer's own outputs (the convolution's window and the skip term D x
are the token's and its three predecessors'), not through the raw row.
"""

from __future__ import annotations

import math

EMBED_C = 2.0


def dims(cfg: dict) -> dict:
    HS, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if HS * P != cfg["mamba_expand"] * cfg["hidden_size"] \
            or cfg["mamba_n_groups"] != 1:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand "
                         "x hidden_size, in one group")
    H = cfg["num_attention_heads"]
    return {"E": cfg["hidden_size"], "H": H,
            "KH": cfg["num_key_value_heads"],
            "D": cfg["hidden_size"] // H, "HS": HS, "P": P,
            "N": cfg["mamba_d_state"], "K": cfg["mamba_d_conv"],
            "I": HS * P, "C": HS * P + 2 * cfg["mamba_d_state"],
            "Q": cfg["mamba_chunk_size"],
            "X": (cfg.get("published") or {}).get(
                "num_local_experts", cfg["num_local_experts"]),
            "Xh": cfg["num_local_experts"],
            "X0": cfg.get("first_local_expert", 0),
            "Kx": cfg["num_experts_per_tok"], "F": cfg["intermediate_size"],
            "Fs": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def n_layers(cfg: dict) -> int:
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types has not num_hidden_layers entries")
    return cfg["num_hidden_layers"]


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def kind(cfg: dict, i: int) -> str:
    """``"mamba"`` or ``"attention"``, as ``layer_types`` has it."""
    return cfg["layer_types"][i]


def layer_leaves(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    E, I, C, N, HS = d["E"], d["I"], d["C"], d["N"], d["HS"]
    k = lambda fan_in: 1.0 / math.sqrt(fan_in)
    leaves = {
        "ln_mixer": ((E,), "scale", 0.1), "ln_ffn": ((E,), "scale", 0.1),
        "router": ((E, d["X"]), "normal", 4.0 * k(E)),
        "w_in": ((d["Xh"], E, 2 * d["F"]), "normal", k(E)),
        "w_out": ((d["Xh"], d["F"], E), "normal", k(d["F"])),
        "sh_in": ((E, 2 * d["Fs"]), "normal", k(E)),
        "sh_out": ((d["Fs"], E), "normal", k(d["Fs"])),
    }
    if kind == "mamba":
        leaves.update({
            "w_z": ((E, I), "normal", k(E)),
            "w_xbc": ((E, C), "normal", k(E)),
            "w_dt": ((E, HS), "normal", 0.25 * k(E)),
            "conv_w": ((d["K"], C), "normal", k(d["K"])),
            "conv_b": ((C,), "normal", 0.1),
            "dt_raw": ((HS,), "normal", 1.0),
            "a_raw": ((HS,), "normal", 1.0),
            "D": ((HS,), "scale", 0.1),
            "norm": ((I,), "scale", 0.1),
            "w_o": ((I, E), "normal", k(I))})
    elif kind == "attention":
        H, KH, Dh = d["H"], d["KH"], d["D"]
        leaves.update({
            "wq": ((E, H, Dh), "normal", k(E)),
            "wk": ((E, KH, Dh), "normal", k(E)),
            "wv": ((E, KH, Dh), "normal", k(E)),
            "wo": ((H, Dh, E), "normal", k(H * Dh))})
    else:
        raise ValueError(f"layer kind {kind!r}: 'mamba' or 'attention'")
    return leaves


def top_leaves(cfg: dict) -> dict:
    d = dims(cfg)
    std = EMBED_C / (cfg["embedding_multiplier"] * math.sqrt(d["E"]))
    return {"embed": ((d["V"], d["E"]), "normal", std),
            "ln_f": ((d["E"],), "scale", 0.1)}
