"""The keye family's leaves: name -> (shape, kind, std) of one decoder layer
and of the top, from the configuration.

Scales: kernels N(0, 1/fan_in) but for the two that write into the residual
stream and the router's (below); norm scales 1 + 0.1 N; the index key's LayerNorm bias 0.1 N
(never zero: the bias has to show in the comparison).  The q/k norms give
every query and key unit-RMS elements, so attention logits have about unit
spread at the head width of 128: a query's weight is not flat over its
context, and which positions the selection keeps shows in the output.

The residual stream is the EMBEDDING's, with the layers as corrections to it
(what a trained decoder's is): embedding N(0, 1) a element (the head is
untied, so the logits' unit scale is the head's N(0, 1/hidden) to keep),
``wo`` at half and ``w_down`` at a quarter of 1/fan_in.  With the llama
family's embedding of N(0, 1/hidden) the first layer's outputs (0.05 to 0.6 a
element) drowned the token's own vector (0.02): every token's hidden state
pointed the same way by the third layer (mean cosine 0.57), all tokens chose
the same 8 of 128 experts (``moe_load_max_over_mean`` read 15.4 of a possible
16; my chip run, PR 30), and the bf16 program sat as far from the float32
reference (``served_gap_max`` 1.2 to 2.1) as the fp8 control did (0.7 to 2.1).
``wo`` at one the stream drifts the same way more slowly (expert load 4.5
over the mean by the sixth layer, CPU at hidden 256), so it stays at half.

The router's kernel is FOUR times 1/fan_in.  Router logits of unit spread give
a token's 8 chosen experts gates of 0.23 down to 0.08, and the 8th and the 9th
expert lie within bf16's rounding of the normed input in about a tenth of all
tokens a layer: the bf16 program and the float32 reference then send the
token through another expert at a twelfth of its expert output, and
``served_gap_max``, a maximum over some 1500 tokens, read those rare tokens
(0.04 to 0.10 on the chip where the fp8 control read 0.20; on the CPU at
hidden 256 and 8192 positions 0.106 / 0.224, and 0.084 with the selection
off in both: not the selection, as PR 30 first took it).  At four times the
spread the gates fall from 0.58 to 0.015, as a trained router's do, the 8th
against the 9th moves a token's output by a sixtieth, and the same reading is
0.023 against 0.266; on the chip, the program's uncached forward at the
published widths over the last 1536 of 9216 positions, 0.064 / 0.227 at unit
scale and 0.026 / 0.159 at four times (PERF.md, section 6).  Which experts a token chooses
does not change with the scale (the order of its logits is the same), so the
experts' load does not either.
"""

from __future__ import annotations

import math


def dims(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return {"E": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "KH": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "X": cfg["num_experts"], "K": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "IH": sa["indexer_num_heads"], "ID": sa["indexer_head_dim"],
            "topk": sa["topk"]}


def n_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def kind(cfg: dict, i: int) -> str:
    """Every layer is an expert layer with an indexer
    (``decoder_sparse_step`` 1, ``mlp_only_layers`` [])."""
    return "sparse"


def layer_leaves(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    E, H, KH, D, X, F = d["E"], d["H"], d["KH"], d["D"], d["X"], d["F"]
    IH, ID = d["IH"], d["ID"]
    k = lambda fan_in: 1.0 / math.sqrt(fan_in)
    return {
        "ln_attn": ((E,), "scale", 0.1), "ln_ffn": ((E,), "scale", 0.1),
        "wq": ((E, H, D), "normal", k(E)),
        "wk": ((E, KH, D), "normal", k(E)),
        "wv": ((E, KH, D), "normal", k(E)),
        "wo": ((H, D, E), "normal", 0.5 * k(H * D)),
        "q_norm": ((D,), "scale", 0.1), "k_norm": ((D,), "scale", 0.1),
        "idx_wq": ((E, IH, ID), "normal", k(E)),
        "idx_wk": ((E, ID), "normal", k(E)),
        "idx_k_scale": ((ID,), "scale", 0.1),
        "idx_k_bias": ((ID,), "normal", 0.1),
        "idx_ww": ((E, IH), "normal", k(E)),
        "router": ((E, X), "normal", 4.0 * k(E)),
        "w_gate": ((X, E, F), "normal", k(E)),
        "w_up": ((X, E, F), "normal", k(E)),
        "w_down": ((X, F, E), "normal", 0.25 * k(F)),
    }


def top_leaves(cfg: dict) -> dict:
    d = dims(cfg)
    k = 1.0 / math.sqrt(d["E"])
    return {"embed": ((d["V"], d["E"]), "normal", 1.0),
            "ln_f": ((d["E"],), "scale", 0.1),
            "head": ((d["E"], d["V"]), "normal", k)}
