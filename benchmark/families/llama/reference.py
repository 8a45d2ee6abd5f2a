"""The llama family's plain reference, in float32 ``jax.numpy``.

Llama-family block as the configuration's source describes it: RMSNorm
(x * rsqrt(mean x^2 + eps) * scale), Q/K/V projections (with bias where the
configuration has ``attention_bias``), rotary embedding in the rotate-half
convention with base ``rope_theta``, grouped-query causal attention scaled
by 1/sqrt(head_dim), output projection, residual; RMSNorm, SwiGLU
(down(silu(gate x) * up x)), residual; final RMSNorm; head = embedding
transposed when ``tie_word_embeddings`` else its own matrix.

The whole [H, T, T] score array is held at once: a cell of this family
sends at most a few thousand positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import _mm, _rms, _rope

from . import leaves


def embed(cfg, top, toks):
    """Token ids [..., T] -> the trunk's input [..., T, E], float32."""
    return top["embed"][toks].astype(jnp.float32)


def layer(cfg, kind, quant, w, x):
    """One decoder layer on one sequence x [T, E] float32."""
    d = leaves.dims(cfg)
    eps, base = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, w["ln_attn"], eps)
    q = _mm("te,ehd->thd", h, w["wq"], quant)
    k = _mm("te,ehd->thd", h, w["wk"], quant)
    v = _mm("te,ehd->thd", h, w["wv"], quant)
    if d["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = _rope(q, base), _rope(k, base)
    G = d["H"] // d["KH"]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = _mm("thd,shd->hts", q, k, quant) / jnp.sqrt(jnp.float32(d["D"]))
    T = x.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hts,shd->thd", p, v, quant)
    x = x + _mm("thd,hde->te", o, w["wo"], quant)
    h = _rms(x, w["ln_ffn"], eps)
    g = jax.nn.silu(_mm("te,ef->tf", h, w["w_gate"], quant))
    u = _mm("te,ef->tf", h, w["w_up"], quant)
    return x + _mm("tf,fe->te", g * u, w["w_down"], quant)


def logits(cfg, quant, top, x, rows):
    """Logits [P, V] at the positions ``rows`` of one sequence x [T, E]."""
    h = _rms(x[rows], top["ln_f"].astype(jnp.float32),
             cfg["rms_norm_eps"])
    if cfg["tie_word_embeddings"]:
        return _mm("pe,ve->pv", h, top["embed"].astype(jnp.float32), quant)
    return _mm("pe,ev->pv", h, top["head"].astype(jnp.float32), quant)
