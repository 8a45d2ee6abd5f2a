"""The toy family's plain reference: pre-LN block, LayerNorm with bias,
rotary GQA attention with biased projections, GELU (tanh) FFN of the
layer's own width, tied head."""

import jax
import jax.numpy as jnp

from harness.reference import _mm, _rope


def _ln(x, s, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * s + b


def embed(cfg, top, toks):
    return top["wte"][toks].astype(jnp.float32)


def layer(cfg, kind, quant, w, x):
    assert w["w_up"].shape[1] == kind == w["w_down"].shape[0]
    eps, base = cfg["ln_eps"], cfg["rope_base"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _ln(x, w["ln1_s"], w["ln1_b"], eps)
    q = _mm("te,ehd->thd", h, w["wq"], quant) + w["bq"]
    k = _mm("te,ehd->thd", h, w["wk"], quant) + w["bk"]
    v = _mm("te,ehd->thd", h, w["wv"], quant) + w["bv"]
    q, k = _rope(q, base), _rope(k, base)
    G = cfg["n_head"] // cfg["n_kv_head"]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = _mm("thd,shd->hts", q, k, quant) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    T = x.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = _mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + _mm("thd,hde->te", o, w["wo"], quant) + w["bo"]
    h = _ln(x, w["ln2_s"], w["ln2_b"], eps)
    u = jax.nn.gelu(_mm("te,ef->tf", h, w["w_up"], quant) + w["b_up"],
                    approximate=True)
    return x + _mm("tf,fe->te", u, w["w_down"], quant) + w["b_down"]


def logits(cfg, quant, top, x, rows):
    h = _ln(x[rows], top["lnf_s"].astype(jnp.float32),
            top["lnf_b"].astype(jnp.float32), cfg["ln_eps"])
    return _mm("pe,ve->pv", h, top["wte"].astype(jnp.float32), quant)
