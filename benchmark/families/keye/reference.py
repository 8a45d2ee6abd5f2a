"""The keye family's plain reference, in float32 ``jax.numpy``.

Block, pre-norm: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
eps ``rms_norm_eps``; final RMSNorm; ``logits = W_head y``.

Attention, token t, input u_t: ``q_t = W_q u_t`` [H, D], ``k_t = W_k u_t``,
``v_t = W_v u_t`` [KH, D]; RMSNorm over the D of every q and k head with a
learned scale; rotary (``rope_theta``, whole head, rotate-half).  Indexer:
``qI_t = W_qI u_t`` [IH, ID], ``kI_t = LayerNorm(W_kI u_t)`` [ID] (scale and
bias), rotary over the whole ID of both, ``w_t = W_w u_t`` [IH];
``I[t, s] = IH^-1/2 ID^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])`` for s <= t;
``S_t`` = the min(t + 1, topk) positions s <= t of largest I[t, s] (exact;
ties to the lower position); ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}
(q[t, h] . k[s, h // G] / sqrt(D)) v[s, h // G]``; ``Attn = W_o concat_h o``.

Experts: ``p = softmax(W_r u)`` over all X in float32; T = the K largest;
``g_e = p_e / sum_{e' in T} p_e'``; ``MoE(u) = sum_{e in T} g_e W_down,e
(silu(W_gate,e u) * W_up,e u)``.  No capacity, no bias, no shared expert.

A long sequence goes through attention in blocks of 256 queries, so no
[T, T] array per head exists at 16 k positions, and through the experts in
blocks of 4 experts (every token through every expert of the block,
weighted by a gate that is 0 where the token did not choose it), so no
float32 copy of a layer's expert parameters exists.

``cfg["_selection_off"]`` (set by nothing but the tests and the builder's
own check that the comparison can SEE the selection) attends every position
s <= t instead of S_t.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import _mm, _rms, _rope

from . import leaves

Q_BLOCK = 256
X_BLOCK = 4


def embed(cfg, top, toks):
    """Token ids [..., T] -> the trunk's input [..., T, E], float32."""
    return top["embed"][toks].astype(jnp.float32)


def _project(cfg, quant, w, h):
    """q [T, H, D], k, v [T, KH, D] and the indexer's qI [T, IH, ID], kI
    [T, ID], w [T, IH] of one sequence h [T, E], normed and rotated."""
    eps, base = cfg["rms_norm_eps"], cfg["rope_theta"]
    f = lambda name: w[name].astype(jnp.float32)
    q = _rms(_mm("te,ehd->thd", h, f("wq"), quant), f("q_norm"), eps)
    k = _rms(_mm("te,ehd->thd", h, f("wk"), quant), f("k_norm"), eps)
    v = _mm("te,ehd->thd", h, f("wv"), quant)
    qi = _rope(_mm("te,ehd->thd", h, f("idx_wq"), quant), base)
    ki = _mm("te,ed->td", h, f("idx_wk"), quant)
    mu = ki.mean(-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(
        ((ki - mu) ** 2).mean(-1, keepdims=True) + eps)
    ki = _rope((ki * f("idx_k_scale") + f("idx_k_bias"))[:, None, :],
               base)[:, 0]
    wi = _mm("te,eh->th", h, f("idx_ww"), quant)
    return _rope(q, base), _rope(k, base), v, qi, ki, wi


def _keep(cfg, quant, qi_b, wi_b, ki, rows):
    """Which positions the queries at ``rows`` attend: bool [len(rows),
    T], and their index scores (-inf past the query)."""
    d = leaves.dims(cfg)
    T, n = ki.shape[0], rows.shape[0]
    causal = jnp.arange(T)[None, :] <= rows[:, None]
    si = _mm("qhd,sd->qhs", qi_b, ki, quant)                # [n, IH, T]
    score = jnp.einsum("qh,qhs->qs", wi_b, jax.nn.relu(si),
                       precision="highest") / jnp.sqrt(
        jnp.float32(d["IH"] * d["ID"]))
    score = jnp.where(causal, score, -jnp.inf)
    if cfg.get("_selection_off"):
        return causal, score
    vals, idx = jax.lax.top_k(score, min(d["topk"], T))  # ties: lower index
    keep = jnp.zeros((n, T), bool).at[
        jnp.arange(n)[:, None], idx].set(vals > -jnp.inf)
    return keep, score


def selection(cfg, quant, w, h):
    """(keep bool [T, T], score [T, T]) of one layer on its normed input
    h [T, E]: for the tests that compare the program's selected sets."""
    _, _, _, qi, ki, wi = _project(cfg, quant, w, h)
    return _keep(cfg, quant, qi, wi, ki, jnp.arange(h.shape[0]))


def _attention(cfg, quant, w, h):
    d = leaves.dims(cfg)
    H, KH, D = d["H"], d["KH"], d["D"]
    T, G = h.shape[0], H // KH
    q, k, v, qi, ki, wi = _project(cfg, quant, w, h)
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(t0):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)
        keep, _ = _keep(cfg, quant, take(qi), take(wi), ki,
                        t0 + jnp.arange(qb))
        s = _mm("qkgd,skd->kgqs", take(q).reshape(qb, KH, G, D), k,
                quant) / jnp.sqrt(jnp.float32(D))
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        return _mm("kgqs,skd->qkgd", p, v, quant).reshape(qb, H, D)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, H, D)
    return _mm("thd,hde->te", o, w["wo"].astype(jnp.float32), quant)


def _experts(cfg, quant, w, h):
    d = leaves.dims(cfg)
    X, K, E, F = d["X"], d["K"], d["E"], d["F"]
    p = jax.nn.softmax(
        _mm("te,ex->tx", h, w["router"].astype(jnp.float32), quant), -1)
    g, chosen = jax.lax.top_k(p, K)
    g = g / g.sum(-1, keepdims=True)
    gate = jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(g)         # [T, X]
    xb = X_BLOCK if X % X_BLOCK == 0 else X
    blocks = lambda name, *shape: w[name].reshape((X // xb, xb) + shape)

    def block(y, part):
        wg, wu, wd, gb = part
        a = jax.nn.silu(_mm("te,xef->txf", h, wg.astype(jnp.float32),
                            quant)) \
            * _mm("te,xef->txf", h, wu.astype(jnp.float32), quant)
        out = _mm("txf,xfe->txe", a, wd.astype(jnp.float32), quant)
        return y + jnp.einsum("tx,txe->te", gb, out,
                              precision="highest"), None

    y, _ = jax.lax.scan(
        block, jnp.zeros_like(h),
        (blocks("w_gate", E, F), blocks("w_up", E, F),
         blocks("w_down", F, E),
         gate.reshape(-1, X // xb, xb).transpose(1, 0, 2)))
    return y


def layer(cfg, kind, quant, w, x):
    """One decoder layer on one sequence x [T, E] float32."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, quant, w,
                       _rms(x, w["ln_attn"].astype(jnp.float32), eps))
    return x + _experts(cfg, quant, w,
                        _rms(x, w["ln_ffn"].astype(jnp.float32), eps))


def logits(cfg, quant, top, x, rows):
    """Logits [P, V] at the positions ``rows`` of one sequence x [T, E]."""
    h = _rms(x[rows], top["ln_f"].astype(jnp.float32),
             cfg["rms_norm_eps"])
    return _mm("pe,ev->pv", h, top["head"].astype(jnp.float32), quant)
