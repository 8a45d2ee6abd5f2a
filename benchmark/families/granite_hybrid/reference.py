"""The granite_hybrid family's plain reference, in float32 ``jax.numpy``.

From ``transformers`` 4.57's ``modeling_granitemoehybrid.py``
(``GraniteMoeHybridMambaLayer.torch_forward``, ``...Attention``,
``...TopKGating``, ``...MoE``, ``...MLP``, ``...DecoderLayer``), E hidden,
eps ``rms_norm_eps``, r ``residual_multiplier``; no bias but the
convolution's:

- top: ``x = embedding_multiplier * Emb[tok]``; a layer: ``x += r *
  Mixer(RMSNorm(x))``, then ``x += r * (Experts(v) + Shared(v))``, ``v =
  RMSNorm(x)``; ``logits = RMSNorm(x) . Emb^T / logits_scaling`` (tied).
- attention: q [H, D], k, v [KH, D], no rotary (``position_embedding_type``
  "nope"), causal softmax of ``attention_multiplier * q . k``, ``W_o``.
- Mamba-2: ``[z | xBC | dt] = u [W_z | W_xbc | W_dt]``; ``xBC =
  silu(conv(xBC))`` (causal, depthwise, kernel K, bias); ``[x | B | C]`` = I
  | N | N, x as HS heads of P, B and C one group; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
  (x) B_t``, ``y_t = h_t . C_t + D x_t``, ONE POSITION AT A TIME
  (``lax.scan``; the program runs it by blocks of 256 as matmuls); ``g =
  RMSNorm(y * silu(z))`` over all I with one learned scale (the gate before
  the norm); ``out = g W_o``.
- experts: router logits over all X in float32, the Kx largest, gates =
  softmax over those; expert e = ``(silu(v W1_e[:, :F]) * v W1_e[:, F:])
  W2_e``; the shared expert the same at width Fs, weight 1.

Departures from the source, each because of the chip's share
(``deployment``): the layer holds experts ``first_local_expert ..
+ num_local_experts`` of the ``published`` count, routes over all of them
and leaves out what the absent ones would have added (the router keeps its
published width: ``router [E, X]``); the vocabulary is the slice the
configuration holds.  ``dt_bias`` and ``A_log`` come from raw leaves through
``mixer_consts`` (leaves.py says why), which the served model's placement
calls too.

A long sequence goes through attention in blocks of 256 queries and
through the experts in blocks of 4 (every token through every expert of
the block, weighted by a gate that is 0 where the token did not choose it),
so neither a [T, T] array a head nor a float32 copy of a layer's experts
exists.

``cfg["_state_drop"]`` (set by nothing but the tests and the builder's own
check that the comparison can SEE the carried state) zeroes the recurrent
state and the convolution's window at every position that is a multiple of
it: what a chunk tick that failed to carry a row's state would compute.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.reference import _mm, _rms

from . import leaves

Q_BLOCK = 256
X_BLOCK = 4
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def mixer_consts(dt_raw, a_raw):
    """(dt_bias, A_log) in bfloat16 from the two raw N(0, 1) leaves: each
    raw value's normal quantile u; ``softplus(dt_bias)`` = exp of u between
    log DT_MIN and log DT_MAX; ``exp(A_log)`` = u between A_MIN and
    A_MAX."""
    f = jnp.float32
    u = lambda raw: 0.5 * (1.0 + jax.lax.erf(raw.astype(f) / math.sqrt(2)))
    dt = jnp.exp(math.log(DT_MIN)
                 + u(dt_raw) * (math.log(DT_MAX) - math.log(DT_MIN)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))         # softplus^-1
    a_log = jnp.log(A_MIN + u(a_raw) * (A_MAX - A_MIN))
    return dt_bias.astype(jnp.bfloat16), a_log.astype(jnp.bfloat16)


def embed(cfg, top, toks):
    """Token ids [..., T] -> the trunk's input [..., T, E], float32."""
    return cfg["embedding_multiplier"] * top["embed"][toks].astype(
        jnp.float32)


def _attention(cfg, quant, w, h):
    d = leaves.dims(cfg)
    H, KH, D = d["H"], d["KH"], d["D"]
    T, G = h.shape[0], H // KH
    f = lambda name: w[name].astype(jnp.float32)
    q = _mm("te,ehd->thd", h, f("wq"), quant)
    k = _mm("te,ehd->thd", h, f("wk"), quant)
    v = _mm("te,ehd->thd", h, f("wv"), quant)
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(t0):
        qs = jax.lax.dynamic_slice_in_dim(q, t0, qb, 0)
        keep = jnp.arange(T)[None, :] <= (t0 + jnp.arange(qb))[:, None]
        s = _mm("qkgd,skd->kgqs", qs.reshape(qb, KH, G, D), k, quant) \
            * cfg["attention_multiplier"]
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        return _mm("kgqs,skd->qkgd", p, v, quant).reshape(qb, H, D)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, H, D)
    return _mm("thd,hde->te", o, f("wo"), quant)


def _mamba(cfg, quant, w, u):
    d = leaves.dims(cfg)
    I, N, HS, P, K = d["I"], d["N"], d["HS"], d["P"], d["K"]
    T = u.shape[0]
    f = lambda name: w[name].astype(jnp.float32)
    z = _mm("te,ei->ti", u, f("w_z"), quant)
    xbc = _mm("te,ec->tc", u, f("w_xbc"), quant)
    dt = _mm("te,eh->th", u, f("w_dt"), quant)
    dt_bias, a_log = mixer_consts(w["dt_raw"], w["a_raw"])
    dt = jax.nn.softplus(dt + dt_bias.astype(jnp.float32))
    A = -jnp.exp(a_log.astype(jnp.float32))
    drop = cfg.get("_state_drop")
    fresh = (jnp.arange(T) % drop == 0) if drop else jnp.zeros((T,), bool)
    conv_w, conv_b, D = f("conv_w"), f("conv_b"), f("D")

    def one(carry, part):
        h, win = carry                      # [HS, P, N], [K - 1, C]
        xbc_t, dt_t, new = part
        h = jnp.where(new, 0.0, h)
        win = jnp.where(new, 0.0, win)
        full = jnp.concatenate([win, xbc_t[None]], 0)       # [K, C]
        a = jax.nn.silu(jnp.sum(full * conv_w, 0) + conv_b)
        x, B, C = a[:I].reshape(HS, P), a[I:I + N], a[I + N:]
        h = h * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x)[..., None] * B[None, None, :]
        y = _mm("hpn,n->hp", h, C, quant) + D[:, None] * x
        return (h, full[1:]), y.reshape(I)

    _, y = jax.lax.scan(
        one, (jnp.zeros((HS, P, N), jnp.float32),
              jnp.zeros((K - 1, xbc.shape[1]), jnp.float32)),
        (xbc, dt, fresh))
    g = _rms(y * jax.nn.silu(z), f("norm"), cfg["rms_norm_eps"])
    return _mm("ti,ie->te", g, f("w_o"), quant)


def _experts(cfg, quant, w, h):
    d = leaves.dims(cfg)
    X, Xh, X0, K, E, F = d["X"], d["Xh"], d["X0"], d["Kx"], d["E"], d["F"]
    logits = _mm("te,ex->tx", h, w["router"].astype(jnp.float32), quant)
    top, chosen = jax.lax.top_k(logits, K)
    gate = jnp.zeros((h.shape[0], X), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(
        jax.nn.softmax(top, -1))[:, X0:X0 + Xh]     # the held experts'
    xb = X_BLOCK if Xh % X_BLOCK == 0 else Xh

    def block(y, part):
        w1, w2, gb = part
        a = _mm("te,xef->txf", h, w1.astype(jnp.float32), quant)
        a = jax.nn.silu(a[..., :F]) * a[..., F:]
        out = _mm("txf,xfe->txe", a, w2.astype(jnp.float32), quant)
        return y + jnp.einsum("tx,txe->te", gb, out,
                              precision="highest"), None

    y, _ = jax.lax.scan(
        block, jnp.zeros_like(h),
        (w["w_in"].reshape(Xh // xb, xb, E, 2 * F),
         w["w_out"].reshape(Xh // xb, xb, F, E),
         gate.reshape(-1, Xh // xb, xb).transpose(1, 0, 2)))
    Fs = d["Fs"]
    a = _mm("te,ef->tf", h, w["sh_in"].astype(jnp.float32), quant)
    a = jax.nn.silu(a[:, :Fs]) * a[:, Fs:]
    return y, _mm("tf,fe->te", a, w["sh_out"].astype(jnp.float32), quant)


def layer(cfg, kind, quant, w, x):
    """One decoder layer of ``kind`` on one sequence x [T, E] float32."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = _rms(x, w["ln_mixer"].astype(jnp.float32), eps)
    mixer = {"mamba": _mamba, "attention": _attention}[kind]
    x = x + r * mixer(cfg, quant, w, u)
    routed, shared = _experts(
        cfg, quant, w, _rms(x, w["ln_ffn"].astype(jnp.float32), eps))
    return x + r * (routed + shared)


def logits(cfg, quant, top, x, rows):
    """Logits [P, V] at the positions ``rows`` of one sequence x [T, E]."""
    h = _rms(x[rows], top["ln_f"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm("pe,ve->pv", h, top["embed"].astype(jnp.float32), quant) \
        / cfg["logits_scaling"]
