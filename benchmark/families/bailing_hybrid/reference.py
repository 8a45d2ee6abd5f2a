"""The bailing_hybrid family's plain reference, in float32 ``jax.numpy``.

From the equations of its published mechanisms (the configuration's
``assumed`` names each reading of a key that is not settled by the key), E
hidden, eps ``rms_norm_eps``, x = RMSNorm(stream), no bias anywhere:

- top: ``x = Emb[tok]``; a layer: ``x += Mixer(RMSNorm(x))``, then ``x +=
  FFN(RMSNorm(x))``; ``logits = RMSNorm(x) . W_head`` (untied).
- KDA (Kimi Linear, arXiv:2510.26692), H heads of dk = dv: ``[q | k | v] =
  silu(conv(x W_qkv))`` (causal, depthwise, kernel K, no bias), ``q =
  L2(q) dk^-1/2``, ``k = L2(k)``; ``g = -5 sigmoid(exp(A_log_h) (x W_f +
  dt_bias))`` a channel (``kda_lower_bound``, ``kda_safe_gate``), ``beta =
  sigmoid(x W_b)`` a head; ``S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} +
  beta k v^T``, ``o_t = S_t^T q_t``, ONE POSITION AT A TIME (``lax.scan``;
  the program runs it by blocks as matmuls and one triangular solve);
  ``out = (RMSNorm_head(o) * sigmoid(x W_g)) W_o``.
- MLA (DeepSeek-V2, ``q_lora_rank`` null): ``[q_nope | q_pe] = x W_q``,
  ``[c | k_pe] = x W_kva``, ``c = RMSNorm(c)``, ``[k_nope | v] = c W_kvb``
  MATERIALISED a head (the program never forms them: it scores absorbed
  queries against the latent); rotary on q_pe and the shared k_pe in
  interleaved pairs; ``score = (q_nope.k_nope + q_pe.k_pe) / sqrt(nope +
  rope)``, causal softmax, ``o = p v``, times ``sigmoid(x W_gate)`` a head,
  ``W_o``.
- experts (DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(x W_r)``, ``b = s +
  bias``; a group's score is the sum of its two largest b (a plain loop over
  the groups here), the ``topk_group`` best groups stay, the
  ``num_experts_per_tok`` largest b in them are chosen, gates
  ``routed_scaling_factor * s / sum(s chosen)``; expert e = ``(silu(v
  W1_e[:, :F]) * v W1_e[:, F:]) W2_e``; the shared expert the same at its
  width, weight 1; a dense layer's SwiGLU the same at ``intermediate_size``.

Departures from the source, each because of the chip's share
(``deployment``): the layer holds experts ``first_local_expert ..
+ num_experts`` of the ``published`` count, routes over all of them and
leaves out what the absent ones would have added; the vocabulary is the
slice the configuration holds, in the embedding and the head alike.
``dt_bias`` and ``A_log`` come from raw leaves through ``kda_consts``
(leaves.py says why), which the served model's placement calls too.

A long sequence goes through attention in blocks of 256 queries and through
the experts in blocks of 4, so neither a [T, T] array a head nor a float32
copy of a layer's experts exists.

``cfg["_state_drop"]`` (set by nothing but the tests and a check by hand
that the comparison can SEE the carried state) zeroes the delta
rule's state and the convolution's window at every position that is a
multiple of it: what a chunk tick that failed to carry a row's state would
compute.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.reference import _mm, _rms

from . import leaves

Q_BLOCK = 256
X_BLOCK = 4
RATE_MIN, RATE_MAX = 1e-3, 0.5
A_MIN, A_MAX = 1.0, 2.0


def kda_consts(cfg, f_raw, a_raw):
    """(dt_bias ``[P]``, A_log ``[H]``) in bfloat16 from the two raw N(0,
    1) leaves: each raw value's normal quantile u; ``exp(A_log)`` = u
    between A_MIN and A_MAX; ``dt_bias`` such that the decay rate
    ``-lower_bound * sigmoid(exp(A_log) dt_bias)`` is exp of u between log
    RATE_MIN and log RATE_MAX."""
    f = jnp.float32
    d = leaves.dims(cfg)
    u = lambda raw: 0.5 * (1.0 + jax.lax.erf(raw.astype(f) / math.sqrt(2)))
    a = (A_MIN + u(a_raw) * (A_MAX - A_MIN)).astype(jnp.bfloat16)
    rate = jnp.exp(math.log(RATE_MIN)
                   + u(f_raw) * (math.log(RATE_MAX) - math.log(RATE_MIN)))
    share = rate / -cfg["kda_lower_bound"]
    z = jnp.log(share) - jnp.log1p(-share)                  # logit
    dt_bias = z.reshape(d["H"], d["dk"]) / a.astype(f)[:, None]
    return (dt_bias.reshape(-1).astype(jnp.bfloat16),
            jnp.log(a.astype(f)).astype(jnp.bfloat16))


def embed(cfg, top, toks):
    """Token ids [..., T] -> the trunk's input [..., T, E], float32."""
    return top["embed"][toks].astype(jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(cfg, quant, w, u):
    d = leaves.dims(cfg)
    H, dk, P, K = d["H"], d["dk"], d["P"], d["K"]
    T = u.shape[0]
    f = lambda name: w[name].astype(jnp.float32)
    qkv = _mm("te,ec->tc", u, f("w_qkv"), quant)
    dt_bias, a_log = kda_consts(cfg, w["f_raw"], w["a_raw"])
    z = (_mm("te,ep->tp", u, f("w_f"), quant)
         + dt_bias.astype(jnp.float32)).reshape(T, H, dk) \
        * jnp.exp(a_log.astype(jnp.float32))[:, None]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(z)
    beta = jax.nn.sigmoid(_mm("te,eh->th", u, f("w_b"), quant))
    drop = cfg.get("_state_drop")
    fresh = (jnp.arange(T) % drop == 0) if drop else jnp.zeros((T,), bool)
    conv_w = f("conv_w")

    def one(carry, part):
        S, win = carry                      # [H, dk, dv], [K - 1, 3P]
        qkv_t, g_t, b_t, new = part
        S = jnp.where(new, 0.0, S)
        win = jnp.where(new, 0.0, win)
        full = jnp.concatenate([win, qkv_t[None]], 0)       # [K, 3P]
        a = jax.nn.silu(jnp.sum(full * conv_w, 0))
        q = _l2(a[:P].reshape(H, dk)) * dk ** -0.5
        k = _l2(a[P:2 * P].reshape(H, dk))
        v = a[2 * P:].reshape(H, dk)
        S = S * jnp.exp(g_t)[..., None]
        corr = v - _mm("hkv,hk->hv", S, k, quant)
        S = S + k[..., None] * (b_t[:, None] * corr)[:, None, :]
        return (S, full[1:]), _mm("hkv,hk->hv", S, q, quant)

    _, o = jax.lax.scan(
        one, (jnp.zeros((H, dk, dk), jnp.float32),
              jnp.zeros((K - 1, 3 * P), jnp.float32)),
        (qkv, g, beta, fresh))
    o = _rms(o, f("norm"), cfg["rms_norm_eps"]).reshape(T, P) \
        * jax.nn.sigmoid(_mm("te,ep->tp", u, f("w_g"), quant))
    return _mm("tp,pe->te", o, f("w_o"), quant)


def _rope(x, theta):
    """x [T, (H,) d] at positions 0..T-1, interleaved pairs."""
    T, dim = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _mla(cfg, quant, w, u):
    d = leaves.dims(cfg)
    H, nope, rope, vd, Lr = d["H"], d["nope"], d["rope"], d["vd"], d["Lr"]
    T = u.shape[0]
    f = lambda name: w[name].astype(jnp.float32)
    q = _mm("te,ehd->thd", u, f("wq"), quant)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cfg["rope_theta"])
    ckv = _mm("te,el->tl", u, f("wkva"), quant)
    c = _rms(ckv[:, :Lr], f("kv_norm"), cfg["rms_norm_eps"])
    k_pe = _rope(ckv[:, Lr:], cfg["rope_theta"])
    kv = _mm("tl,lhd->thd", c, f("wkvb"), quant)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(t0):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)
        keep = jnp.arange(T)[None, :] <= (t0 + jnp.arange(qb))[:, None]
        s = (_mm("qhd,shd->hqs", sl(q_nope), k_nope, quant)
             + _mm("qhr,sr->hqs", sl(q_pe), k_pe, quant)) \
            / math.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        return _mm("hqs,shv->qhv", p, v, quant)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, H, vd)
    o = o * jax.nn.sigmoid(_mm("te,eh->th", u, f("w_gate"), quant))[..., None]
    return _mm("thv,hve->te", o, f("wo"), quant)


def route(cfg, s, bias):
    """The group-limited choice over one sequence, a plain loop over the
    groups: scores s ``[T, X]``, bias ``[X]`` -> gates ``[T, X]`` (0 where
    not chosen)."""
    d = leaves.dims(cfg)
    X, G, Gk, Kx = d["X"], d["G"], d["Gk"], d["Kx"]
    per = X // G
    b = s + bias
    group = jnp.stack([jnp.sort(b[:, i * per:(i + 1) * per], -1)[:, -2:]
                       .sum(-1) for i in range(G)], -1)     # [T, G]
    kept = jnp.argsort(-group, -1, stable=True)[:, :Gk]
    mask = jnp.zeros_like(group, bool).at[
        jnp.arange(s.shape[0])[:, None], kept].set(True)
    b = jnp.where(jnp.repeat(mask, per, -1), b, -jnp.inf)
    chosen = jnp.argsort(-b, -1, stable=True)[:, :Kx]
    sc = jnp.take_along_axis(s, chosen, -1)
    gates = cfg["routed_scaling_factor"] * sc / sc.sum(-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                chosen].set(gates)


def _swiglu(quant, h, w_in, w_out):
    F = w_out.shape[0]
    a = _mm("te,ef->tf", h, w_in.astype(jnp.float32), quant)
    a = jax.nn.silu(a[:, :F]) * a[:, F:]
    return _mm("tf,fe->te", a, w_out.astype(jnp.float32), quant)


def _experts(cfg, quant, w, h):
    """(the held experts' gated sum, the shared expert's output)."""
    d = leaves.dims(cfg)
    Xh, X0, E, F = d["Xh"], d["X0"], d["E"], d["F"]
    s = jax.nn.sigmoid(_mm("te,ex->tx", h, w["router"].astype(jnp.float32),
                           quant))
    gate = route(cfg, s, w["router_bias"].astype(jnp.float32))[
        :, X0:X0 + Xh]                                      # the held ones'
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
    return y, _swiglu(quant, h, w["sh_in"], w["sh_out"])


def layer(cfg, kind, quant, w, x):
    """One decoder layer of ``kind`` on one sequence x [T, E] float32."""
    eps = cfg["rms_norm_eps"]
    mix, _, dense = kind.partition("_")
    u = _rms(x, w["ln_mixer"].astype(jnp.float32), eps)
    x = x + {"kda": _kda, "mla": _mla}[mix](cfg, quant, w, u)
    h = _rms(x, w["ln_ffn"].astype(jnp.float32), eps)
    if dense:
        return x + _swiglu(quant, h, w["mlp_in"], w["mlp_out"])
    routed, shared = _experts(cfg, quant, w, h)
    return x + routed + shared


def logits(cfg, quant, top, x, rows):
    """Logits [P, V] at the positions ``rows`` of one sequence x [T, E]."""
    h = _rms(x[rows], top["ln_f"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm("pe,ev->pv", h, top["head"].astype(jnp.float32), quant)
