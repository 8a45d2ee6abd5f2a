"""The plain reference: the architecture's forward pass in float32
``jax.numpy`` — no cache, no kernels, no batching, matmuls at "highest".

It imports nothing of the program and takes nothing the program made: its
weights come from harness/weights.py, from the seed, layer by layer, so a
model whose float32 copy would not fit the chip still runs.

Llama-family block as the configuration's source describes it: RMSNorm
(x * rsqrt(mean x^2 + eps) * scale), Q/K/V projections (with bias where the
configuration has ``attention_bias``), rotary embedding in the rotate-half
convention with base ``rope_theta``, grouped-query causal attention scaled
by 1/sqrt(head_dim), output projection, residual; RMSNorm, SwiGLU
(down(silu(gate x) * up x)), residual; final RMSNorm; head = embedding
transposed when ``tie_word_embeddings`` else its own matrix.

``quant="fp8"`` is the control of "How correct is decided": the same
forward with both operands of every matmul rounded to float8_e4m3 (per-
tensor absmax scaling) — the nearest precision below the bf16 that the
configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W


def _q(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant),
                      precision="highest",
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, base):
    """x [T, H, D], positions 0..T-1, rotate-half."""
    T, _, D = x.shape
    half = D // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg_items, quant, w, x):
    """One decoder layer on one sequence x [T, E] float32."""
    cfg = dict(cfg_items)
    d = W.dims(cfg)
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


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_items, quant):
    return jax.jit(functools.partial(_layer, cfg_items, quant))


def _logits(cfg_items, quant, top, x, rows):
    cfg = dict(cfg_items)
    h = _rms(x[rows], top["ln_f"].astype(jnp.float32),
             cfg["rms_norm_eps"])
    if cfg["tie_word_embeddings"]:
        return _mm("pe,ve->pv", h, top["embed"].astype(jnp.float32), quant)
    return _mm("pe,ev->pv", h, top["head"].astype(jnp.float32), quant)


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_items, with_control: bool):
    def gaps(top, x, xc, rows, served):
        ref = _logits(cfg_items, None, top, x, rows)
        best = ref.max(-1)
        take = lambda tok: jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
        out = {"served": best - take(served)}
        if with_control:
            ctl = _logits(cfg_items, "fp8", top, xc, rows)
            out["control"] = best - take(jnp.argmax(ctl, -1))
        return out
    return jax.jit(gaps)


def served_gaps(cfg: dict, seed: int, samples: list, *, t_pad: int,
                p_pad: int, control: bool = False) -> list:
    """For each sample ``(prompt, served)``: run the reference once over
    prompt + served tokens and return, per served token, how far its logit
    lies below the reference's best at that position (0 = the reference's
    own choice).  With ``control`` also the same gap for the token that the
    fp8 forward puts first at each of those positions.

    Every sequence is padded to ``t_pad`` and every row list to ``p_pad``
    (one compiled shape per cell); causal attention makes the padding
    invisible to the positions read."""
    items = W._items(cfg)
    top = W.top(cfg, seed)
    embed = top["embed"].astype(jnp.float32)
    toks = np.zeros((len(samples), t_pad), np.int32)
    for i, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served)
        if len(seq) > t_pad or len(served) > p_pad:
            raise ValueError("sample longer than the cell's padded shape")
        toks[i, :len(seq)] = seq
    xs = [embed[toks[i]] for i in range(len(samples))]
    xcs = list(xs) if control else None
    for li in range(cfg["num_hidden_layers"]):
        w = W.layer(cfg, seed, li)
        xs = [_layer_fn(items, None)(w, x) for x in xs]
        if control:
            xcs = [_layer_fn(items, "fp8")(w, x) for x in xcs]
    out = []
    fn = _gap_fn(items, control)
    for i, (prompt, served) in enumerate(samples):
        n = len(served)
        rows = np.zeros(p_pad, np.int32)
        rows[:n] = len(prompt) - 1 + np.arange(n)
        tok = np.zeros(p_pad, np.int32)
        tok[:n] = served
        g = fn(top, xs[i], xcs[i] if control else xs[i],
               jnp.asarray(rows), jnp.asarray(tok))
        out.append({k: np.asarray(v)[:n] for k, v in g.items()})
    return out
