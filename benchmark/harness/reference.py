"""The plain reference: the architecture's forward pass in float32
``jax.numpy`` — no cache, no kernels, no batching, matmuls at "highest".

It imports nothing of the program and takes nothing the program made: its
weights come from harness/weights.py, from the seed, layer by layer, so a
model whose float32 copy would not fit the chip still runs.

What a layer and the head ARE the configuration's family says
(families/<family>/reference.py: ``embed``, ``layer``, ``logits``); here
are the helpers a family may build them from (``_q``, ``_mm``, ``_rms``,
``_rope``) and everything that is the same for every family: the sample's
padding, the loop over the layers, the control, the gap.

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

from . import cells
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


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: str, kind, quant):
    """One layer of kind ``kind``, jitted once per (configuration, kind,
    precision): a pattern of many layers compiles one program a kind."""
    cfg = W.thaw(frozen)
    return jax.jit(functools.partial(
        cells.family(cfg).reference.layer, cfg, kind, quant))


@functools.lru_cache(maxsize=None)
def _gap_fn(frozen: str, with_control: bool):
    cfg = W.thaw(frozen)
    logits = cells.family(cfg).reference.logits

    def gaps(top, x, xc, rows, served):
        ref = logits(cfg, None, top, x, rows)
        best = ref.max(-1)
        take = lambda tok: jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
        out = {"served": best - take(served)}
        if with_control:
            ctl = logits(cfg, "fp8", top, xc, rows)
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
    fam, frozen = cells.family(cfg), W.freeze(cfg)
    top = W.top(cfg, seed)
    toks = np.zeros((len(samples), t_pad), np.int32)
    for i, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served)
        if len(seq) > t_pad or len(served) > p_pad:
            raise ValueError("sample longer than the cell's padded shape")
        toks[i, :len(seq)] = seq
    xs = list(fam.reference.embed(cfg, top, jnp.asarray(toks)))
    xcs = list(xs) if control else None
    for li in range(fam.leaves.n_layers(cfg)):
        w, kind = W.layer(cfg, seed, li), fam.leaves.kind(cfg, li)
        xs = [_layer_fn(frozen, kind, None)(w, x) for x in xs]
        if control:
            xcs = [_layer_fn(frozen, kind, "fp8")(w, x) for x in xcs]
    out = []
    fn = _gap_fn(frozen, control)
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
