"""Selective state-space (Mamba-2) recurrence against a per-slot state arena.

A state-space layer caches no key or value: a row's whole past is one
fixed-size state ``h [H, P, N]`` (float32) and the last ``K - 1`` inputs of
its causal depthwise convolution.  Both live in an arena indexed by the
engine's SLOT, not by a block id (``HybridCache``), beside the paged K/V
pool that the model's attention layers keep.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (x) B_t        A < 0, a head
    y_t = h_t . C_t + D * x_t

- :func:`ssm_step`: one token a row — one read and one write of the state.
- :func:`ssm_chunk_scan`: a prompt chunk, by blocks of ``block`` positions
  (the state-space duality form: inside a block the recurrence is a masked
  matmul, between blocks the state is carried), taking a row's state in
  and giving it back.  A position with ``dt = 0`` advances nothing
  (``exp(0) = 1``, no input): that is how padding is kept out of the state.
- :func:`ssm_scan_reference`: the plain sequential scan, for the tests.
- :func:`conv_step` / :func:`conv_chunk`: the causal depthwise convolution
  with its window carried the same way.

No Pallas kernel here: everything is XLA (PERF.md names the fusions).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class HybridCache(NamedTuple):
    """The key side of the cache of a model with state-space layers
    (``models.hybrid_lm.HybridLM``): the K pool of its attention layers
    and, per slot, the state arenas of its state-space layers (one array a
    layer, so a step program updates each in place and slices none out of
    a stack).  A pytree, so it stands wherever the engine holds or donates
    "the K pool"; the V pool stays a plain array."""

    k: jax.Array          # [attn_layers, N, KH, bs, D]
    ssm: tuple            # a state-space layer: [slots, H, P, N] float32
    conv: tuple           # a state-space layer: [slots, K - 1, C]


def ssm_step(h, x, dt, A, B, C, D):
    """One token a row.  h ``[R, H, P, N]`` float32, x ``[R, H, P]``, dt
    ``[R, H]`` (after softplus; 0 leaves the row's state as it is), A, D
    ``[H]``, B, C ``[R, N]``.  Returns (y ``[R, H, P]`` float32, h)."""
    f = jnp.float32
    x, dt, B, C = x.astype(f), dt.astype(f), B.astype(f), C.astype(f)
    decay = jnp.exp(dt * A.astype(f))                       # [R, H]
    h = h * decay[:, :, None, None] + (
        (dt[:, :, None] * x)[..., None] * B[:, None, None, :])
    y = jnp.einsum("rhpn,rn->rhp", h, C,
                   preferred_element_type=f) + D.astype(f)[:, None] * x
    return y, h


def ssm_scan_reference(h, x, dt, A, B, C, D):
    """The recurrence one position at a time over one row: x ``[T, H,
    P]``, dt ``[T, H]``, B, C ``[T, N]``, h ``[H, P, N]``.  Returns (y
    ``[T, H, P]``, h)."""
    def one(h, part):
        xt, dtt, Bt, Ct = part
        y, h = ssm_step(h[None], xt[None], dtt[None], A, Bt[None],
                        Ct[None], D)
        return h[0], y[0]

    h, y = jax.lax.scan(one, h, (x, dt, B, C))
    return y, h


def ssm_chunk_scan(h, x, dt, A, B, C, D, block: int = 256):
    """A chunk of T positions a row, in blocks of ``block``.  h ``[R, H,
    P, N]`` float32 (each row's state BEFORE the chunk), x ``[R, T, H,
    P]``, dt ``[R, T, H]`` (0 at padding), A, D ``[H]``, B, C ``[R, T,
    N]`` (one group, shared by all heads).  Returns (y ``[R, T, H, P]``
    float32, h after the chunk).

    Inside a block, with ``cs_t = sum_{s <= t} dt_s A`` (<= 0, falling):
    ``y_t = exp(cs_t) C_t . h_in + sum_{s <= t} exp(cs_t - cs_s) (C_t .
    B_s) dt_s x_s`` and ``h_out = exp(cs_last) h_in + sum_s exp(cs_last -
    cs_s) dt_s x_s (x) B_s``: every exponent is <= 0, so nothing
    overflows whatever the decay."""
    f = jnp.float32
    R, T, H, P = x.shape
    Q = min(block, T)
    if T % Q:
        raise ValueError(f"chunk of {T} positions is not a whole number "
                         f"of blocks of {Q}")
    nb = T // Q
    blocks = lambda a: jnp.moveaxis(
        a.astype(f).reshape((R, nb, Q) + a.shape[2:]), 1, 0)
    xb, dtb, Bb, Cb = blocks(x), blocks(dt), blocks(B), blocks(C)
    Af, Df = A.astype(f), D.astype(f)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(h, part):
        xq, dq, Bq, Cq = part               # [R, Q, ...]
        cs = jnp.cumsum(dq * Af, axis=1)                    # [R, Q, H]
        dx = dq[..., None] * xq                             # [R, Q, H, P]
        # within the block: [R, H, Q(t), Q(s)]
        ct = cs.transpose(0, 2, 1)
        seg = ct[:, :, :, None] - ct[:, :, None, :]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)),
                          0.0)
        cb = jnp.einsum("rtn,rsn->rts", Cq, Bq,
                        preferred_element_type=f)
        y = jnp.einsum("rhts,rshp->rthp", decay * cb[:, None], dx,
                       preferred_element_type=f)
        # what the carried state gives
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "rtn,rhpn->rthp", Cq, h, preferred_element_type=f)
        # the state the block hands on
        tail = jnp.exp(cs[:, -1:, :] - cs)                  # [R, Q, H]
        h = h * jnp.exp(cs[:, -1])[:, :, None, None] + jnp.einsum(
            "rshp,rsn->rhpn", tail[..., None] * dx, Bq,
            preferred_element_type=f)
        return h, y + Df[:, None] * xq

    h, y = jax.lax.scan(one, h.astype(f), (xb, dtb, Bb, Cb))
    return jnp.moveaxis(y, 0, 1).reshape(R, T, H, P), h


def conv_step(window, u, w, b):
    """One token a row through the causal depthwise convolution.  window
    ``[R, K - 1, C]`` (the row's last inputs, oldest first), u ``[R, C]``,
    w ``[K, C]`` (tap k multiplies the input K - 1 - k positions back),
    b ``[C]``.  Returns (out ``[R, C]`` float32 before the activation,
    the window moved on by one)."""
    f = jnp.float32
    full = jnp.concatenate([window.astype(f), u.astype(f)[:, None]], 1)
    out = jnp.einsum("rkc,kc->rc", full, w.astype(f)) + b.astype(f)
    return out, full[:, 1:].astype(window.dtype)


def conv_chunk(window, u, lens, w, b):
    """A chunk of T positions a row: window ``[R, K - 1, C]``, u ``[R, T,
    C]``, lens ``[R]`` (the real positions; the window that comes back
    holds the last K - 1 inputs at or before position ``lens - 1``, so
    padding does not move it).  Returns (out ``[R, T, C]`` float32,
    window)."""
    f = jnp.float32
    K = w.shape[0]
    T = u.shape[1]
    full = jnp.concatenate([window.astype(f), u.astype(f)], 1)
    out = sum(full[:, k:k + T] * w[k].astype(f) for k in range(K)) \
        + b.astype(f)
    # input j of the chunk sits at full[:, K - 1 + j]: the last K - 1
    # real inputs are full[:, lens : lens + K - 1]
    idx = lens[:, None] + jnp.arange(K - 1)[None, :]
    new = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    return out, new.astype(window.dtype)
