"""Gated delta rule with per-channel decay (Kimi Delta Attention, KDA)
against the per-slot state arena.

Like a Mamba-2 layer (``ops/ssm.py``) a KDA layer caches no key or value: a
row's whole past is one state ``S [H, dk, dv]`` (float32) a head and the
last ``K - 1`` inputs of its causal depthwise convolution, both in the
engine's arena indexed by SLOT (``ops.ssm.HybridCache``).  Per position t
and head, with ``k`` unit-norm, ``alpha = exp(g)`` a decay PER CHANNEL of
the key (``g <= 0``) and ``beta`` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

- :func:`kda_step`: one token a row, one read and one write of the state.
- :func:`kda_chunk_scan`: a prompt chunk by blocks of ``block`` positions
  (the WY / UT form: inside a block the delta rule's corrections are one
  unit-lower-triangular solve and the rest matmuls, between blocks the
  state is carried).  Decays inside a block are taken PAIRWISE,
  ``exp(G_t - G_s)`` for ``s <= t`` with ``G`` the block's cumulative log
  decay, so every exponent is <= 0 and nothing overflows whatever the
  decay.  A position with ``g = 0`` and ``beta = 0`` advances nothing:
  that is how padding is kept out of the state.
- :func:`kda_scan_reference`: the plain sequential scan, for the tests.

No Pallas kernel: everything is XLA (PERF.md names the fusions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions a block of the chunked form (sixteen blocks a 512-token chunk)
KDA_BLOCK = 32


def kda_step(S, q, k, v, g, beta):
    """One token a row.  S ``[R, H, dk, dv]`` float32, q, k ``[R, H,
    dk]``, v ``[R, H, dv]``, g ``[R, H, dk]`` (log decay, 0 keeps the
    state), beta ``[R, H]`` (0 adds nothing).  Returns (o ``[R, H, dv]``
    float32, S)."""
    f = jnp.float32
    q, k, v, g, beta = (a.astype(f) for a in (q, k, v, g, beta))
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("rhkv,rhk->rhv", S, k,
                                          preferred_element_type=f))
    S = S + k[..., None] * u[..., None, :]
    return jnp.einsum("rhkv,rhk->rhv", S, q, preferred_element_type=f), S


def kda_scan_reference(S, q, k, v, g, beta):
    """The recurrence one position at a time over one row: q, k, g ``[T,
    H, dk]``, v ``[T, H, dv]``, beta ``[T, H]``, S ``[H, dk, dv]``.
    Returns (o ``[T, H, dv]``, S)."""
    def one(S, part):
        o, S = kda_step(S[None], *(a[None] for a in part))
        return S[0], o[0]

    S, o = jax.lax.scan(one, S, (q, k, v, g, beta))
    return o, S


def kda_chunk_scan(S, q, k, v, g, beta, block: int = KDA_BLOCK):
    """A chunk of T positions a row, in blocks of ``block``.  S ``[R, H,
    dk, dv]`` float32 (each row's state BEFORE the chunk), q, k, g ``[R,
    T, H, dk]``, v ``[R, T, H, dv]``, beta ``[R, T, H]`` (g and beta 0 at
    padding).  Returns (o ``[R, T, H, dv]`` float32, S after the chunk).

    Inside a block, with ``G_t = sum_{s <= t} g_s`` (per channel, <= 0,
    falling), ``Kd_t = exp(G_t) k_t``, ``Qd_t = exp(G_t) q_t`` and the
    pairwise ``A_ts = beta_t sum_c k_t k_s exp(G_t - G_s)`` (s < t) and
    ``P_ts = sum_c q_t k_s exp(G_t - G_s)`` (s <= t): the block's
    corrections ``U = (I + A)^-1 (beta V - beta Kd S_in)``, its output
    ``O = Qd S_in + P U`` and the state it hands on ``S_out = exp(G_last)
    S_in + (exp(G_last - G) k)^T U``."""
    f = jnp.float32
    R, T, H, dk = q.shape
    Q = min(block, T)
    if T % Q:
        raise ValueError(f"chunk of {T} positions is not a whole number "
                         f"of blocks of {Q}")
    nb = T // Q
    blocks = lambda a: jnp.moveaxis(
        a.astype(f).reshape((R, nb, Q) + a.shape[2:]), 1, 0)
    # [nb, R, H, Q, ...]: heads before positions inside a block
    heads = lambda a: jnp.moveaxis(blocks(a), 3, 2)
    qb, kb, vb, gb = heads(q), heads(k), heads(v), heads(g)
    bb = jnp.moveaxis(blocks(beta), 3, 2)                   # [nb, R, H, Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)

    def pairwise(G, mask):
        d = G[..., :, None, :] - G[..., None, :, :]         # [R,H,Q,Q,dk]
        return jnp.where(mask[..., None],
                         jnp.exp(jnp.where(mask[..., None], d, 0.0)), 0.0)

    def one(S, part):
        qq, kk, vv, gg, bq = part           # [R, H, Q, ...]
        G = jnp.cumsum(gg, axis=2)
        A = bq[..., None] * jnp.einsum("rhtc,rhsc,rhtsc->rhts", kk, kk,
                                       pairwise(G, strict),
                                       preferred_element_type=f)
        P = jnp.einsum("rhtc,rhsc,rhtsc->rhts", qq, kk, pairwise(G, lower),
                       preferred_element_type=f)
        eG = jnp.exp(G)
        rhs = jnp.concatenate([bq[..., None] * vv,
                               bq[..., None] * eG * kk], -1)
        eye = jnp.eye(Q, dtype=f)
        sol = jax.lax.linalg.triangular_solve(
            eye + A, rhs, left_side=True, lower=True, unit_diagonal=True)
        U = sol[..., :vv.shape[-1]] - jnp.einsum(
            "rhtc,rhcv->rhtv", sol[..., vv.shape[-1]:], S,
            preferred_element_type=f)
        O = jnp.einsum("rhtc,rhcv->rhtv", eG * qq, S,
                       preferred_element_type=f) + jnp.einsum(
            "rhts,rhsv->rhtv", P, U, preferred_element_type=f)
        last = G[..., -1:, :]                               # [R, H, 1, dk]
        S = S * jnp.exp(last[..., 0, :])[..., None] + jnp.einsum(
            "rhsc,rhsv->rhcv", jnp.exp(last - G) * kk, U,
            preferred_element_type=f)
        return S, O

    S, O = jax.lax.scan(one, S.astype(f), (qb, kb, vb, gb, bb))
    # [nb, R, H, Q, dv] -> [R, T, H, dv]
    return jnp.moveaxis(O, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        R, T, H, -1), S
