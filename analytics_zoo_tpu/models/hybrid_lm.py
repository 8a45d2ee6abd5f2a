"""A decoder whose layers mix a recurrent kind and an attention kind, each
followed by routed experts beside a shared expert (or, in the leading
layers, a dense SwiGLU): Mamba-2 state-space mixers with NoPE attention
(IBM Granite 4.0-H: ``granitemoehybrid``), or gated delta-rule mixers (KDA)
with latent attention (MLA) under a sigmoid router (inclusionAI Ling 3.0:
``bailing_hybrid``).  Each model takes ONE recurrent and ONE attention
kind: the engine holds one state geometry and one pool geometry a model.

A sibling of ``models/lm.py:TransformerLM``, not a mode of it: nothing here
is on the path a llama or keye model traces.  Served by the paged + chunked
``ContinuousEngine`` only (``decode_step_paged_ssm`` /
``prefill_chunk_paged_ssm``): its attention layers keep K/V in the block
pool, its state-space layers keep a fixed-size state a SLOT
(``ops.ssm.HybridCache``).  ``generate()`` / ``beam_search()`` and the slot
arena have no place for that state and raise.

Equations (E hidden, eps ``ln_eps``, r ``residual_multiplier``; no bias but
the convolution's):

- top: ``x = embedding_multiplier * Emb[tok]``; a layer: ``x += r *
  Mixer(RMSNorm(x))``, then ``x += r * (Experts(v) + Shared(v))`` with ``v
  = RMSNorm(x)``; ``logits = RMSNorm(x) . Emb^T / logits_scaling`` (tied).
- attention: q ``H x D``, k, v ``KH x D``, no rotary, no q/k norm, causal
  softmax of ``attention_multiplier * q . k``, output projection.
- Mamba-2: ``[z | xBC | dt] = u W_in`` (I | I + 2N | HS); ``xBC =
  silu(conv(xBC))`` (causal, depthwise, kernel K, bias); ``[x | B | C]`` = I
  | N | N with x as HS heads of P and ONE group of B, C; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence of
  ``ops/ssm.py``; ``g = RMSNorm(y * silu(z))`` over all I (the gate before
  the norm, one learned scale); ``out = g W_out``.
- experts: router logits over ALL ``experts_total`` in float32, the
  ``experts_per_token`` largest, gates = softmax over those; expert e =
  ``(silu(v W1_e[:, :F]) * v W1_e[:, F:]) W2_e``; the shared expert has the
  same form at its own width and weight 1.  The layer is TOLD which experts
  it holds (``first_expert .. first_expert + experts_held``): it routes over
  all of them, computes its own experts' gated outputs and leaves the rest
  out (``HeldExperts``) — the chip's share of an expert-parallel layer,
  run without its exchange.

The second pair of kinds (``"kda"`` / ``"mla"``; x = RMSNorm(stream)):

- KDA (``ops/kda.py``), H heads of dk = dv: ``[q | k | v] = silu(conv(x
  W_qkv))`` (causal, depthwise, kernel K, no bias), ``q = L2(q) dk^-1/2``,
  ``k = L2(k)``; ``g = lower_bound * sigmoid(exp(A_log_h) (x W_f +
  dt_bias))`` a channel of the key; ``beta = sigmoid(x W_b)`` a head; the
  delta rule; ``out = (RMSNorm_head(o) * sigmoid(x W_g)) W_o``.
- MLA, H heads: ``[q_nope | q_pe] = x W_q``; ``[c | k_pe] = x W_kva``, ``c =
  RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` a head; rotary (interleaved
  pairs) on q_pe and the ONE shared k_pe; ``score = (q_nope.k_nope +
  q_pe.k_pe) / sqrt(nope + rope)``, causal softmax, a sigmoid gate a head
  (``sigmoid(x W_gate)``), ``W_o``.  Served ABSORBED: the paged cache
  holds ``c`` (the K pool) and ``k_pe`` (the V pool) a token and no head,
  ``q_nope W_kvb,k^T`` scores against c, and ``(sum p c) W_kvb,v`` is the
  head's value.
- experts under ``router_score="sigmoid"``: ``s = sigmoid(x W_r)`` in
  float32, ``b = s + bias`` for the selection alone: a group's score is the
  sum of its two largest b, the ``expert_groups_kept`` best of
  ``expert_groups`` groups stay, and the ``top_k`` largest b inside them
  are chosen; gates ``routed_scale * s / sum(s chosen)``.
- the first ``dense_layers`` layers: a SwiGLU of ``dense_width`` in the
  experts' place; the head is untied where ``tie_embeddings`` is false.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.lm import _flat_pools, _stacked_pools
from analytics_zoo_tpu.ops.kda import KDA_BLOCK, kda_chunk_scan, kda_step
from analytics_zoo_tpu.ops.ssm import (HybridCache, conv_chunk, conv_step,
                                       ssm_chunk_scan, ssm_step)

# what a tick of such a model says of itself (the engine books them to the
# flight record under these names; docs/observability.md); a model with
# latent attention also counts the latent cache rows its MLA layers read
HYBRID_COUNTERS = ("ssm_rows", "ssm_chunk_tokens", "moe_assignments",
                   "moe_held_assignments", "moe_max_load")
LATENT_COUNTERS = ("ssm_rows", "ssm_chunk_tokens", "latent_kv_rows",
                   "moe_assignments", "moe_held_assignments", "moe_max_load")
STATE_KINDS = ("mamba", "kda")
ATTENTION_KINDS = ("attention", "mla")

_init = nn.initializers.lecun_normal()


class _Scale(nn.Module):
    """RMSNorm with a learned scale, computed in float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                + self.eps)
        return xf * scale.astype(jnp.float32)


def sigmoid_route(logits, bias, top_k, groups, groups_kept, scale):
    """The group-limited sigmoid choice (DeepSeek-V3's ``noaux_tc``):
    logits ``[N, X]`` float32 -> (gates ``[N, top_k]``, chosen experts
    ``[N, top_k]``).  ``bias`` moves the choice and never a gate."""
    N, X = logits.shape
    s = jax.nn.sigmoid(logits)
    b = s + bias.astype(jnp.float32)
    per = X // groups
    best = jax.lax.top_k(b.reshape(N, groups, per), 2)[0].sum(-1)
    _, kept = jax.lax.top_k(best, groups_kept)             # [N, kept]
    keep = jnp.zeros((N, groups), bool).at[
        jnp.arange(N)[:, None], kept].set(True)
    b = jnp.where(jnp.repeat(keep, per, axis=1), b, -jnp.inf)
    _, chosen = jax.lax.top_k(b, top_k)
    sc = jnp.take_along_axis(s, chosen, axis=1)
    return scale * sc / jnp.sum(sc, -1, keepdims=True), chosen


class HeldExperts(nn.Module):
    """Token-choice experts of which this chip holds a contiguous share.

    The router has all ``experts_total`` outputs; a token's ``top_k``
    largest logits choose its experts and their softmax gives the gates.
    Only assignments to the held experts are computed: they are sorted to
    the front, by expert, and ``jax.lax.ragged_dot`` multiplies each group
    through its expert's matrices; the assignments to absent experts lie
    past the last group, belong to none, and are dropped from the sum.
    Nothing is capacity-dropped: a row's output does not depend on its
    batchmates.

    Parameters: ``router [E, X]``, ``w_in [held, E, 2F]`` (gate | up),
    ``w_out [held, F, E]``; under ``score="sigmoid"`` also ``bias [X]``,
    and the choice is group-limited (:func:`sigmoid_route`).
    """

    experts_total: int
    experts_held: int
    first_expert: int
    expert_width: int
    top_k: int
    dtype: jnp.dtype = jnp.bfloat16
    score: str = "softmax"
    groups: int = 1
    groups_kept: int = 1
    scale: float = 1.0

    @nn.compact
    def __call__(self, x, count=None):
        """x ``[..., E]`` -> (y, stats): stats = int32 ``[assignments,
        held assignments, largest load of a held expert]`` over the tokens
        where ``count`` (bool ``x.shape[:-1]``; None: all) is true."""
        E = x.shape[-1]
        X, Xh, F, K = (self.experts_total, self.experts_held,
                       self.expert_width, self.top_k)
        lo = self.first_expert
        if not (1 <= K <= X and 0 <= lo and lo + Xh <= X and Xh >= 1):
            raise ValueError(
                f"experts {lo}..{lo + Xh} of {X}, top_k {K}: not a share")
        router = self.param("router", _init, (E, X), jnp.float32)
        w_in = self.param("w_in", _init, (Xh, E, 2 * F), jnp.float32)
        w_out = self.param("w_out", _init, (Xh, F, E), jnp.float32)
        xt = x.reshape(-1, E)
        N = xt.shape[0]
        logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32))
        if self.score == "sigmoid":
            bias = self.param("bias", nn.initializers.zeros, (X,),
                              jnp.float32)
            gates, chosen = sigmoid_route(logits, bias, K, self.groups,
                                          self.groups_kept, self.scale)
        else:
            top, chosen = jax.lax.top_k(logits, K)          # [N, K]
            gates = jax.nn.softmax(top, axis=-1)
        local = chosen.reshape(-1) - lo                     # [N*K]
        held = (local >= 0) & (local < Xh)
        group = jnp.where(held, local, Xh)      # absent: past every group
        order = jnp.argsort(group)              # stable: expert, then token
        cnt = jnp.ones((N,), bool) if count is None else count.reshape(-1)
        cnt = jnp.repeat(cnt, K)
        sizes = jnp.zeros((Xh + 1,), jnp.int32).at[group].add(1)[:Xh]
        load = jnp.zeros((Xh + 1,), jnp.int32).at[group].add(
            cnt.astype(jnp.int32))[:Xh]
        xs = xt.astype(self.dtype)[order // K]              # [N*K, E]
        rd = lambda a, w: jax.lax.ragged_dot(
            a, w.astype(self.dtype), sizes,
            preferred_element_type=jnp.float32)
        h = rd(xs, w_in)
        h = jax.nn.silu(h[:, :F]) * h[:, F:]
        ys = rd(h.astype(self.dtype), w_out)                # [N*K, E] f32
        # a row past the last group belongs to no expert: whatever the
        # grouped matmul left there is not part of the result
        ys = jnp.where((jnp.arange(N * K) < jnp.sum(sizes))[:, None], ys,
                       0.0)
        ya = ys[jnp.argsort(order)].reshape(N, K, E)
        y = jnp.einsum("nk,nke->ne", gates, ya)
        stats = jnp.stack([jnp.sum(cnt), jnp.sum(load), jnp.max(load)]
                          ).astype(jnp.int32)
        return y.reshape(x.shape), stats


class _SharedExpert(nn.Module):
    width: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        E = x.shape[-1]
        w_in = self.param("w_in", _init, (E, 2 * self.width), jnp.float32)
        w_out = self.param("w_out", _init, (self.width, E), jnp.float32)
        h = jnp.dot(x.astype(self.dtype), w_in.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        h = jax.nn.silu(h[..., :self.width]) * h[..., self.width:]
        return jnp.dot(h.astype(self.dtype), w_out.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class Mamba2Mixer(nn.Module):
    """The state-space mixer.  ``chunk`` runs T positions a row from a
    carried (state, window) and gives both back; ``step`` runs one."""

    hidden_size: int
    heads: int
    head_dim: int
    state: int
    conv_kernel: int
    block: int
    eps: float
    dtype: jnp.dtype

    def setup(self):
        I = self.heads * self.head_dim
        C = I + 2 * self.state
        E = self.hidden_size
        self._I, self._C = I, C
        f32 = jnp.float32
        self.in_proj = self.param("in_proj", _init,
                                  (E, I + C + self.heads), f32)
        self.conv_w = self.param("conv_w", _init, (self.conv_kernel, C),
                                 f32)
        self.conv_b = self.param("conv_b", nn.initializers.zeros, (C,), f32)
        self.dt_bias = self.param("dt_bias", nn.initializers.zeros,
                                  (self.heads,), f32)
        self.A_log = self.param("A_log", nn.initializers.zeros,
                                (self.heads,), f32)
        self.D = self.param("D", nn.initializers.ones, (self.heads,), f32)
        self.norm = self.param("norm", nn.initializers.ones, (I,), f32)
        self.out_proj = self.param("out_proj", _init, (I, E), f32)

    def _split(self, u):
        p = jnp.dot(u.astype(self.dtype), self.in_proj.astype(self.dtype),
                    preferred_element_type=jnp.float32).astype(self.dtype)
        I, C = self._I, self._C
        return p[..., :I], p[..., I:I + C], p[..., I + C:]

    def _dt(self, dt):
        return jax.nn.softplus(dt.astype(jnp.float32)
                               + self.dt_bias.astype(jnp.float32))

    def _xbc(self, a):
        """silu of the convolution's output -> x [.., H, P], B, C [.., N]"""
        a = jax.nn.silu(a).astype(self.dtype)
        I, N = self._I, self.state
        x = a[..., :I].reshape(a.shape[:-1] + (self.heads, self.head_dim))
        return x, a[..., I:I + N], a[..., I + N:]

    def _out(self, y, z):
        g = y.reshape(z.shape).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + self.eps)
        g = g * self.norm.astype(jnp.float32)
        return jnp.dot(g.astype(self.dtype), self.out_proj.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _A(self):
        return -jnp.exp(self.A_log.astype(jnp.float32))

    def project(self, u):
        """The input projection, row by row: (z, what ``_out`` takes
        beside the core's output; (xbc, dt), what the core takes)."""
        z, xbc, dt = self._split(u)
        return z, (xbc, dt)

    def chunk_core(self, xd, h, window, lens):
        """:meth:`chunk` between its projections: ``xd`` of ``[R, T]``
        rows -> (y ``[R, T, H, P]``, h, window)."""
        xbc, dt = xd
        T = xbc.shape[1]
        a, window = conv_chunk(window, xbc, lens, self.conv_w, self.conv_b)
        x, B, C = self._xbc(a)
        real = jnp.arange(T)[None, :] < lens[:, None]
        dt = jnp.where(real[..., None], self._dt(dt), 0.0)
        Q = min(self.block, T)
        pad = -T % Q
        if pad:
            padT = lambda t: jnp.pad(
                t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            x, dt, B, C = padT(x), padT(dt), padT(B), padT(C)
        y, h = ssm_chunk_scan(h, x, dt, self._A(), B, C, self.D, Q)
        return y[:, :T], h, window

    def step_core(self, xd, h, window, live):
        """:meth:`step` between its projections: y ``[R, H, P]``."""
        xbc, dt = xd
        a, moved = conv_step(window, xbc, self.conv_w, self.conv_b)
        window = jnp.where(live[:, None, None], moved, window)
        x, B, C = self._xbc(a)
        dt = jnp.where(live[:, None], self._dt(dt), 0.0)
        y, h = ssm_step(h, x, dt, self._A(), B, C, self.D)
        return y, h, window

    def chunk(self, u, h, window, lens):
        """u ``[R, T, E]``, h ``[R, H, P, N]``, window ``[R, K - 1, C]``,
        lens ``[R]``: positions at or past ``lens`` advance neither."""
        z, xd = self.project(u)
        y, h, window = self.chunk_core(xd, h, window, lens)
        return self._out(y, z), h, window

    def step(self, u, h, window, live):
        """u ``[R, E]``; rows where ``live`` is false keep their state."""
        z, xd = self.project(u)
        y, h, window = self.step_core(xd, h, window, live)
        return self._out(y, z), h, window


class NopeAttention(nn.Module):
    """Grouped-query attention with no positional term and a fixed score
    multiplier, dense (``__call__``) or against the paged pool
    (``paged``).  The pool's read applies ``D ** -0.5`` itself, so q
    carries ``multiplier * D ** 0.5``."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    multiplier: float
    dtype: jnp.dtype

    def setup(self):
        dense = lambda feats, axis=-1: nn.DenseGeneral(
            feats, axis=axis, use_bias=False, dtype=self.dtype,
            param_dtype=jnp.float32)
        H, KH, D = self.num_heads, self.num_kv_heads, self.head_dim
        self.query = dense((H, D))
        self.key = dense((KH, D))
        self.value = dense((KH, D))
        self.attn_out = dense(self.hidden_size, (-2, -1))

    def __call__(self, x):
        B, T, _ = x.shape
        H, KH, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.query(x).reshape(B, T, KH, H // KH, D)
        k, v = self.key(x), self.value(x)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                       preferred_element_type=jnp.float32) * self.multiplier
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("bhgqk,bkhd->bqhgd",
                       jax.nn.softmax(s, -1).astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return self.attn_out(o.reshape(B, T, H, D).astype(self.dtype))

    def paged(self, xs, pool_k, pool_v, tables, pos, limit, kernel):
        from analytics_zoo_tpu.ops.flash_attention import (
            paged_attention, paged_kv_update)

        q = self.query(xs) * jnp.asarray(
            self.multiplier * self.head_dim ** 0.5, self.dtype)
        pool_k, pool_v = paged_kv_update(pool_k, pool_v, tables, pos,
                                         self.key(xs), self.value(xs),
                                         limit=limit)
        o = paged_attention(q, pool_k, pool_v, tables, pos, kernel=kernel)
        return self.attn_out(o.astype(self.dtype)), pool_k, pool_v


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


class KdaMixer(nn.Module):
    """The gated delta-rule mixer, in the shape of :class:`Mamba2Mixer`:
    ``chunk`` runs T positions a row from a carried (state, window) and
    gives both back, ``step`` runs one.  The input projection is one
    matrix, ``[q | k | v | f | beta | gate]``."""

    hidden_size: int
    heads: int
    head_dim: int
    conv_kernel: int
    lower_bound: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        H, P, E = self.heads, self.heads * self.head_dim, self.hidden_size
        f32 = jnp.float32
        self.in_proj = self.param("in_proj", _init, (E, 5 * P + H), f32)
        self.conv_w = self.param("conv_w", _init, (self.conv_kernel, 3 * P),
                                 f32)
        self.dt_bias = self.param("dt_bias", nn.initializers.zeros, (P,),
                                  f32)
        self.A_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
        self.norm = self.param("norm", nn.initializers.ones,
                               (self.head_dim,), f32)
        self.out_proj = self.param("out_proj", _init, (P, E), f32)

    def _split(self, u):
        """-> qkv [.., 3P] before the convolution, g [.., H, dk], beta
        [.., H], gate [.., P], all float32."""
        p = jnp.dot(u.astype(self.dtype), self.in_proj.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        H, dk = self.heads, self.head_dim
        P = H * dk
        f = p[..., 3 * P:4 * P] + self.dt_bias.astype(jnp.float32)
        f = f.reshape(f.shape[:-1] + (H, dk)) * jnp.exp(
            self.A_log.astype(jnp.float32))[:, None]
        g = self.lower_bound * jax.nn.sigmoid(f)
        beta = jax.nn.sigmoid(p[..., 4 * P:4 * P + H])
        return p[..., :3 * P], g, beta, p[..., 4 * P + H:]

    def _qkv(self, a):
        """silu of the convolution's output -> q, k, v [.., H, dk]."""
        a = jax.nn.silu(a)
        H, dk = self.heads, self.head_dim
        q, k, v = (a[..., i * H * dk:(i + 1) * H * dk].reshape(
            a.shape[:-1] + (H, dk)) for i in range(3))
        return _l2(q) * dk ** -0.5, _l2(k), v

    def _out(self, o, gate):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + self.eps)
        o = (o * self.norm.astype(jnp.float32)).reshape(gate.shape) \
            * jax.nn.sigmoid(gate)
        return jnp.dot(o.astype(self.dtype), self.out_proj.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def project(self, u):
        """The input projection, row by row: (gate, what ``_out`` takes
        beside the core's output; (qkv, g, beta), what the core takes)."""
        qkv, g, beta, gate = self._split(u)
        return gate, (qkv, g, beta)

    def chunk_core(self, xd, S, window, lens):
        """:meth:`chunk` between its projections: ``xd`` of ``[R, T]``
        rows -> (o ``[R, T, H, dv]``, S, window)."""
        qkv, g, beta = xd
        T = qkv.shape[1]
        a, window = conv_chunk(window, qkv, lens, self.conv_w, None)
        q, k, v = self._qkv(a)
        real = jnp.arange(T)[None, :] < lens[:, None]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        Q = min(KDA_BLOCK, T)
        pad = -T % Q
        if pad:
            padT = lambda t: jnp.pad(
                t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            q, k, v, g, beta = (padT(t) for t in (q, k, v, g, beta))
        o, S = kda_chunk_scan(S, q, k, v, g, beta, Q)
        return o[:, :T], S, window

    def step_core(self, xd, S, window, live):
        """:meth:`step` between its projections: o ``[R, H, dv]``."""
        qkv, g, beta = xd
        a, moved = conv_step(window, qkv, self.conv_w, None)
        window = jnp.where(live[:, None, None], moved, window)
        q, k, v = self._qkv(a)
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        o, S = kda_step(S, q, k, v, g, beta)
        return o, S, window

    def chunk(self, u, S, window, lens):
        """u ``[R, T, E]``, S ``[R, H, dk, dv]``, window ``[R, K - 1,
        3P]``, lens ``[R]``: positions at or past ``lens`` advance
        neither."""
        gate, xd = self.project(u)
        o, S, window = self.chunk_core(xd, S, window, lens)
        return self._out(o, gate), S, window

    def step(self, u, S, window, live):
        """u ``[R, E]``; rows where ``live`` is false keep their state."""
        gate, xd = self.project(u)
        o, S, window = self.step_core(xd, S, window, live)
        return self._out(o, gate), S, window


def rope_interleaved(x, pos, theta):
    """Rotary over the last axis in interleaved pairs ``(x[2i], x[2i+1])``
    at angle ``pos * theta ** (-2i / d)``: x ``[B, T, (H,) d]``, pos ``[B,
    T]``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * freqs
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


class LatentAttention(nn.Module):
    """Multi-head latent attention (no query latent) with a sigmoid gate a
    head, served ABSORBED: dense (``__call__``) or against the paged
    latent cache (``paged``: ``c`` in the K pool, ``k_pe`` in the V pool,
    one row a token and no head)."""

    hidden_size: int
    num_heads: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    latent_rank: int
    rope_theta: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        H, E, L = self.num_heads, self.hidden_size, self.latent_rank
        f32 = jnp.float32
        self.wq = self.param("wq", _init, (E, H, self.nope_dim
                                          + self.rope_dim), f32)
        self.wkva = self.param("wkva", _init, (E, L + self.rope_dim), f32)
        self.kv_norm = self.param("kv_norm", nn.initializers.ones, (L,),
                                  f32)
        self.wkvb = self.param("wkvb", _init,
                               (L, H, self.nope_dim + self.value_dim), f32)
        self.gate = self.param("gate", _init, (E, H), f32)
        self.wo = self.param("wo", _init, (H, self.value_dim, E), f32)

    def _project(self, x, pos):
        """x ``[B, T, E]`` at positions ``pos [B, T]`` -> (the absorbed
        query ``[B, T, H, L]``, q_pe ``[B, T, H, r]``, c ``[B, T, L]``,
        k_pe ``[B, T, r]``)."""
        dt, f32 = self.dtype, jnp.float32
        mm = lambda spec, a, w: jnp.einsum(spec, a.astype(dt), w.astype(dt),
                                           preferred_element_type=f32)
        q = mm("bte,ehd->bthd", x, self.wq)
        q_pe = rope_interleaved(q[..., self.nope_dim:], pos, self.rope_theta)
        ckv = mm("bte,el->btl", x, self.wkva)
        L = self.latent_rank
        c = ckv[..., :L]
        c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + self.eps) \
            * self.kv_norm.astype(f32)
        k_pe = rope_interleaved(ckv[..., L:], pos, self.rope_theta)
        qa = mm("bthn,lhn->bthl", q[..., :self.nope_dim],
                self.wkvb[..., :self.nope_dim])
        return qa, q_pe, c, k_pe

    def _attend(self, x, qa, q_pe, cs, pes, mask):
        """Scores of the absorbed queries against the latent rows ``cs
        [B, C, L]`` / ``pes [B, C, r]`` where ``mask [B, T, C]``, the
        latent mean back through ``W_kvb,v``, the gate, ``W_o``."""
        dt, f32 = self.dtype, jnp.float32
        s = (jnp.einsum("bthl,bcl->bhtc", qa.astype(dt), cs.astype(dt),
                        preferred_element_type=f32)
             + jnp.einsum("bthr,bcr->bhtc", q_pe.astype(dt), pes.astype(dt),
                          preferred_element_type=f32)) \
            * (self.nope_dim + self.rope_dim) ** -0.5
        s = jnp.where(mask[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        oc = jnp.einsum("bhtc,bcl->bthl", p.astype(dt), cs.astype(dt),
                        preferred_element_type=f32)
        o = jnp.einsum("bthl,lhv->bthv", oc.astype(dt),
                       self.wkvb[..., self.nope_dim:].astype(dt),
                       preferred_element_type=f32)
        o = o * jax.nn.sigmoid(jnp.einsum(
            "bte,eh->bth", x.astype(dt), self.gate.astype(dt),
            preferred_element_type=f32))[..., None]
        return jnp.einsum("bthv,hve->bte", o.astype(dt), self.wo.astype(dt),
                          preferred_element_type=f32)

    def __call__(self, x):
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        qa, q_pe, c, k_pe = self._project(x, pos)
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
        return self._attend(x, qa, q_pe, c, k_pe, mask)

    def paged(self, xs, pool_c, pool_pe, tables, pos, limit, kernel):
        """``NopeAttention.paged``'s contract over the latent pools
        (``[N, 1, bs, L]`` and ``[N, 1, bs, r]``): the chunk's rows are
        written first (``limit`` drops padding), then every row reads its
        whole table in one gather.  ``kernel`` is not used: no kernel takes
        these widths."""
        from analytics_zoo_tpu.ops.flash_attention import paged_kv_update

        B, T, _ = xs.shape
        at = pos[:, None] + jnp.arange(T)[None, :]
        qa, q_pe, c, k_pe = self._project(xs, at)
        pool_c, pool_pe = paged_kv_update(pool_c, pool_pe, tables, pos,
                                          c[:, :, None], k_pe[:, :, None],
                                          limit=limit)
        rows = lambda pool: jnp.take(pool, tables, axis=0).reshape(
            B, -1, pool.shape[-1])
        cs, pes = rows(pool_c), rows(pool_pe)
        mask = jnp.arange(cs.shape[1])[None, None, :] <= at[:, :, None]
        return (self._attend(xs, qa, q_pe, cs, pes, mask), pool_c,
                pool_pe)


class _Dims(NamedTuple):
    """What a layer needs of the model's numbers."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    attention_multiplier: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_conv: int
    ssm_chunk: int
    experts_total: int
    experts_held: int
    first_expert: int
    expert_width: int
    experts_per_token: int
    shared_width: int
    residual_multiplier: float
    ln_eps: float
    dtype: jnp.dtype
    kda_heads: int
    kda_head_dim: int
    kda_lower_bound: float
    latent_rank: int
    rope_dim: int
    value_dim: int
    rope_theta: float
    dense_width: int
    router_score: str
    expert_groups: int
    expert_groups_kept: int
    routed_scale: float


class HybridLayer(nn.Module):
    """One decoder layer of any kind; a ``dense`` one has a SwiGLU of
    ``dense_width`` where the others have their experts."""

    kind: str
    cfg: "_Dims"
    dense: bool = False

    def setup(self):
        c = self.cfg
        self.ln_mixer = _Scale(c.ln_eps)
        self.ln_ffn = _Scale(c.ln_eps)
        if self.kind == "mamba":
            self.mamba = Mamba2Mixer(c.hidden_size, c.ssm_heads,
                                     c.ssm_head_dim, c.ssm_state,
                                     c.ssm_conv, c.ssm_chunk, c.ln_eps,
                                     c.dtype)
        elif self.kind == "kda":
            self.kda = KdaMixer(c.hidden_size, c.kda_heads, c.kda_head_dim,
                                c.ssm_conv, c.kda_lower_bound,
                                c.ln_eps, c.dtype)
        elif self.kind == "mla":
            self.attention = LatentAttention(
                c.hidden_size, c.num_heads, c.head_dim, c.rope_dim,
                c.value_dim, c.latent_rank, c.rope_theta, c.ln_eps, c.dtype)
        else:
            self.attention = NopeAttention(
                c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
                c.attention_multiplier, c.dtype)
        if self.dense:
            self.mlp = _SharedExpert(c.dense_width, c.dtype)
            return
        self.moe = HeldExperts(c.experts_total, c.experts_held,
                               c.first_expert, c.expert_width,
                               c.experts_per_token, c.dtype,
                               c.router_score, c.expert_groups,
                               c.expert_groups_kept, c.routed_scale)
        self.shared = _SharedExpert(c.shared_width, c.dtype)

    def recurrent(self):
        """The state-carrying mixer of a ``"mamba"`` or ``"kda"`` layer."""
        return self.mamba if self.kind == "mamba" else self.kda

    def ffn(self, x, count):
        """x float32 ``[..., E]`` after the mixer's residual."""
        v = self.ln_ffn(x).astype(self.cfg.dtype)
        if self.dense:
            return x + self.cfg.residual_multiplier * self.mlp(v), \
                jnp.zeros((3,), jnp.int32)
        y, stats = self.moe(v, count)
        return x + self.cfg.residual_multiplier * (
            y + self.shared(v)), stats

    def mixed(self, x, out):
        return x + self.cfg.residual_multiplier * out.astype(jnp.float32)

    def normed(self, x):
        return self.ln_mixer(x).astype(self.cfg.dtype)


class HybridLM(nn.Module):
    """The whole decoder.  ``layer_types`` is the pattern: ``"mamba"`` /
    ``"attention"`` (granite) or ``"kda"`` / ``"mla"`` (Ling) a layer."""

    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    experts_total: int
    experts_held: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    first_expert: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    attention_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    max_position: int = 131072
    ln_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    # the gated delta-rule layers (``"kda"``): heads of dk = dv, the
    # decay's floor a position (log)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_lower_bound: float = -5.0
    # the latent attention layers (``"mla"``; head_dim is the nope width)
    latent_rank: int = 0
    rope_dim: int = 0
    value_dim: int = 0
    rope_theta: float = 10000.0
    # the first ``dense_layers`` layers' SwiGLU, the router, the head
    dense_layers: int = 0
    dense_width: int = 0
    router_score: str = "softmax"
    expert_groups: int = 1
    expert_groups_kept: int = 1
    routed_scale: float = 1.0
    tie_embeddings: bool = True

    pp_stages = 0               # what the engine asks of every model

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_layers(self) -> int:
        """Layers that keep K/V (or the latent) in the block pool."""
        return sum(t in ATTENTION_KINDS for t in self.layer_types)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state a slot."""
        return sum(t in STATE_KINDS for t in self.layer_types)

    @property
    def latent(self) -> bool:
        return "mla" in self.layer_types

    @property
    def kv_heads(self) -> int:
        return 1 if self.latent else self.num_kv_heads

    @property
    def head_size(self) -> int:
        return self.latent_rank + self.rope_dim if self.latent \
            else self.head_dim

    @property
    def counter_names(self) -> tuple:
        """What the step programs return, in order (``HYBRID_COUNTERS``,
        or ``LATENT_COUNTERS`` under latent attention)."""
        return LATENT_COUNTERS if self.latent else HYBRID_COUNTERS

    def paged_cache_geometry(self) -> dict:
        """(heads, width) a token of ONE attention layer keeps in the K
        pool and in the V pool: K and V heads, or the latent ``c`` and the
        shared rotary key."""
        if self.latent:
            return {"k": (1, self.latent_rank), "v": (1, self.rope_dim)}
        return {"k": (self.num_kv_heads, self.head_dim),
                "v": (self.num_kv_heads, self.head_dim)}

    def state_geometry(self) -> dict:
        """Shapes a SLOT holds for ONE recurrent layer."""
        if "kda" in self.layer_types:
            P = self.kda_heads * self.kda_head_dim
            return {"ssm": (self.kda_heads, self.kda_head_dim,
                            self.kda_head_dim),
                    "conv": (self.ssm_conv - 1, 3 * P)}
        I = self.ssm_heads * self.ssm_head_dim
        return {"ssm": (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                "conv": (self.ssm_conv - 1, I + 2 * self.ssm_state)}

    def setup(self):
        kinds = set(self.layer_types)
        bad = kinds - set(STATE_KINDS) - set(ATTENTION_KINDS)
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}: a layer is "
                             f"'mamba', 'kda', 'attention' or 'mla'")
        if len(kinds & set(STATE_KINDS)) > 1 \
                or len(kinds & set(ATTENTION_KINDS)) > 1:
            raise ValueError(
                f"layer_types mixes {sorted(kinds)}: a model has one "
                f"recurrent kind and one attention kind (one state and one "
                f"pool geometry)")
        self.embed = nn.Embed(self.vocab_size, self.hidden_size,
                              param_dtype=jnp.float32)
        dims = _Dims(*(getattr(self, f) for f in _Dims._fields))
        self.layers = [HybridLayer(t, dims, i < self.dense_layers,
                                   name=f"layer_{i}")
                       for i, t in enumerate(self.layer_types)]
        self.ln_f = _Scale(self.ln_eps)
        if not self.tie_embeddings:
            self.lm_head = self.param("lm_head", _init, (self.hidden_size,
                                                         self.vocab_size),
                                      jnp.float32)

    def _embed(self, tok):
        return self.embedding_multiplier \
            * self.embed(tok).astype(jnp.float32)

    def _logits(self, x):
        h = self.ln_f(x).astype(self.dtype)
        if not self.tie_embeddings:
            return jnp.dot(h, self.lm_head.astype(self.dtype),
                           preferred_element_type=jnp.float32) \
                / self.logits_scaling
        return jnp.dot(h, self.embed.embedding.astype(self.dtype).T,
                       preferred_element_type=jnp.float32) \
            / self.logits_scaling

    def __call__(self, tokens, train: bool = False):
        """The uncached forward: tokens ``[B, T]`` -> logits ``[B, T, V]``
        float32, every state starting from zero."""
        B, T = tokens.shape
        geo = self.state_geometry()
        x = self._embed(tokens)
        lens = jnp.full((B,), T, jnp.int32)
        for layer in self.layers:
            u = layer.normed(x)
            if layer.kind in STATE_KINDS:
                out, _, _ = layer.recurrent().chunk(
                    u, jnp.zeros((B,) + geo["ssm"], jnp.float32),
                    jnp.zeros((B,) + geo["conv"], self.dtype), lens)
            else:
                out = layer.attention(u)
            x, _ = layer.ffn(layer.mixed(x, out), None)
        return self._logits(x)

    # ---- against the engine's caches -----------------------------------

    def _stats(self, rows, chunk_tokens, moe, latent_rows=None):
        moe = jnp.stack(moe)                                # [layers, 3]
        first = [rows, chunk_tokens]
        if latent_rows is not None:
            first.append(latent_rows)
        return jnp.stack(first + [jnp.sum(moe[:, 0]), jnp.sum(moe[:, 1]),
                                  jnp.max(moe[:, 2])]).astype(jnp.int32)

    def _latent_rows(self, cache, tables):
        """Latent cache rows the MLA layers' gathers read in one pass:
        every row's whole table, a layer (None without latent
        attention)."""
        if not self.latent:
            return None
        return jnp.int32(self.kv_layers * tables.size * cache.k.shape[3])

    def _paged_trunk(self, parts, cache, pool_v):
        """The layers over the engine's caches for one or two PARTS of
        rows (``_Part``: the decode rows, a grid of chunk rows, or the two
        in that order).  One part runs each layer's mixer whole
        (``part.mix``); two share every matrix that acts row by row: a
        recurrent layer projects both parts' rows in one matmul, runs the
        two parts' cores one after the other on the state arrays, and
        projects out once; an attention layer runs the
        decode rows' call, then the chunk rows'; the expert layer (or dense
        SwiGLU) takes every row in one call.  Returns (the parts' x, cache,
        pool_v, the layers' expert counters)."""
        (pk, pv), N = _flat_pools((cache.k, pool_v))
        ssm, conv, moe = list(cache.ssm), list(cache.conv), []
        xs = [p.x for p in parts]
        ia = im = 0
        for layer in self.layers:
            us = [layer.normed(x) for x in xs]
            if layer.kind in STATE_KINDS:
                outs, ssm[im], conv[im] = _recurrent(
                    layer.recurrent(), parts, us, ssm[im], conv[im])
                im += 1
            else:
                outs = []
                for part, u in zip(parts, us):
                    lag = None
                    if outs:
                        # this part's first write to the pool needs no
                        # value that the part before's read of it
                        # produces, so nothing orders the two and a
                        # compiler may keep a copy of the pool for that
                        # read.  Ordering them by a value does: min(0 or
                        # 1, 0) adds 0, whatever the sum holds
                        lag = jnp.minimum(jnp.isnan(jnp.sum(
                            outs[-1])).astype(jnp.int32), 0)
                    out, pk, pv = part.attend(layer.attention, u, pk, pv,
                                              ia * N, lag)
                    outs.append(out)
                ia += 1
            xs, st = _joined(layer.ffn,
                             [layer.mixed(x, o) for x, o in zip(xs, outs)],
                             [p.count for p in parts])
            moe.append(st)
        pk, pv = _stacked_pools((pk, pv), max(ia, 1))
        return xs, HybridCache(pk, tuple(ssm), tuple(conv)), pv, moe

    def _decode_part(self, tok, tables, pos, live, kernel):
        """The trunk's part of the decode rows: one token a row; ``cache.
        ssm`` / ``cache.conv`` hold one array a recurrent layer, ``[slots,
        ...]``, and row b of the batch IS slot b; rows where ``live`` is
        false (frozen, empty) advance no state and count for nothing."""
        def attend(attention, u, pk, pv, first, lag):
            out, pk, pv = attention.paged(u[:, None], pk, pv,
                                          tables + first, pos, None, kernel)
            return out[:, 0], pk, pv

        return _Part(
            self._embed(tok),
            lambda mixer, u, h, w: mixer.step(u, h, w, live),
            lambda mixer, xd, h, w: mixer.step_core(xd, h, w, live),
            attend, live)

    def _chunk_part(self, toks, n_slots, tables, pos, lens, slots, kernel):
        """The trunk's part of a grid of chunk rows (what
        :meth:`prefill_chunk_paged_ssm` says of them) and (whether each
        row is real, which positions count)."""
        S = toks.shape[1]
        real = slots < n_slots
        read = jnp.minimum(slots, n_slots - 1)
        fresh = pos == 0
        count = (jnp.arange(S)[None, :] < lens[:, None]) & real[:, None]

        def carried(run, hs, ws):
            """``run(h, w)`` from each row's own state (zero where the
            row starts at position 0), written back to its slot."""
            h = jnp.where(fresh[:, None, None, None], 0.0, hs[read])
            w = jnp.where(fresh[:, None, None], 0, ws[read]).astype(ws.dtype)
            y, h, w = run(h, w)
            return (y, hs.at[slots].set(h, mode="drop"),
                    ws.at[slots].set(w, mode="drop"))

        def attend(attention, u, pk, pv, first, lag):
            return attention.paged(u, pk, pv, tables + first,
                                   pos if lag is None else pos + lag,
                                   pos + lens, kernel)

        part = _Part(
            self._embed(toks),
            lambda mixer, u, hs, ws: carried(
                lambda h, w: mixer.chunk(u, h, w, lens), hs, ws),
            lambda mixer, xd, hs, ws: carried(
                lambda h, w: mixer.chunk_core(xd, h, w, lens), hs, ws),
            attend, count)
        return part, real, count

    def decode_step_paged_ssm(self, tok, cache: HybridCache, pool_v,
                              tables, pos, live, kernel="gather"):
        """One token a row (``_decode_part``).  Returns (logits [B, V],
        cache, pool_v, counters int32: ``counter_names``, the expert counts
        summed over the layers and the load the largest of any layer)."""
        (x,), new, pv, moe = self._paged_trunk(
            [self._decode_part(tok, tables, pos, live, kernel)], cache,
            pool_v)
        stats = self._stats(jnp.sum(live), jnp.int32(0), moe,
                            self._latent_rows(cache, tables))
        return self._logits(x), new, pv, stats

    def prefill_chunk_paged_ssm(self, toks, cache: HybridCache, pool_v,
                                tables, pos, lens, slots, kernel="gather"):
        """A chunk of S positions a row: row j of the grid belongs to slot
        ``slots[j]`` (a padding row carries an out-of-range slot: it reads
        the last slot's state and writes nothing), starts at position
        ``pos[j]`` and holds ``lens[j]`` real tokens.  A row at position 0
        starts from a zero state whatever its slot held; padding columns
        advance neither the state nor the convolution's window, and write
        no K/V.  Returns (last-real-position logits [B, V], cache, pool_v,
        counters)."""
        n_slots = cache.ssm[0].shape[0] if cache.ssm else 1
        part, real, count = self._chunk_part(toks, n_slots, tables, pos,
                                             lens, slots, kernel)
        (x,), new, pv, moe = self._paged_trunk([part], cache, pool_v)
        last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
        stats = self._stats(jnp.sum(real), jnp.sum(count), moe,
                            self._latent_rows(cache, tables))
        return self._logits(last)[:, 0], new, pv, stats

    def fused_step_paged_ssm(self, tok, cache: HybridCache, pool_v, tables,
                             pos, live, ctoks, ctabs, cpos, clens, cslots,
                             kernel="gather"):
        """:meth:`decode_step_paged_ssm` of the decode rows, then
        :meth:`prefill_chunk_paged_ssm` of a grid of chunk rows on the
        caches it left, in ONE pass: every matrix that acts row by row (a
        recurrent mixer's projections, the router, the experts, the shared
        expert or dense SwiGLU, the head) takes both kinds of row in one
        call, so a tick reads those weights once, not twice.  A PREFILLING
        row is frozen in the decode rows (``live`` false), so its chunk
        reads the state it would have read after the decode pass.  The
        same caches, chunk logits and live rows' logits as the two passes
        (a frozen row's, which the engine takes no token from, may
        differ); the counters are theirs summed, but for the largest load
        of an expert, which is now over both kinds of row.  Returns (logits [B, V], the chunks'
        last-real-position logits [kb, V], cache, pool_v, counters)."""
        n_slots = cache.ssm[0].shape[0] if cache.ssm else 1
        chunk, real, count = self._chunk_part(ctoks, n_slots, ctabs, cpos,
                                              clens, cslots, kernel)
        (xd, xc), new, pv, moe = self._paged_trunk(
            [self._decode_part(tok, tables, pos, live, kernel), chunk],
            cache, pool_v)
        last = jnp.take_along_axis(xc, (clens - 1)[:, None, None], axis=1)
        latent = self._latent_rows(cache, tables)
        stats = self._stats(
            jnp.sum(live) + jnp.sum(real), jnp.sum(count), moe,
            None if latent is None
            else latent + self._latent_rows(cache, ctabs))
        logits = self._logits(jnp.concatenate([xd, last[:, 0]]))
        B = tok.shape[0]
        return logits[:B], logits[B:], new, pv, stats


class _Part(NamedTuple):
    """What :meth:`HybridLM._paged_trunk` needs of one kind of row: ``x``
    (the embedded rows), ``mix(mixer, u, hs, ws)`` and ``core(mixer,
    projected, hs, ws)`` (a recurrent layer's whole mixer, or the mixer
    between its projections, over the slot-indexed state arrays), ``attend
    (attention, u, pk, pv, first_block, lag)`` (``lag``: an int32 zero that
    orders this part's pool writes after the part before's reads, or None)
    and ``count`` (whose expert picks are counted)."""

    x: jax.Array
    mix: Callable
    core: Callable
    attend: Callable
    count: jax.Array


def _recurrent(mixer, parts, us, hs, ws):
    """A recurrent layer's mixer over the parts of
    :meth:`HybridLM._paged_trunk`: (the parts' outputs, hs, ws)."""
    if len(parts) == 1:
        out, hs, ws = parts[0].mix(mixer, us[0], hs, ws)
        return [out], hs, ws
    gate, proj = mixer.project(jnp.concatenate(
        [u.reshape(-1, u.shape[-1]) for u in us]))
    leads = [u.shape[:-1] for u in us]
    starts = [0, math.prod(leads[0])]
    ys = [None, None]
    # the chunk rows' core first, then the decode rows' on the state
    # arrays it returned: the rows a chunk carries are frozen among the
    # decode rows and the decode rows' slots carry no chunk, so either
    # order gives the same states, and this one keeps fewer temporaries
    # alive (compiled for a v5e: granite's fused program of 4 chunk rows
    # 1.70 GB the other way round, 1.17 GB this way)
    for i in (1, 0):
        lead, n, at = leads[i], math.prod(leads[i]), starts[i]
        mine = jax.tree.map(
            lambda a: a[at:at + n].reshape(lead + a.shape[1:]), proj)
        y, hs, ws = parts[i].core(mixer, mine, hs, ws)
        ys[i] = y.reshape((n,) + y.shape[len(lead):])
    out = mixer._out(jnp.concatenate(ys), gate)
    return _split_rows(out, leads), hs, ws


def _split_rows(y, leads):
    """``y [sum of rows, ...]`` back into arrays of the leading shapes."""
    out, at = [], 0
    for lead in leads:
        n = math.prod(lead)
        out.append(y[at:at + n].reshape(lead + y.shape[1:]))
        at += n
    return out


def _joined(fn, xs, counts):
    """``fn(x, count) -> (y, stats)`` over the rows of every part at once
    (one call, whatever the parts' shapes); one part goes as it is."""
    if len(xs) == 1:
        y, stats = fn(xs[0], counts[0])
        return [y], stats
    E = xs[0].shape[-1]
    y, stats = fn(jnp.concatenate([x.reshape(-1, E) for x in xs]),
                  jnp.concatenate([c.reshape(-1) for c in counts]))
    return _split_rows(y, [x.shape[:-1] for x in xs]), stats
