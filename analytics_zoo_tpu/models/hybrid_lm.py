"""A decoder whose layers are of two kinds — Mamba-2 state-space mixers
and NoPE attention — each followed by routed experts beside a shared
expert (IBM Granite 4.0-H: ``granitemoehybrid``).

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
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.lm import _flat_pools, _stacked_pools
from analytics_zoo_tpu.ops.ssm import (HybridCache, conv_chunk, conv_step,
                                       ssm_chunk_scan, ssm_step)

# what a tick of such a model says of itself (the engine books them to the
# flight record under these names; docs/observability.md)
HYBRID_COUNTERS = ("ssm_rows", "ssm_chunk_tokens", "moe_assignments",
                   "moe_held_assignments", "moe_max_load")

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
    ``w_out [held, F, E]``.
    """

    experts_total: int
    experts_held: int
    first_expert: int
    expert_width: int
    top_k: int
    dtype: jnp.dtype = jnp.bfloat16

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
        top, chosen = jax.lax.top_k(logits, K)              # [N, K]
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

    def chunk(self, u, h, window, lens):
        """u ``[R, T, E]``, h ``[R, H, P, N]``, window ``[R, K - 1, C]``,
        lens ``[R]``: positions at or past ``lens`` advance neither."""
        T = u.shape[1]
        z, xbc, dt = self._split(u)
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
        return self._out(y[:, :T], z), h, window

    def step(self, u, h, window, live):
        """u ``[R, E]``; rows where ``live`` is false keep their state."""
        z, xbc, dt = self._split(u)
        a, moved = conv_step(window, xbc, self.conv_w, self.conv_b)
        window = jnp.where(live[:, None, None], moved, window)
        x, B, C = self._xbc(a)
        dt = jnp.where(live[:, None], self._dt(dt), 0.0)
        y, h = ssm_step(h, x, dt, self._A(), B, C, self.D)
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


class HybridLayer(nn.Module):
    """One decoder layer of either kind."""

    kind: str
    cfg: "_Dims"

    def setup(self):
        c = self.cfg
        self.ln_mixer = _Scale(c.ln_eps)
        self.ln_ffn = _Scale(c.ln_eps)
        if self.kind == "mamba":
            self.mamba = Mamba2Mixer(c.hidden_size, c.ssm_heads,
                                     c.ssm_head_dim, c.ssm_state,
                                     c.ssm_conv, c.ssm_chunk, c.ln_eps,
                                     c.dtype)
        else:
            self.attention = NopeAttention(
                c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
                c.attention_multiplier, c.dtype)
        self.moe = HeldExperts(c.experts_total, c.experts_held,
                               c.first_expert, c.expert_width,
                               c.experts_per_token, c.dtype)
        self.shared = _SharedExpert(c.shared_width, c.dtype)

    def ffn(self, x, count):
        """x float32 ``[..., E]`` after the mixer's residual."""
        v = self.ln_ffn(x).astype(self.cfg.dtype)
        y, stats = self.moe(v, count)
        return x + self.cfg.residual_multiplier * (
            y + self.shared(v)), stats

    def mixed(self, x, out):
        return x + self.cfg.residual_multiplier * out.astype(jnp.float32)

    def normed(self, x):
        return self.ln_mixer(x).astype(self.cfg.dtype)


class HybridLM(nn.Module):
    """The whole decoder.  ``layer_types`` is the pattern, one of
    ``"mamba"`` / ``"attention"`` a layer."""

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

    pp_stages = 0               # what the engine asks of every model

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_layers(self) -> int:
        """Layers that keep K/V in the block pool."""
        return sum(t == "attention" for t in self.layer_types)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state a slot."""
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def head_size(self) -> int:
        return self.head_dim

    def state_geometry(self) -> dict:
        """Shapes a SLOT holds for ONE state-space layer."""
        I = self.ssm_heads * self.ssm_head_dim
        return {"ssm": (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                "conv": (self.ssm_conv - 1, I + 2 * self.ssm_state)}

    def setup(self):
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}: a layer is "
                             f"'mamba' or 'attention'")
        self.embed = nn.Embed(self.vocab_size, self.hidden_size,
                              param_dtype=jnp.float32)
        dims = _Dims(*(getattr(self, f) for f in _Dims._fields))
        self.layers = [HybridLayer(t, dims, name=f"layer_{i}")
                       for i, t in enumerate(self.layer_types)]
        self.ln_f = _Scale(self.ln_eps)

    def _embed(self, tok):
        return self.embedding_multiplier \
            * self.embed(tok).astype(jnp.float32)

    def _logits(self, x):
        h = self.ln_f(x).astype(self.dtype)
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
            if layer.kind == "mamba":
                out, _, _ = layer.mamba.chunk(
                    u, jnp.zeros((B,) + geo["ssm"], jnp.float32),
                    jnp.zeros((B,) + geo["conv"], self.dtype), lens)
            else:
                out = layer.attention(u)
            x, _ = layer.ffn(layer.mixed(x, out), None)
        return self._logits(x)

    # ---- against the engine's caches -----------------------------------

    def _stats(self, rows, chunk_tokens, moe):
        moe = jnp.stack(moe)                                # [layers, 3]
        return jnp.stack([rows, chunk_tokens, jnp.sum(moe[:, 0]),
                          jnp.sum(moe[:, 1]),
                          jnp.max(moe[:, 2])]).astype(jnp.int32)

    def _paged_trunk(self, x, cache, pool_v, mamba, attend, count):
        """The layers over the engine's caches: ``mamba(mixer, u, h, w)``
        and ``attend(attention, u, pk, pv, first_block)`` run a layer's
        mixer of either kind and give its caches back; ``count`` says
        whose expert picks are counted.  Returns (x, cache, pool_v, the
        layers' expert counters)."""
        (pk, pv), N = _flat_pools((cache.k, pool_v))
        ssm, conv, moe = list(cache.ssm), list(cache.conv), []
        ia = im = 0
        for layer in self.layers:
            u = layer.normed(x)
            if layer.kind == "mamba":
                out, ssm[im], conv[im] = mamba(layer.mamba, u, ssm[im],
                                               conv[im])
                im += 1
            else:
                out, pk, pv = attend(layer.attention, u, pk, pv, ia * N)
                ia += 1
            x, st = layer.ffn(layer.mixed(x, out), count)
            moe.append(st)
        pk, pv = _stacked_pools((pk, pv), max(ia, 1))
        return x, HybridCache(pk, tuple(ssm), tuple(conv)), pv, moe

    def decode_step_paged_ssm(self, tok, cache: HybridCache, pool_v,
                              tables, pos, live, kernel="gather"):
        """One token a row.  ``cache.ssm`` / ``cache.conv`` hold one array
        a state-space layer, ``[slots, ...]``, row b of the batch IS slot
        b; rows where ``live`` is false (frozen, empty) advance no state
        and count for nothing.  Returns (logits [B, V], cache, pool_v,
        counters int32 [5]: ``HYBRID_COUNTERS``, the expert counts summed
        over the layers and the load the largest of any layer)."""
        def attend(attention, u, pk, pv, first):
            out, pk, pv = attention.paged(u[:, None], pk, pv,
                                          tables + first, pos, None, kernel)
            return out[:, 0], pk, pv

        x, cache, pv, moe = self._paged_trunk(
            self._embed(tok), cache, pool_v,
            lambda mixer, u, h, w: mixer.step(u, h, w, live), attend, live)
        stats = self._stats(jnp.sum(live), jnp.int32(0), moe)
        return self._logits(x), cache, pv, stats

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
        S = toks.shape[1]
        n_slots = cache.ssm[0].shape[0] if cache.ssm else 1
        real = slots < n_slots
        read = jnp.minimum(slots, n_slots - 1)
        fresh = pos == 0
        count = (jnp.arange(S)[None, :] < lens[:, None]) & real[:, None]

        def mamba(mixer, u, hs, ws):
            h = jnp.where(fresh[:, None, None, None], 0.0, hs[read])
            w = jnp.where(fresh[:, None, None], 0, ws[read]).astype(ws.dtype)
            out, h, w = mixer.chunk(u, h, w, lens)
            return (out, hs.at[slots].set(h, mode="drop"),
                    ws.at[slots].set(w, mode="drop"))

        x, cache, pv, moe = self._paged_trunk(
            self._embed(toks), cache, pool_v, mamba,
            lambda attention, u, pk, pv, first: attention.paged(
                u, pk, pv, tables + first, pos, pos + lens, kernel),
            count)
        last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
        stats = self._stats(jnp.sum(real), jnp.sum(count), moe)
        return self._logits(last)[:, 0], cache, pv, stats
