"""Flash attention — fused Pallas TPU kernel (fwd + custom-VJP bwd).

No reference counterpart (the reference's TransformerLayer/BERT materialise
full [T, T] score matrices on CPU — ref: zoo pipeline/api/keras/layers
self_attention); this is TPU perf work the rebuild owns: the score matrix
never hits HBM, softmax is computed online block-by-block in VMEM
(O(T) memory instead of O(T^2)), and q·k / p·v ride the MXU in the operand
dtype (bf16 in the transformer stack) with f32 accumulators.

Kernel structure (canonical TPU flash): 3D grid — (batch*heads, q-blocks,
k-blocks) with the k dimension marked ``arbitrary`` so Mosaic pipelines
K/V block DMAs against compute; online-softmax state (running max, sum,
accumulator) lives in VMEM scratch across the k iterations; outputs are
written on the last k step.  Causal runs skip fully-masked blocks.

Interface matches the model stack: q, k, v are [B, T, H, D]; optional
``kv_mask`` [B, Tk] bool (True = attend) covers padding; ``causal`` adds the
autoregressive mask.  On the CPU backend the kernels run in Pallas
interpret mode, so the same code path is unit-testable on the CPU mesh
(SURVEY.md §4 single-box test doctrine); any other non-TPU backend
raises.

Layout notes (Mosaic): per-row stats (max / logsumexp / delta) are kept as
[rows, 1] columns end-to-end — including the HBM residual, shaped
[B*H, T, 1] — so no row->column relayout is ever needed; the key mask is
[B, 1, Tk] int32, read as [1, bk] lane-aligned slices.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/where NaN-free


def _interpret_default() -> bool:
    """Compiled (Mosaic) on a TPU, Pallas interpret mode on an explicit
    CPU platform — the tier-1 reference.  Any other backend is an error:
    a host that was meant to find the chip and did not must not run the
    kernels interpreted and look healthy."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas attention kernels run compiled on 'tpu' or interpreted "
        f"on 'cpu'; the default backend is {backend!r}")


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _causal_mask(s, q0, k0, bq, bk):
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _block_live(causal, qi, kj, bq, bk):
    """False only when the causal mask kills the whole (qi, kj) block."""
    if not causal:
        return True
    return (qi + 1) * bq - 1 >= kj * bk


def _params(interpret, n_arb):
    if interpret:
        return {"interpret": True}
    sem = ("parallel",) * (3 - n_arb) + ("arbitrary",) * n_arb
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=sem)}


# ---------------------------------------------------------------------------
# forward kernel:  grid (B*H, num_q_blocks, num_k_blocks)
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(causal, qi, kj, block_q, block_k))
    def _accumulate():
        q = q_ref[0]                                   # [bq, D] (op dtype)
        k = k_ref[0]                                   # [bk, D]
        v = v_ref[0]
        s = scale * jax.lax.dot_general(               # [bq, bk] f32 accum
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kvm = mask_ref[0]                              # [1, bk] int32
        s = jnp.where(kvm > 0, s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * block_q, kj * block_k,
                             block_q, block_k)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked row: s - m_new would be 0 everywhere (both NEG_INF);
        # subtract 0 instead so exp(NEG_INF) underflows to 0
        m_sub = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        p = jnp.exp(s - m_sub)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # logsumexp residual; fully-masked rows get +big so bwd's
        # exp(s - lse) underflows to 0 instead of exp(-inf - -inf) = 1
        lse_ref[0] = jnp.where(l > 0, m_ref[:] + jnp.log(l_safe), -NEG_INF)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_live(causal, qi, kj, block_q, block_k))
    def _accumulate():
        q = q_ref[0]                                   # [bq, D]
        do = do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]          # [bq, 1]
        k = k_ref[0]                                   # [bk, D]
        v = v_ref[0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kvm = mask_ref[0]
        s = jnp.where(kvm > 0, s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * block_q, kj * block_k,
                             block_q, block_k)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dp = jax.lax.dot_general(                      # dO @ V^T
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] = dq_acc[:] + scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, mask_ref, q_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, block_q, block_k):
    # grid (B*H, num_k_blocks, num_q_blocks) — innermost walks q blocks
    kj, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(causal, qi, kj, block_q, block_k))
    def _accumulate():
        k = k_ref[0]                                   # [bk, D]
        v = v_ref[0]
        kvm = mask_ref[0]                              # [1, bk]
        q = q_ref[0]                                   # [bq, D]
        do = do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]          # [bq, 1]
        s = scale * jax.lax.dot_general(               # [bq, bk]
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.where(kvm > 0, s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * block_q, kj * block_k,
                             block_q, block_k)
        p = jnp.exp(s - lse)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(   # P^T @ dO
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                          # [bq, bk]
        dk_acc[:] = dk_acc[:] + scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing (operands flattened to [B*H, T, D])
# ---------------------------------------------------------------------------

def _fwd_call(q, k, v, mask, *, scale, causal, bq, bk, interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    h_per_b = bh // mask.shape[0]
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk)
    return pl.pallas_call(
        kernel,
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // h_per_b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="zoo_flash_attn_fwd",
        **_params(interpret, 1),
    )(q, k, v, mask)


def _bwd_call(q, k, v, mask, o, lse, do, *, scale, causal, bq, bk,
              interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    h_per_b = bh // mask.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [BH, Tq, 1]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // h_per_b, 0, j)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="zoo_flash_attn_bwd_dq",
        **_params(interpret, 1),
    )(q, k, v, mask, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(bh, tk // bk, tq // bq),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, j, i: (b // h_per_b, 0, j)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="zoo_flash_attn_bwd_dkv",
        **_params(interpret, 1),
    )(k, v, mask, q, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP wrapper (per static config, cached)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _flash_fn(scale, causal, bq, bk, interpret):
    cfg = dict(scale=scale, causal=causal, bq=bq, bk=bk,
               interpret=interpret)

    @jax.custom_vjp
    def fa(q, k, v, mask):
        return _fwd_call(q, k, v, mask, **cfg)[0]

    def fwd(q, k, v, mask):
        o, lse = _fwd_call(q, k, v, mask, **cfg)
        return o, (q, k, v, mask, o, lse)

    def bwd(res, g):
        q, k, v, mask, o, lse = res
        dq, dk, dv = _bwd_call(q, k, v, mask, o, lse, g, **cfg)
        return dq, dk, dv, np.zeros(mask.shape, jax.dtypes.float0)

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention(q, k, v, kv_mask=None, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """Fused attention over [B, T, H, D] operands.

    kv_mask: [B, Tk] bool, True = key position attends (padding mask).
    Padding to block multiples is handled here; padded keys are masked,
    padded query rows are dropped from the output (their grads flow back
    as zeros through the pad's VJP).
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = _interpret_default()
    # Mosaic tiles are (8, 128): block sublane dims must be 8-multiples
    # (T itself gets padded up to the block size below, so rounding is free)
    bq = min(block_q, max(8, -(-Tq // 8) * 8))
    bk = min(block_k, max(8, -(-Tk // 8) * 8))

    # [B, T, H, D] -> [B*H, T, D]
    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qf = _pad_to(flat(q), bq, axis=1)
    kf = _pad_to(flat(k), bk, axis=1)
    vf = _pad_to(flat(v), bk, axis=1)
    mask = jnp.ones((B, Tk), jnp.int32) if kv_mask is None \
        else kv_mask.astype(jnp.int32)
    mask = _pad_to(mask, bk, axis=1)[:, None, :]   # [B, 1, Tk]

    fa = _flash_fn(float(scale), bool(causal), bq, bk, bool(interpret))
    of = fa(qf, kf, vf, mask)
    return of[:, :Tq, :].reshape(B, H, Tq, D).transpose(0, 2, 1, 3)


def sharded_flash_attention(q, k, v, mesh, kv_mask=None, *,
                            causal: bool = False, **kw):
    """flash_attention on a multi-device mesh.

    A Mosaic kernel is a custom call XLA cannot GSPMD-partition, so under a
    dp/tp-sharded train step the plain kernel would force full all-gathers
    (or fail to compile).  Attention is independent per (batch row, head):
    shard_map over the mesh's batch axes (B) and ``tp`` (H) runs the kernel
    on each shard's local block with zero collectives.
    """
    from jax.sharding import PartitionSpec as P

    from analytics_zoo_tpu.parallel.mesh import batch_axes

    batch = batch_axes(mesh) or None
    tp = "tp" if "tp" in mesh.axis_names and mesh.shape["tp"] > 1 else None
    qkv_spec = P(batch, None, tp, None)
    mask_spec = P(batch, None)

    def local(qs, ks, vs, ms):
        return flash_attention(qs, ks, vs, ms, causal=causal, **kw)

    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:1] + k.shape[1:2], bool)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, kv_mask)


# ---------------------------------------------------------------------------
# paged attention (block-pool KV cache, serving/paged_cache.py)
#
# Pool layout is HEAD-MAJOR: ``[N, KH, bs, D]`` (physical block, kv
# head, position-in-block, head dim).  The fused kernel streams one
# (block, head) tile per grid step, so the minor-most two dims of its
# K/V BlockSpec must be the Mosaic-tiled ``(bs, D)`` pair — the same
# page layout jax's production TPU paged-attention kernel uses.  The
# gather fallback and the scatter below address the identical storage.
# ---------------------------------------------------------------------------

KV_SCALE_DTYPE = jnp.bfloat16   # per-(block, position, head) int8 scales


@jax.tree_util.register_pytree_node_class
class QuantKV:
    """int8 KV block arena + per-(block, position, kv-head) scales.

    ``data``: int8 ``[..., N, KH, bs, D]`` (leading dims free — the
    engine stacks a layers axis in front); ``scale``: ``data.shape[:-1]``
    in :data:`KV_SCALE_DTYPE`.  One scale per stored K/V row (amax over
    D / 127) keeps the scatter in :func:`paged_kv_update` local — a
    write never has to re-read or re-scale the rest of its block — and
    at bf16 scales the storage cost is ``D + 2`` bytes per row vs
    ``2*D`` for bf16 K/V: ~1.94x the blocks at equal HBM for D=64.

    Registered as a pytree so it threads OPAQUELY through jit / scan /
    donate_argnums / ``flax.apply`` exactly like the plain array pool it
    replaces; ``__getitem__`` indexes data and scales alike (a layer's
    pool out of the stacked one — for tests and tools: the step programs
    never slice a layer out, see ``models/lm.py:_flat_pools``).
    """

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data, self.scale = data, scale

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __getitem__(self, idx):
        return QuantKV(self.data[idx], self.scale[idx])

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype


def quantize_kv(x, scale_dtype=KV_SCALE_DTYPE):
    """Symmetric per-row int8 quantization over the LAST axis.

    Returns ``(q int8 x.shape, scale scale_dtype x.shape[:-1])`` with
    ``x ~= q * scale``.  The scale is rounded to its STORAGE dtype
    before the divide, so :func:`dequantize_kv` reproduces exactly what
    any reader of the stored (data, scale) pair computes — round-trip
    error is pure integer rounding, identical for the gather fallback
    and the fused kernel.  All-zero rows quantize to (0, scale 1)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(scale_dtype)
    sf = scale.astype(jnp.float32)[..., None]
    q = jnp.clip(jnp.round(xf / sf), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_kv(data, scale):
    """Inverse of :func:`quantize_kv`: f32 ``data * scale[..., None]``."""
    return data.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def _paged_scatter_index(tables, pos, S, bs, N, limit):
    """(physical block, offset) per written position, drop-encoded.

    Logical position p of row b maps to (``tables[b, p // bs]``,
    ``p % bs``); block indices past the table width clamp to the last
    column (the allocator keeps unallocated entries at the sink block),
    and positions ``>= limit[b]`` get the out-of-range block id N so a
    ``mode="drop"`` scatter skips them outright."""
    B = pos.shape[0]
    M = tables.shape[1]
    p = pos[:, None] + jnp.arange(S)[None, :]               # [B, S]
    blk = jnp.minimum(p // bs, M - 1)
    phys = jnp.take_along_axis(tables, blk, axis=1)         # [B, S]
    if limit is not None:
        # out-of-range index + mode="drop" = the write never happens
        phys = jnp.where(p < limit[:, None], phys, N)
    return phys, p % bs


def paged_kv_update(pool_k, pool_v, tables, pos, new_k, new_v,
                    limit=None):
    """Scatter S new K/V rows per batch row into a block-pool cache.

    pool_k/pool_v: ``[N, KH, bs, D]`` — the flat head-major block arena
    (N physical blocks of bs token positions each) — or a
    :class:`QuantKV` pair of the same geometry, in which case the new
    rows are QUANTIZED ON WRITE (:func:`quantize_kv`) and both the int8
    data and the per-row scales scatter through the same index.
    tables: ``[B, M]`` int32 — row b's logical block j lives in
    physical block ``tables[b, j]``.  pos: ``[B]`` int32 — row b's
    tokens land at logical positions ``pos[b] .. pos[b]+S-1``.
    new_k/new_v: ``[B, S, KH, D]``.

    Logical position p maps to (physical block ``tables[b, p // bs]``,
    offset ``p % bs``); positions whose logical block index exceeds the
    table width clamp to the last table entry, which the allocator
    keeps pointed at the sink block for anything unallocated, so
    overshoot writes land in garbage space instead of a live block.
    Distinctness contract (the allocator's invariant, not checked
    here): every (row, position) a caller actually cares about maps to
    a PRIVATE tail block of that row, so real writes never collide;
    sink-block collisions are garbage-on-garbage.

    Speculative verify rides this same scatter: the engine writes k+1
    positions per row per round (``S = k+1``) and REJECTION IS POINTER
    ROLLBACK — the next round re-enters with ``pos`` advanced only past
    the accepted prefix, so rejected entries are overwritten in place
    before any attention read can reach them (reads mask to ``<= pos``)
    and no block is ever copied.  Rejected positions that spill past a
    row's allocated table clamp into the sink block per the rule above,
    which is why the engine only has to allocate blocks through
    ``pos + k`` rather than the worst-case round end.

    ``limit`` (``[B]`` int32, optional): row b's writes at logical
    positions ``>= limit[b]`` are DROPPED outright.  Chunked prefill
    passes its per-row true length here: with tables SLICED to a narrow
    ``[B, M']`` window (bounded compile shapes proportional to the fill
    frontier, not the max sequence), a padding position past the window
    would otherwise clamp to table column M'-1 — a live frontier block
    — and corrupt real K/V.  Reads are unaffected; attention masking is
    :func:`paged_attention`'s job.
    """
    quant = isinstance(pool_k, QuantKV)
    N, KH, bs, D = (pool_k.data if quant else pool_k).shape
    S = new_k.shape[1]
    phys, off = _paged_scatter_index(tables, pos, S, bs, N, limit)
    # every INDEXED dimension (block, kv head, offset) leads and the
    # window is the trailing [D] row: XLA then scatters into the pool
    # in the layout it already has, in place on a donated buffer.
    # ``pool.at[phys, :, off]`` (kv heads inside the window, between
    # the two indexed dims) made the TPU compiler transpose the whole
    # operand around every write.  KH stays an axis of its own, so a
    # pool sharded over ``tp`` on KH partitions the same way.  The
    # indexed dims lead the result: [B, S, KH, D], new_k's own layout
    # ([B, S, KH] for the scales).
    idx = (phys[:, :, None], jnp.arange(KH)[None, None, :],
           off[:, :, None])

    def put(pool, rows):
        return pool.at[idx].set(rows.astype(pool.dtype), mode="drop")

    if quant:
        qk, sk = quantize_kv(new_k, pool_k.scale.dtype)
        qv, sv = quantize_kv(new_v, pool_v.scale.dtype)
        return (QuantKV(put(pool_k.data, qk), put(pool_k.scale, sk)),
                QuantKV(put(pool_v.data, qv), put(pool_v.scale, sv)))
    return put(pool_k, new_k), put(pool_v, new_v)


# ---------------------------------------------------------------------------
# fused paged-attention kernel
#
# Grid (B, KH // hb): one grid step per (batch row, group of ``hb`` KV
# heads), and the walk over the row's blocks is a loop INSIDE the step
# that ends at the row's frontier: ``nb = (pos[b] + S - 1) // bs + 1``
# blocks (at most the table's M), whatever the table's width.  The pools
# stay in HBM (``pl.ANY``); block j of row b is fetched by one async copy
# per tensor, ``pool[tables[b, j], h*hb:(h+1)*hb]`` = ``[hb, bs, D]``
# (the pool is ``[N, KH, bs, D]``, so all heads of a block are ONE
# contiguous read), into one of two VMEM slots while the other slot is
# computed on; the last block's iteration fetches the NEXT grid step's
# first block instead, so a row's first copy is hidden behind the row
# before it (the slot the next step starts in rides in SMEM scratch;
# both grid dimensions are ``arbitrary``: the steps run in order on the
# one core a v5e has).  ``tables``/``pos`` are scalar-prefetch operands.
# The [B, M*bs, KH, D] gather is never materialised, a table column past
# a row's frontier costs nothing, and a slot that holds nothing
# (``pos`` 0, table all sink: the engine re-pins it each pass) costs one
# block of the sink.
#
# What a step carries (measured on a v5e at B = 32, M = 10, bs = 256,
# D = 128; PERF.md, PR 33): a grid step's fixed cost is ~0.35 us and a
# [256, 128] bf16 tile takes 0.08 us at 819 GB/s, so one KV head and one
# table column a step — grid (B, KH, M), 2560 steps a layer on Mistral —
# was bound by the steps, not by HBM.  With all 8 heads a copy moves
# 1 MiB (K and V) in ~1.3 us and the kernel runs at 600-720 GB/s.
#
# Heads per step (``_paged_heads_per_step``): the largest divisor of KH
# whose tiles fit a VMEM budget of 8 MiB, half of the 16 MiB a kernel
# may scope on a v5e.  Per head: the query tile and the f32 output tile
# (both double-buffered by the pipeline), the f32 accumulator, the m/l
# columns (lane-padded to 128), two slots each of a K and a V block, and
# three [SGp, bs] f32 temporaries (logits, probabilities, their cast).
# At S = 1 (SGp = 8) that is ~0.3 MiB a head: every head of every
# configuration in one step.  At a chunk's S = 256 it is 6.3 MiB
# (Mistral, SGp 1024) or 9.3 MiB (Qwen, SGp 1536) a head: one head a
# step, as before — there the matmul of a step (0.7-1 us) hides the
# step's cost and nothing is to gain.
#
# Queries are regrouped head-major ([B, KH, S*G, D], row r = s*G + g,
# padded to 8 sublanes): each head of a step owns ALL G query heads of
# its KV head, which is what makes grouped-query attention free here;
# the heads of a step are the batch axis of one ``dot_general``.
# Masking matches the gather fallback exactly — query s attends logical
# positions <= pos[b] + s; in-block tails mask element-wise to NEG_INF.
#
# int8 pools add the two scale operands, a row's [M, hb, 1, bs] a step:
# k-scales multiply the logits columns post-matmul, v-scales fold into p
# pre-matmul — both in-register, algebraically identical to dequantizing
# the tiles.
# ---------------------------------------------------------------------------

_PAGED_VMEM_BUDGET = 8 * 2 ** 20


def _paged_heads_per_step(SGp, KH, bs, D, q_itemsize, kv_itemsize):
    """KV heads one grid step of the fused kernel carries: the largest
    divisor of ``KH`` whose VMEM reckoning (the comment above) fits
    ``_PAGED_VMEM_BUDGET``, and never less than one."""
    per_head = (2 * SGp * D * q_itemsize        # query tile, two buffers
                + 2 * SGp * D * 4               # f32 output tile, two
                + SGp * D * 4                   # accumulator
                + 2 * SGp * 128 * 4             # m, l (lane-padded)
                + 4 * bs * D * kv_itemsize      # K, V: two slots each
                + 3 * SGp * bs * 4)             # logits, p, p's cast
    fit = max(1, _PAGED_VMEM_BUDGET // per_head)
    return max(h for h in range(1, KH + 1) if KH % h == 0 and h <= fit)


def _paged_block_update(q, k, v, sk, sv, j, pos, acc_ref, m_ref, l_ref, *,
                        scale, bs, G):
    """Fold logical block ``j`` of a row into the online-softmax state of
    the step's heads: q ``[hb, SGp, D]``, k/v ``[hb, bs, D]``, the int8
    scales sk/sv ``[hb, 1, bs]`` (None for a plain pool)."""
    if sk is not None:
        # one operand dtype into the MXU; |k| <= 127 is exact in
        # bf16 and f32 alike, so the cast changes no product
        k = k.astype(q.dtype)
    s = scale * jax.lax.dot_general(                   # [hb, SGp, bs] f32
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    if sk is not None:
        s = s * sk.astype(jnp.float32)                 # [hb, 1, bs] bcast
    lpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    qrow = jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1) // G                    # row r -> s=r//G
    s = jnp.where(lpos <= pos + qrow, s, NEG_INF)
    m_prev, l_prev = m_ref[:], l_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # fully-masked row: subtract 0 instead of NEG_INF so
    # exp(NEG_INF) underflows to 0 (same trick as _fwd_kernel)
    m_sub = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
    p = jnp.exp(s - m_sub)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if sv is not None:
        # fold the v scales into p's columns: (p * sv) @ v_int8
        # == p @ (v_int8 * sv[:, None]) without a [bs, D] dequant
        p = p * sv.astype(jnp.float32)
        v = v.astype(jnp.float32)
    else:
        p = p.astype(v.dtype)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _paged_state_init(acc_ref, m_ref, l_ref):
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _paged_state_out(o_ref, acc_ref, l_ref):
    l = l_ref[:]
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _paged_fused_kernel(tables_ref, pos_ref, *refs, scale, bs, G, S, hb,
                        quant):
    if quant:
        (q_ref, k_hbm, v_hbm, sk_ref, sv_ref, o_ref,
         kbuf, vbuf, sem, slot_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, slot_ref, acc_ref, m_ref, l_ref) = refs
    b, h = pl.program_id(0), pl.program_id(1)
    nB, nH = pl.num_programs(0), pl.num_programs(1)
    M = tables_ref.shape[1]

    def copies(row, hg, j, slot):
        blk = tables_ref[row, j]
        heads = pl.ds(hg * hb, hb)
        return (pltpu.make_async_copy(k_hbm.at[blk, heads], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[blk, heads], vbuf.at[slot],
                                      sem.at[1, slot]))

    @pl.when((b == 0) & (h == 0))
    def _first():
        slot_ref[0] = 0
        for c in copies(b, h, 0, 0):
            c.start()

    pos = pos_ref[b]
    # block j holds logical positions [j*bs, (j+1)*bs); the furthest
    # position any query row attends is pos + S - 1
    nb = jnp.minimum((pos + (S - 1)) // bs, M - 1) + 1
    slot0 = slot_ref[0]     # where the step before put this step's block 0
    _paged_state_init(acc_ref, m_ref, l_ref)

    def block(j, carry):
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < nb)
        def _next_block():
            for c in copies(b, h, j + 1, 1 - slot):
                c.start()

        @pl.when((j + 1 == nb) & ((b + 1 < nB) | (h + 1 < nH)))
        def _next_step():
            wrap = h + 1 == nH
            for c in copies(jnp.where(wrap, b + 1, b),
                            jnp.where(wrap, 0, h + 1), 0, 1 - slot):
                c.start()

        for c in copies(b, h, j, slot):
            c.wait()
        _paged_block_update(
            q_ref[0], kbuf[slot], vbuf[slot],
            sk_ref[0, j] if quant else None,
            sv_ref[0, j] if quant else None,
            j, pos, acc_ref, m_ref, l_ref, scale=scale, bs=bs, G=G)
        return carry

    jax.lax.fori_loop(0, nb, block, None)
    slot_ref[0] = (slot0 + nb) % 2
    _paged_state_out(o_ref, acc_ref, l_ref)


def _paged_fused_kernel_narrow(tables_ref, pos_ref, *refs, scale, bs, G, S,
                               quant):
    """The walk by BlockSpecs, for heads under a lane tile wide (grid
    ``(B, KH // hb, M)``, the M dimension ``arbitrary``): a column past
    the row's frontier re-names the tile the step before it had, so it
    copies nothing, and skips the update."""
    if quant:
        (q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, j = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        _paged_state_init(acc_ref, m_ref, l_ref)

    @pl.when(j * bs <= pos + (S - 1))
    def _accumulate():
        _paged_block_update(
            q_ref[0], k_ref[0], v_ref[0],
            sk_ref[0, 0] if quant else None,
            sv_ref[0, 0] if quant else None,
            j, pos, acc_ref, m_ref, l_ref, scale=scale, bs=bs, G=G)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        _paged_state_out(o_ref, acc_ref, l_ref)


# jitted on its own: a step program calls it once a layer (twice in a
# fused decode + chunk program) with the same shapes, and a call of a
# jitted function is traced and lowered to Mosaic ONCE a program, not
# once a call site; XLA inlines the calls
@functools.partial(jax.jit, static_argnames=("interpret", "looped"))
def _paged_attention_fused(q, pool_k, pool_v, tables, pos, interpret,
                           looped=None):
    B, S, H, D = q.shape
    quant = isinstance(pool_k, QuantKV)
    kd = pool_k.data if quant else pool_k
    vd = pool_v.data if quant else pool_v
    N, KH, bs, _ = kd.shape
    if H % KH:
        raise ValueError(f"query heads {H} not a multiple of KV heads "
                         f"{KH}")
    G = H // KH
    M = tables.shape[1]
    SG = S * G
    SGp = -(-SG // 8) * 8          # Mosaic sublane multiple
    hb = _paged_heads_per_step(SGp, KH, bs, D, q.dtype.itemsize,
                               kd.dtype.itemsize)
    if looped is None:
        # Mosaic pads an HBM ref whose rows are under a lane tile wide
        # and then refuses to slice a block out of it; the interpreter
        # has no lanes (``looped`` is an argument for the tests alone)
        looped = interpret or D % 128 == 0
    # [B, S, H, D] -> [B, KH, S*G, D]: row r of kv head h is query
    # (s = r // G, head h*G + r % G), padded rows are mask-dead
    qf = q.reshape(B, S, KH, G, D).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(B, KH, SG, D)
    if SGp != SG:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, SGp - SG), (0, 0)))
    scale = 1.0 / float(np.sqrt(D))
    operands = [qf, kd, vd]
    state = [pltpu.VMEM((hb, SGp, D), jnp.float32),
             pltpu.VMEM((hb, SGp, 1), jnp.float32),
             pltpu.VMEM((hb, SGp, 1), jnp.float32)]
    if quant:
        # the scales of the blocks the tables name, gathered here
        # ([B, M, KH, bs]: some KB): handing the kernel the whole
        # [N, KH, bs] array made XLA re-lay ALL of it out, padded to
        # Mosaic's tiles, before every call (the scatter that writes the
        # scales keeps them in a layout of its own).  They ride as
        # [.., KH, 1, bs]: with the unit axis the tile's last two dims
        # (1, bs) equal the array's, any ``hb`` is legal, and block j's
        # scales are a leading-axis index
        operands += [jnp.take(pool_k.scale, tables, axis=0)[:, :, :, None],
                     jnp.take(pool_v.scale, tables, axis=0)[:, :, :, None]]
    if looped:
        kernel = functools.partial(_paged_fused_kernel, scale=scale, bs=bs,
                                   G=G, S=S, hb=hb, quant=quant)
        grid = (B, KH // hb)
        qspec = pl.BlockSpec((1, hb, SGp, D),
                             lambda b, h, t, p: (b, h, 0, 0))
        kvspec = pl.BlockSpec(memory_space=pl.ANY)
        sspec = pl.BlockSpec((1, M, hb, 1, bs),
                             lambda b, h, t, p: (b, 0, h, 0, 0))
        scratch = [pltpu.VMEM((2, hb, bs, D), kd.dtype),
                   pltpu.VMEM((2, hb, bs, D), vd.dtype),
                   pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.SMEM((1,), jnp.int32)] + state
    else:
        kernel = functools.partial(_paged_fused_kernel_narrow, scale=scale,
                                   bs=bs, G=G, S=S, quant=quant)
        grid = (B, KH // hb, M)
        qspec = pl.BlockSpec((1, hb, SGp, D),
                             lambda b, h, j, t, p: (b, h, 0, 0))

        def live(b, j, p):      # column j, held at the row's frontier
            return jnp.minimum(j, jnp.minimum((p[b] + (S - 1)) // bs,
                                              M - 1))
        kvspec = pl.BlockSpec(
            (1, hb, bs, D),
            lambda b, h, j, t, p: (t[b, live(b, j, p)], h, 0, 0))
        sspec = pl.BlockSpec(
            (1, 1, hb, 1, bs),
            lambda b, h, j, t, p: (b, live(b, j, p), h, 0, 0))
        scratch = state
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[qspec, kvspec, kvspec] + ([sspec, sspec] if quant else []),
        out_specs=qspec, scratch_shapes=scratch)
    params = ({"interpret": True} if interpret else
              {"compiler_params": pltpu.CompilerParams(
                  dimension_semantics=("arbitrary",) * len(grid))})
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, SGp, D), jnp.float32),
        name="zoo_paged_attn_decode",
        **params,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), *operands)
    out = out[:, :, :SG, :].reshape(B, KH, S, G, D)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, D)


def _paged_attention_fused_tp(q, pool_k, pool_v, tables, pos, mesh,
                              kv_sharded, interpret):
    """The fused kernel under a tensor-parallel mesh.

    A Mosaic kernel is a custom call XLA cannot GSPMD-partition, so the
    tp-sharded pool is read through :func:`shard_map` instead: each chip
    runs :func:`_paged_attention_fused` on its LOCAL pool shard — the
    kv heads of a step shrink tp-fold (``KH/tp`` heads of a block a
    copy) and the block-table indirection needs no change because
    tables/pos are replicated host-side state.  Correctness rides the
    head-contiguity of the layout: query head ``h = kh*G + g`` (GQA
    fold), so a contiguous shard of the KV heads owns exactly the
    contiguous shard of the query heads that attend through it — zero
    collectives, like :func:`sharded_flash_attention`.

    ``kv_sharded=False`` is the divisibility hatch (``KH % tp != 0``:
    the engine replicates the pool instead of sharding it) — every spec
    drops to replicated and each chip redundantly computes the full
    attention, bitwise-equal across chips.

    int8 ``QuantKV`` pools are unpacked into (data, scale) leaves so the
    per-block scales shard on the same kv-heads axis as the data — one
    spec per leaf, rebuilt into ``QuantKV`` inside the per-chip body.
    """
    from jax.sharding import PartitionSpec as P

    quant = isinstance(pool_k, QuantKV)
    KH = (pool_k.data if quant else pool_k).shape[1]
    tp = "tp" if ("tp" in mesh.axis_names and mesh.shape["tp"] > 1
                  and kv_sharded) else None
    if tp is not None and KH % mesh.shape["tp"]:
        raise ValueError(
            f"kv heads {KH} not divisible by tp={mesh.shape['tp']}: a "
            f"pool this shape must be replicated (pass kv_sharded=False)")
    q_spec = P(None, None, tp, None)        # [B, S, H, D]: heads
    pool_spec = P(None, tp, None, None)     # [N, KH, bs, D]: kv heads
    scale_spec = P(None, tp, None)          # [N, KH, bs]: kv heads
    tab_spec = P(None, None)                # replicated host-side state
    pos_spec = P(None)

    if quant:
        def local(qs, kd, ksc, vd, vsc, t, p):
            return _paged_attention_fused(qs, QuantKV(kd, ksc),
                                          QuantKV(vd, vsc), t, p,
                                          interpret)
        in_specs = (q_spec, pool_spec, scale_spec, pool_spec,
                    scale_spec, tab_spec, pos_spec)
        operands = (q, pool_k.data, pool_k.scale, pool_v.data,
                    pool_v.scale, tables, pos)
    else:
        def local(qs, kd, vd, t, p):
            return _paged_attention_fused(qs, kd, vd, t, p, interpret)
        in_specs = (q_spec, pool_spec, pool_spec, tab_spec, pos_spec)
        operands = (q, pool_k, pool_v, tables, pos)
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                      out_specs=q_spec, check_vma=False)(*operands)


def paged_attention(q, pool_k, pool_v, tables, pos, *,
                    kernel: str = "gather",
                    interpret: Optional[bool] = None,
                    mesh=None, kv_sharded: bool = True):
    """Block-causal attention of S query tokens per row against a PAGED
    KV cache: keys/values live behind per-row block tables in one flat
    head-major ``[N, KH, bs, D]`` pool (or a :class:`QuantKV` int8 pool
    of the same geometry), so co-resident sequences share physical
    blocks (prefix caching) and only occupy the blocks they have
    actually filled.

    q: ``[B, S, H, D]`` (already rope'd/scaled upstream conventions —
    this op applies the 1/sqrt(D) scale itself, matching the dense
    decode paths); pos: ``[B]`` int32, row b's queries sit at logical
    positions ``pos[b] .. pos[b]+S-1`` and query j attends logical cache
    positions ``<= pos[b]+j`` (its own K/V must already be in the pool —
    call :func:`paged_kv_update` first; write-then-read inside one jit
    is a plain data dependency).  ``KH <= H`` is grouped-query
    attention: q regroups ``[B, S, KH, G, D]`` so each KV head serves
    its G query heads without materialising expanded K/V.  Output is
    f32 (the accumulation dtype) under both kernels.

    The table width M is a free parameter: callers may pass a SLICED
    ``[B, M']`` table whose window covers every position ``<= pos[b] +
    S - 1`` they attend — chunked prefill does exactly this so the
    attention cost tracks the fill frontier (bucketed for a bounded
    compile count), not the max sequence length.

    ``kernel`` selects the implementation; both honor the identical
    masking/GQA/quantization contract, so greedy decode is
    token-identical across them:

    - ``"fused"`` — the Pallas TPU kernel above: grid ``(B, KH // hb)``,
      one step per row and group of ``hb`` KV heads (all of them at
      decode and verify widths, one at a chunk's: the VMEM rule of
      ``_paged_heads_per_step``); inside a step a loop over the row's
      live blocks — up to its frontier ``(pos[b] + S - 1) // bs``,
      whatever M is — copies ``[hb, bs, D]`` of K and of V HBM->VMEM
      through the scalar-prefetched block table, double-buffered, the
      next step's first block fetched under this step's last; online
      softmax in VMEM scratch (the dense flash kernel's structure),
      int8 scales applied in-register.  A row whose table is all sink
      is cheap only if its ``pos`` is small: the engine holds an empty
      slot's at 0.  The decode hot path on TPU.
    - ``"gather"`` — the ``jnp.take`` fallback: one materialised
      ``[B, M, KH, bs, D]`` gather (int8 pools dequantize the gathered
      rows) then the masked einsum-softmax the dense decode path runs,
      f32 accumulation.  The CPU / interpret-free reference path —
      tier-1 parity tests pin the fused kernel (in Pallas interpret
      mode) against it.

    ``interpret`` (fused only): run the kernel in Pallas interpret mode;
    defaults to compiled on TPU and interpreted on CPU, like
    :func:`flash_attention` (any other backend raises).

    ``mesh`` (fused only): run the kernel per-chip under
    :func:`shard_map` — the tp-sharded-pool read path
    (:func:`_paged_attention_fused_tp`).  ``kv_sharded`` says whether
    the pool actually shards over ``tp`` on the kv-heads dim (the
    engine's default layout) or is replicated (the ``KH % tp != 0``
    hatch); it must match the pool's real placement.  The gather
    fallback ignores both — ``jnp.take`` is GSPMD-partitionable as-is.
    """
    if kernel not in ("gather", "fused"):
        raise ValueError(f"kernel must be 'gather' or 'fused', got "
                         f"{kernel!r}")
    if kernel == "fused":
        if interpret is None:
            interpret = _interpret_default()
        if mesh is not None:
            return _paged_attention_fused_tp(q, pool_k, pool_v, tables,
                                             pos, mesh, kv_sharded,
                                             bool(interpret))
        return _paged_attention_fused(q, pool_k, pool_v, tables, pos,
                                      bool(interpret))
    B, S, H, D = q.shape
    quant = isinstance(pool_k, QuantKV)
    N, KH, bs, _ = (pool_k.data if quant else pool_k).shape
    if H % KH:
        raise ValueError(f"query heads {H} not a multiple of KV heads "
                         f"{KH}")
    G = H // KH
    M = tables.shape[1]
    L = M * bs

    def gathered(pool):
        # [B, M] tables -> [B, M*bs(=L), KH, D] rows: logical position
        # l of row b is pool[tables[b, l // bs], :, l % bs]
        if isinstance(pool, QuantKV):
            data = jnp.take(pool.data, tables, axis=0)  # [B,M,KH,bs,D]
            cache = dequantize_kv(data,
                                  jnp.take(pool.scale, tables, axis=0))
        else:
            cache = jnp.take(pool, tables, axis=0)
        return jnp.moveaxis(cache, 2, 3).reshape(B, L, KH, D)

    cache_k = gathered(pool_k)
    cache_v = gathered(pool_v)
    p = pos[:, None] + jnp.arange(S)[None, :]               # [B, S]
    mask = (jnp.arange(L)[None, None, :]
            <= p[:, :, None])[:, None, None, :, :]          # [B,1,1,S,L]
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    qg = q.reshape(B, S, KH, G, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, cache_k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(cache_v.dtype),
                   cache_v, preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, D)
