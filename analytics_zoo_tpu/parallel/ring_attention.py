"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

No reference counterpart (SURVEY.md §2.3 item 6: the reference is a CPU
data-parallel stack and predates LLM-scale training); this is a TPU-first
capability the rebuild treats as core: long sequences are sharded over the
``sp`` axis, each device holds its Q/K/V chunk, and K/V chunks rotate around
the ring via ``lax.ppermute`` (one ICI hop per step) while a numerically
stable online-softmax accumulator builds the exact attention output —
compute overlaps the rotation, memory per device is O(T/sp).

Used inside ``shard_map`` (see ``ring_self_attention``) by the transformer
models when the mesh has sp > 1; with sp == 1 it degenerates to one local
attention step, so models can call it unconditionally.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def _chunk_attn(q, k, v, *, scale, mask):
    """One Q-chunk x K-chunk attention block with f32 accumulators.

    q: [B, Tq, H, D], k/v: [B, Tk, H, D], mask: bool broadcastable to
    [B, Tq, Tk] (or None).  Returns (scores_max [B,H,Tq], exp_sum [B,H,Tq],
    out [B,Tq,H,D]) pieces for online-softmax merging.
    """
    # Operands stay in their input dtype (bf16 on the MXU path);
    # preferred_element_type gives f32 accumulation — softmax math is f32.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        mask = jnp.broadcast_to(mask, logits.shape[:1] + logits.shape[2:])
        logits = jnp.where(mask[:, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # [B,H,Tq]
    # Guard fully-masked rows: exp(-inf - -inf) -> nan; use 0 contribution.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[:, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m_safe, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partial results."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1.transpose(0, 2, 1)[..., None] + \
        o2 * a2.transpose(0, 2, 1)[..., None]
    return m, l, o


def ring_attention(q, k, v, kv_mask=None, *, axis_name: str = "sp",
                   causal: bool = False, scale: Optional[float] = None):
    """Exact attention over a sequence sharded on `axis_name`.

    Must be called inside shard_map/pmap with `axis_name` bound.  Shapes
    (per device): q, k, v: [B, T_local, H, D]; kv_mask: [B, T_local] bool
    (True = attend) rotating around the ring with K/V.  Returns
    [B, T_local, H, D].
    """
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(
        jnp.float32)

    perm = [(j, (j + 1) % sp) for j in range(sp)]
    # positions for causal masking
    q_pos = my * T + jnp.arange(T)

    def attend(i, k_cur, v_cur, mask_cur, m, l, o):
        src = (my - i) % sp  # whose chunk we currently hold
        mask = None
        if causal:
            k_pos = src * T + jnp.arange(T)
            mask = (q_pos[:, None] >= k_pos[None, :])[None]  # [1,Tq,Tk]
        if mask_cur is not None:
            kvm = mask_cur[:, None, :]  # [B,1,Tk]
            mask = kvm if mask is None else (mask & kvm)
        m2, l2, o2 = _chunk_attn(q, k_cur, v_cur, scale=scale, mask=mask)
        return _merge(m, l, o, m2, l2, o2)

    def step(carry, i):
        k_cur, v_cur, mask_cur, m, l, o = carry
        m, l, o = attend(i, k_cur, v_cur, mask_cur, m, l, o)
        # rotate K/V (and their mask) one step around the ring
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = None if mask_cur is None else \
            lax.ppermute(mask_cur, axis_name, perm)
        return (k_nxt, v_nxt, mask_nxt, m, l, o), None

    # Derive the fresh accumulators from q (times zero) so they carry
    # exactly q's device-varying axes — shard_map's type system requires the
    # scan carry to match its (varying) outputs, and which axes vary depends
    # on the enclosing mesh, not just the ring axis.  XLA folds the zeros.
    zero32 = q.astype(jnp.float32) * 0.0  # accumulators are f32
    base = jnp.sum(zero32, axis=-1).transpose(0, 2, 1)  # [B,H,T]
    m0 = base - jnp.inf
    l0 = base
    o0 = zero32
    # The last chunk needs no rotation afterwards (the carry is discarded),
    # so scan sp-1 rotating steps and attend to the final chunk outside —
    # saves one ppermute round (fwd AND bwd) per call.
    carry = (k, v, kv_mask, m0, l0, o0)
    if sp > 1:
        carry, _ = lax.scan(step, carry, jnp.arange(sp - 1))
    k_l, v_l, mask_l, m, l, o = carry
    m, l, o = attend(sp - 1, k_l, v_l, mask_l, m, l, o)
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def full_attention(q, k, v, kv_mask=None, *, causal: bool = False,
                   scale: Optional[float] = None):
    """Single-device reference attention, [B, T, H, D] layout.
    kv_mask: [B, T] bool, True = position may be attended to."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(
        jnp.float32)
    mask = None
    if causal:
        pos = jnp.arange(T)
        mask = (pos[:, None] >= pos[None, :])[None]  # [1,T,T]
    if kv_mask is not None:
        kvm = kv_mask[:, None, :]  # [B,1,T]
        mask = kvm if mask is None else (mask & kvm)
    m, l, o = _chunk_attn(q, k, v, scale=scale, mask=mask)
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ulysses_attention(q, k, v, kv_mask=None, *, axis_name: str = "sp",
                      causal: bool = False):
    """Ulysses-style all-to-all sequence parallelism: exchange the local
    sequence shard for a head shard (one all_to_all over ICI), run EXACT
    full attention on the complete sequence for H/sp heads, and exchange
    back.  The alternative to the ring: 2 all_to_alls total instead of
    sp-1 ppermute rounds, at the cost of requiring heads % sp == 0 and
    holding the full sequence per device for the local heads.

    Must be called inside shard_map with `axis_name` bound; per-device
    shapes q/k/v: [B, T_local, H, D]; kv_mask: [B, T_local] bool.
    """
    sp = lax.axis_size(axis_name)
    if sp == 1:
        return full_attention(q, k, v, kv_mask, causal=causal)
    H = q.shape[2]
    if H % sp:
        raise ValueError(
            f"ulysses needs heads ({H}) divisible by the sp axis ({sp}); "
            "use the ring strategy for this mesh")

    def seq2head(x):    # [B, T/sp, H, D] -> [B, T, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
    mask = None
    if kv_mask is not None:
        mask = lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
    o = full_attention(qg, kg, vg, mask, causal=causal)
    # [B, T, H/sp, D] -> [B, T/sp, H, D]
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ring_self_attention(q, k, v, mesh: Mesh, kv_mask=None, *,
                        causal: bool = False, batch_axes=("dp", "fsdp"),
                        seq_axis: str = "sp", head_axis: str = "tp",
                        strategy: str = "ring"):
    """shard_map wrapper: global [B, T, H, D] arrays sharded
    (B over dp, T over sp, H over tp) -> exact global attention.
    kv_mask: optional [B, T] bool padding mask.

    ``strategy``: "ring" (K/V rotate via ppermute, O(T/sp) memory,
    works for any head count) or "ulysses" (2 all_to_alls exchanging
    seq-shards for head-shards, full attention locally; needs
    local heads % sp == 0).  Degenerates gracefully: any axis missing
    from the mesh is ignored.
    """
    if strategy not in ("ring", "ulysses"):
        # validate BEFORE the degenerate early-returns: a typo'd strategy
        # must fail on the dev box, not first on the production sp mesh
        raise ValueError(f"unknown sp strategy {strategy!r} "
                         "(expected 'ring' or 'ulysses')")
    batch = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    seq = seq_axis if seq_axis in mesh.axis_names else None
    heads = head_axis if head_axis in mesh.axis_names else None
    spec = P(batch, seq, heads, None)
    mspec = P(batch, seq)

    if seq is None:
        # No sequence axis: plain attention; XLA already handles dp/tp
        # sharding of the einsums without manual collectives.
        return full_attention(q, k, v, kv_mask, causal=causal)

    fn = functools.partial(
        ulysses_attention if strategy == "ulysses" else ring_attention,
        axis_name=seq, causal=causal)
    if kv_mask is None:
        mapped = jax.shard_map(lambda q, k, v: fn(q, k, v), mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec)
        return mapped(q, k, v)
    mapped = jax.shard_map(fn, mesh=mesh,
                       in_specs=(spec, spec, spec, mspec), out_specs=spec)
    return mapped(q, k, v, kv_mask)
