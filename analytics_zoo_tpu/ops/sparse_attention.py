"""Learned sparse attention: an indexer's cache beside K/V, selection inside
paged attention.

A model with an indexer (models/lm.py, ``indexer_topk``) caches ONE index
key a token and layer beside K and V, in an arena of its own on the same
block table: ``[N, 1, bs, ID]``.  A query scores every cached index key of
its row (float32), keeps the exact top ``topk`` positions at or before its
own, and attends those alone.  Everything here is a sibling of
``ops/flash_attention.py``'s paged functions: a model without an indexer
calls none of it, and its programs do not change.

- decode (S = 1): ``lax.top_k`` gives the positions; K and V of those
  positions ONLY are gathered through the table (``topk`` token rows a
  row and layer, whatever the context) and attended.
- a chunk (S > 1): each query has its own set, so the chunk computes
  densely under the selection's mask — the same mathematics; the k-th
  largest score of a query is found by a binary search over the ordered
  bit pattern (32 counting passes, exact, no sort).  A sparse chunk read
  is the first perf_opt to follow (PERF.md).

No Pallas kernel here: scoring, selection and the gather are XLA.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.flash_attention import _paged_scatter_index


class IndexedKeys(NamedTuple):
    """The key side of the cache of a model with an indexer
    (``TransformerLM.indexer_topk``): the K pool and, on the same block
    ids, the pool of index keys.  A pytree, so it stands wherever the
    engine holds, donates, zeroes or resizes "the K pool"; the V pool
    stays a plain array."""

    k: jax.Array          # [layers, N, 1, bs, KH*D]: token-major rows
    index: jax.Array      # [layers, N, 1, bs, ID]


def paged_index_update(pool_i, tables, pos, new_i, limit=None):
    """Scatter S new index keys a row into the index-key arena
    ``[N, 1, bs, ID]`` through the same tables, with the same ``limit``
    guard and the same drop encoding as :func:`paged_kv_update`.
    new_i: ``[B, S, ID]``."""
    N, _, bs, _ = pool_i.shape
    phys, off = _paged_scatter_index(tables, pos, new_i.shape[1], bs, N,
                                     limit)
    return pool_i.at[phys, 0, off].set(new_i.astype(pool_i.dtype),
                                       mode="drop")


def index_scores(qi, w, ki, qpos):
    """The indexer's score of every cached position for every query:
    ``I[b, s, l] = IH^-1/2 * ID^-1/2 * sum_j w[b, s, j] * relu(qi[b, s, j]
    . ki[b, l])`` in float32, ``-inf`` where ``l > qpos[b, s]``.

    qi ``[B, S, IH, ID]``, w ``[B, S, IH]``, ki ``[B, L, ID]``, qpos
    ``[B, S]`` int32.  A chunk (S > 1) goes one index head at a time, so
    the ``[B, S, IH, L]`` array (half a GB for a 512-token chunk at 16 k
    positions) never exists; a decode row (S = 1) scores all its heads in
    one matmul (``[B, IH, L]`` is 17 MB at 16 rows and 16 k positions; the
    head-by-head scan ran on the vector unit, 2.3 ms a layer against 0.34;
    PERF.md).  The heads are weighted and summed elementwise either way: a
    matmul would round the float32 ReLU outputs to bf16 on the TPU."""
    B, S, IH, ID = qi.shape
    L = ki.shape[1]
    kf = ki.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if S == 1:
        s = jnp.einsum("bhd,bld->bhl", qi[:, 0].astype(jnp.float32), kf,
                       preferred_element_type=jnp.float32)
        acc = jnp.sum(wf[:, 0, :, None] * jax.nn.relu(s), axis=1)[:, None]
    else:
        def head(acc, hw):
            qh, wh = hw                                 # [B, S, ID], [B, S]
            s = jnp.einsum("bsd,bld->bsl", qh.astype(jnp.float32), kf,
                           preferred_element_type=jnp.float32)
            return acc + wh[..., None] * jax.nn.relu(s), None

        acc, _ = jax.lax.scan(
            head, jnp.zeros((B, S, L), jnp.float32),
            (jnp.moveaxis(qi, 2, 0), jnp.moveaxis(wf, 2, 0)))
    acc = acc * (1.0 / float(np.sqrt(IH * ID)))
    live = jnp.arange(L)[None, None, :] <= qpos[:, :, None]
    return jnp.where(live, acc, -jnp.inf)


def topk_mask(scores, k: int):
    """Boolean mask of the exact top ``k`` entries of every row of
    ``scores`` ``[..., L]`` float32 (ties to the lower position); ``-inf``
    entries are never selected, so a row with fewer than ``k`` finite
    entries keeps all of them.

    The k-th largest value is found without a sort: floats map to unsigned
    integers of the same order, and 32 counting passes fix the k-th
    largest key bit by bit.  Only where a row has ties AT the threshold
    (exact zeros of the ReLU can) does the position-ordered tie-break
    run."""
    L = scores.shape[-1]
    finite = scores > -jnp.inf
    if k >= L:
        return finite
    u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where((u >> 31) == 1, ~u, u | jnp.uint32(0x80000000))

    def bit(i, pre):
        cand = pre | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        cnt = jnp.sum(key >= cand[..., None], axis=-1)
        return jnp.where(cnt >= k, cand, pre)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    ge = (key >= kth[..., None]) & finite
    want = jnp.minimum(jnp.sum(finite, axis=-1), k)

    def tie_break(_):
        gt = (key > kth[..., None]) & finite
        eq = ge & ~gt
        room = (want - jnp.sum(gt, axis=-1))[..., None]
        return gt | (eq & (jnp.cumsum(eq, axis=-1) <= room))

    return jax.lax.cond(jnp.any(jnp.sum(ge, axis=-1) != want),
                        tie_break, lambda _: ge, None)


def masked_attention(q, k, v, mask):
    """Grouped-query attention of q ``[B, S, H, D]`` over k, v ``[B, L,
    KH, D]`` under a boolean mask ``[B, S, L]``; float32 out ``[B, S, H,
    D]``.  One KV head and one block of 128 queries at a time, so the
    float32 logits held at once are ``[B, G, 128, L]``, not ``[B, H, S,
    L]`` (1.1 GB a row for a 512-token chunk at 16 k positions)."""
    B, S, H, D = q.shape
    L, KH = k.shape[1], k.shape[2]
    G = H // KH
    qb = 128 if S > 128 and S % 128 == 0 else S
    nq = S // qb
    scale = 1.0 / float(np.sqrt(D))
    # [KH, nq, B, qb, G, D] / [KH, B, L, D]
    qh = q.reshape(B, nq, qb, KH, G, D).transpose(3, 1, 0, 2, 4, 5)
    kh, vh = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)
    mb = mask.reshape(B, nq, qb, L).transpose(1, 0, 2, 3)   # [nq,B,qb,L]

    def one_head(hkv):
        qs, ks, vs = hkv

        def one_block(qm):
            qx, mx = qm                         # [B, qb, G, D], [B, qb, L]
            # float32 operands at the default precision: on the TPU the
            # MXU takes them as the bf16 they were (one pass, float32
            # accumulation); XLA:CPU has no bf16 x bf16 -> f32 batched dot
            # inside a loop body
            s = jnp.einsum("bqgd,bld->bgql", qx.astype(jnp.float32),
                           ks.astype(jnp.float32)) * scale
            s = jnp.where(mx[:, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bgql,bld->bqgd", p, vs.astype(jnp.float32))

        return jax.lax.map(one_block, (qs, mb))         # [nq, B, qb, G, D]

    o = jax.lax.map(one_head, (qh, kh, vh))         # [KH, nq, B, qb, G, D]
    return o.transpose(2, 1, 3, 0, 4, 5).reshape(B, S, H, D)


def sparse_attention(q, k, v, qi, w, ki, topk: int):
    """The uncached forward of a layer with an indexer: every query of q
    ``[B, T, H, D]`` attends the top ``topk`` positions at or before its
    own by :func:`index_scores`.  k, v ``[B, T, KH, D]``, ki ``[B, T,
    ID]``.  A token before position ``topk`` attends all it may: plain
    causal attention."""
    B, T = q.shape[:2]
    qpos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    mask = topk_mask(index_scores(qi, w, ki, qpos), topk)
    return masked_attention(q, k, v, mask)


def _gather_positions(pool, tables):
    """``[N, KH, bs, D]`` through ``[B, M]`` tables -> ``[B, M*bs, KH,
    D]``: logical position l of row b is ``pool[tables[b, l // bs], :, l %
    bs]``."""
    B, M = tables.shape
    _, KH, bs, D = pool.shape
    cache = jnp.take(pool, tables, axis=0)              # [B, M, KH, bs, D]
    return jnp.moveaxis(cache, 2, 3).reshape(B, M * bs, KH, D)


def paged_sparse_attention(q, pool_k, pool_v, pool_i, tables, pos, qi, w,
                           topk: int):
    """Selection inside paged attention: S query tokens a row against the
    paged K/V and index-key arenas of one layer, one table.

    ``pool_k``/``pool_v`` are TOKEN-major here, ``[N, 1, bs, KH*D]``: one
    row a token with its KV heads side by side (``paged_kv_update`` writes
    it as a one-head pool), because the selected read gathers whole token
    rows, and a row that lies in one piece gathers four times as fast as
    KH strided pieces (0.5 ms against 2.0 ms for 16 x 2048 rows on a v5e;
    PERF.md).  ``pool_i`` is ``[N, 1, bs, ID]``.

    Row b's queries sit at positions ``pos[b] .. pos[b]+S-1``; each scores
    the row's cached index keys, read THROUGH THE TABLE, up to its own
    position (its own key is already written), keeps the exact top
    ``topk`` and attends those positions.  Returns ``(o [B, S, H, D]
    float32, n_read [B] int32)``: ``n_read`` counts the positions whose
    K/V the attention read for the row — ``min(context, topk)`` on the
    decode path (S = 1), which gathers the selected token rows and no
    others; the chunk path (S > 1) reads every position under the table
    and masks, and says so."""
    B, S, H, D = q.shape
    bs, W = pool_k.shape[2:]
    KH = W // D
    M = tables.shape[1]
    L = M * bs
    qpos = pos[:, None] + jnp.arange(S)[None, :]            # [B, S]
    ki = _gather_positions(pool_i, tables)[:, :, 0]         # [B, L, ID]
    scores = index_scores(qi, w, ki, qpos)                  # [B, S, L]
    if S > 1:
        mask = topk_mask(scores, topk)
        rows = lambda pool: _gather_positions(pool, tables).reshape(
            B, L, KH, D)
        o = masked_attention(q, rows(pool_k), rows(pool_v), mask)
        return o, jnp.minimum(pos + S, L).astype(jnp.int32)
    K = min(int(topk), L)
    vals, sel = jax.lax.top_k(scores[:, 0], K)              # [B, K]
    valid = vals > -jnp.inf
    phys = jnp.take_along_axis(tables, sel // bs, axis=1)
    off = sel % bs
    ks = pool_k[phys, 0, off].reshape(B, K, KH, D)
    vs = pool_v[phys, 0, off].reshape(B, K, KH, D)
    G = H // KH
    s = jnp.einsum("bhgd,bkhd->bhgk", q.reshape(B, KH, G, D), ks,
                   preferred_element_type=jnp.float32) \
        * (1.0 / float(np.sqrt(D)))
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(vs.dtype), vs,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D), jnp.sum(valid, axis=1).astype(jnp.int32)
