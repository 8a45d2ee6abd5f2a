"""Decoder-only causal language model + KV-cache generation.

The reference's generative surface is the RNN ``Seq2seq`` (SURVEY.md §2.5,
upstream ``pyzoo/zoo/models/seq2seq``) — it predates decoder-only LMs.
This module completes the family the TPU-native way:

- **Training** is one causal transformer forward: full attention on a
  single chip, the fused Pallas flash kernel where measured to win, and
  causal RING attention over the ``sp`` axis for long sequences (the same
  `parallel/ring_attention.py` machinery BERT uses, with the causal mask
  staying exact across ring hops).
- **Generation** is ONE ``lax.scan`` over positions with a preallocated
  KV cache threaded through the carry — static shapes, no Python loop, no
  per-token dispatch; prompt prefill and sampling are the same scan
  (prompt positions teacher-force, later positions feed back argmax).
- Weights are tied (logits = hidden @ embed.T) and carry the same
  Megatron tp layout as BERT, so ``LM_PARTITION_RULES`` compose with
  dp/sp/tp meshes unchanged.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.models.transformer import (
    _constrain_seq, attention_dispatch)
from analytics_zoo_tpu.parallel.pipeline import pp_stage_rules as _ppsr

LM_PARTITION_RULES = (
    (r"pos_embed/embedding", P()),      # positions replicate (before the
    (r"embed/embedding", P("tp", None)),   # vocab rule can re.search-match)
    # NOTE (GQA): key/value kernels have num_kv_heads on the sharded
    # head dim — keep num_kv_heads a multiple of the tp size (or
    # override these two rules to P()) when sharding narrow-KV models
    (r"(query|key|value)/kernel", P(None, "tp")),
    (r"attn_out/kernel", P("tp", None)),
    (r"ffn_up/kernel", P(None, "tp")),
    (r"ffn_gate/kernel", P(None, "tp")),   # SwiGLU gate: column-parallel
    (r"ffn_down/kernel", P("tp", None)),
    (r"lm_head/kernel", P(None, "tp")),    # untied head: vocab-sharded
    (r".*", P()),
)


# TransformerLM(pp_stages=N): GPipe-stacked stage params sharded over pp
# on the stage dim; embeddings/head follow the non-pp rules.  NOTE: no tp
# entries for the trunk — pipeline stages execute inside shard_map, where
# a tp-sharded weight would just be all-gathered every tick (memory at
# rest, zero compute parallelism); combine pp with dp/fsdp instead.
LM_PP_PARTITION_RULES = _ppsr() + LM_PARTITION_RULES

# TransformerLM(pp_stages=v*S, pp_schedule="interleaved") on a pp=S
# mesh: stage params are stored CHUNKED [v, S, ...] (round-robin
# placement — parallel/pipeline.py), so the pp shard moves to dim 1.
# n_chunks here is only a LAYOUT FLAG (any value > 1 selects the
# chunked specs) — these rules apply to every v, not just v=2.
LM_PP_INTERLEAVED_PARTITION_RULES = _ppsr(n_chunks=2) + LM_PARTITION_RULES


# MoE-LM (moe_experts > 0): expert weights over ep(+tp) + the LM rules.
# (moe.py imports no LM/transformer modules at top level — no cycle.)
from analytics_zoo_tpu.models.moe import MOE_PARTITION_RULES as _MOE_RULES

LM_MOE_PARTITION_RULES = _MOE_RULES + LM_PARTITION_RULES


def beam_search(model: TransformerLM, variables, prompt,
                max_new_tokens: int, beam_size: int = 4, *,
                prompt_len=None, eos_id=None,
                length_penalty: float = 0.0) -> tuple:
    """Beam-search decoding as lax.scans (compiler-friendly: the beam
    lives as an extra leading dim, KV caches reorder on-device with a
    batched gather instead of host-side bookkeeping).

    prompt: [B, P] int32.  ``prompt_len`` (optional [B] int32) gives each
    row's true length for right-padded ragged batches — same contract as
    ``generate()``.  Returns ``(tokens [B, beam, max_new], scores
    [B, beam])`` with beams sorted best-first.

    ``eos_id``: a beam that emits it (past its prompt) FREEZES — its
    score stops accumulating and its tail fills with eos (fixed shapes;
    the frozen hypothesis keeps competing in top-k on its final score,
    the standard finished-beam semantics).  Without EOS handling a beam
    would keep scoring past end-of-sequence and eos-trained models would
    rank garbage continuations.

    ``length_penalty`` (alpha): beams are ranked by
    ``score / ((5 + n_tokens) / 6) ** alpha`` (GNMT), where ``n_tokens``
    counts real tokens up to and including eos.  ``alpha=0`` (default)
    ranks by raw sum log-prob; returned ``scores`` are always the
    ranking scores.

    Uniform prompts run a width-1 PREFILL scan first (K-wide prefill
    would waste (K-1)/K of the prefill FLOPs); ragged batches run one
    K-wide scan with per-row teacher-forcing, like ``generate()``.
    """
    _arena_model(model, "beam_search()")
    B, Pn = prompt.shape
    K = int(beam_size)
    L = Pn + max_new_tokens
    if max_new_tokens <= 0:
        return (jnp.zeros((B, K, 0), jnp.int32),
                jnp.zeros((B, K), jnp.float32))
    if L > model.max_position:
        raise ValueError(f"prompt+new = {L} exceeds max_position "
                         f"{model.max_position}")
    V = model.vocab_size
    H = model.kv_heads                  # GQA: cache stores KV heads only
    D = model.head_size
    cdtype = jnp.dtype(model.dtype)
    ragged = prompt_len is not None
    plen = (jnp.full((B,), Pn, jnp.int32) if not ragged
            else jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1, Pn))

    def step(carry, t):
        """One K-wide position step: decode, expand/teacher-force, reorder.

        Rows still inside their prompt (t+1 < plen) teacher-force it on
        all K identical beams; a row's FIRST expansion (t+1 == plen)
        draws candidates from beam 0 only (the clones would produce K
        duplicate hypotheses); after that it's standard K*V expansion.
        """
        tok, ck, cv, scores, toks, done, nlen = carry
        logits, ck, cv = model.apply(
            variables, tok, ck, cv, t, method=TransformerLM.decode_step)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                  axis=-1).reshape(B, K, V)
        if eos_id is not None:
            # frozen beams: the only continuation is eos at logp 0, so
            # the finished score competes unchanged in top-k
            frozen = jnp.full((V,), -jnp.inf,
                              jnp.float32).at[eos_id].set(0.0)
            logp = jnp.where(done[:, :, None], frozen[None, None, :], logp)
        cand = scores[:, :, None] + logp                 # [B, K, V]
        first = (t + 1 == plen)                          # [B]
        cand = jnp.where(
            (first[:, None] & (jnp.arange(K) > 0)[None, :])[:, :, None],
            -jnp.inf, cand)
        top_s, top_i = lax.top_k(cand.reshape(B, K * V), K)
        src_beam = top_i // V
        nxt = (top_i % V).astype(jnp.int32)
        # a row is INACTIVE while still teacher-forcing its prompt
        # (w < 0) and again once its own max_new window is complete
        # (w >= max_new: ragged batches keep scanning for longer-prompt
        # rows — a completed row must freeze its scores and beam order,
        # not keep re-ranking on tokens outside its window)
        w = t + 1 - plen                # [B] generated-token index
        teach = w < 0
        inactive = teach | (w >= max_new_tokens)
        active = ~inactive
        # reorder beam state to follow the winning hypotheses; inactive
        # rows gather identity (no reorder)
        src_eff = jnp.where(inactive[:, None], jnp.arange(K)[None, :],
                            src_beam)
        new_toks = jnp.take_along_axis(toks, src_eff[:, :, None], axis=1)
        new_done = jnp.take_along_axis(done, src_eff, axis=1)
        new_len = jnp.take_along_axis(nlen, src_eff, axis=1)
        gidx = (jnp.arange(B)[:, None] * K + src_eff).reshape(-1)
        ck, cv = ck[:, gidx], cv[:, gidx]
        p_tok = prompt[:, jnp.minimum(t + 1, Pn - 1)]    # [B]
        nxt = jnp.where(teach[:, None], p_tok[:, None], nxt)
        top_s = jnp.where(inactive[:, None], scores, top_s)
        new_len = jnp.where(active[:, None] & ~new_done, new_len + 1,
                            new_len)
        if eos_id is not None:
            new_done = new_done | (active[:, None] & (nxt == eos_id))
        new_toks = lax.dynamic_update_index_in_dim(
            new_toks.transpose(2, 0, 1), nxt, t, 0).transpose(1, 2, 0)
        return (nxt.reshape(B * K), ck, cv, top_s, new_toks, new_done,
                new_len), None

    def tile(c):        # [layers, B, L, H, D] -> [layers, B*K, L, H, D]
        return jnp.repeat(c, K, axis=1)

    if not ragged and Pn > 1:
        # ---- width-1 prefill over the shared prompt ------------------
        ck1 = jnp.zeros((model.num_layers, B, L, H, D), cdtype)
        cv1 = jnp.zeros_like(ck1)

        def prefill(carry, t):
            ck, cv = carry
            _, ck, cv = model.apply(
                variables, prompt[:, t], ck, cv, t,
                method=TransformerLM.decode_step)
            return (ck, cv), None

        (ck1, cv1), _ = lax.scan(prefill, (ck1, cv1), jnp.arange(Pn - 1))
        if max_new_tokens == 1:
            # single-token beams need one more decode step but never the
            # K-wide cache tile or the generation scan; with every
            # hypothesis the same length the penalty only rescales
            logits, _, _ = model.apply(
                variables, prompt[:, Pn - 1], ck1, cv1, Pn - 1,
                method=TransformerLM.decode_step)
            logp0 = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            scores0, tok0_k = lax.top_k(logp0, K)
            # GNMT lp(1) == 1, so the penalty cannot reorder or rescale
            return tok0_k[:, :, None], scores0
        ck0, cv0 = tile(ck1), tile(cv1)
        t0 = Pn - 1
        tok0 = jnp.repeat(prompt[:, Pn - 1], K)
    else:
        ck0 = jnp.zeros((model.num_layers, B * K, L, H, D), cdtype)
        cv0 = jnp.zeros_like(ck0)
        t0 = 0
        tok0 = jnp.repeat(prompt[:, 0], K)

    # toks buffer covers every position the scan writes; the per-row
    # generated window [plen-1, plen-1+max_new) is gathered at the end
    carry = (tok0, ck0, cv0, jnp.zeros((B, K), jnp.float32),
             jnp.zeros((B, K, L - 1), jnp.int32),
             jnp.zeros((B, K), bool), jnp.zeros((B, K), jnp.int32))
    (_, _, _, scores, toks, done, nlen), _ = lax.scan(
        step, carry, t0 + jnp.arange(L - 1 - t0))
    widx = jnp.clip(plen[:, None, None] - 1
                    + jnp.arange(max_new_tokens)[None, None, :], 0, L - 2)
    toks = jnp.take_along_axis(toks, jnp.broadcast_to(
        widx, (B, K, max_new_tokens)), axis=2)
    if length_penalty:
        lp = ((5.0 + nlen.astype(jnp.float32)) / 6.0) ** float(
            length_penalty)
        scores = scores / lp
        order = jnp.argsort(-scores, axis=1)
        toks = jnp.take_along_axis(toks, order[:, :, None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
    # without a penalty, lax.top_k already left beams sorted best-first
    return toks, scores


def unstack_pp_params(params, n_chunks: int = 1):
    """pp-trained param tree (``trunk/stages/...`` with a leading stage
    dim) -> the flat ``layer_{i}`` tree a ``pp_stages=0`` TransformerLM
    expects.  The bridge from pipeline training to cached-decode serving:
    train with pp, ``unstack_pp_params``, generate on a non-pp model of
    the same dimensions.

    ``pp_schedule="interleaved"`` models store stages CHUNKED
    [v, S, ...] (logical stage k*S + r at leaf[k, r] — round-robin
    placement, parallel/pipeline.py); pass the model's ``n_chunks``
    (= pp_stages / mesh pp size) so the logical order is reassembled."""
    out = {k: v for k, v in params.items() if k != "trunk"}
    stacked = params["trunk"]["stages"]
    stage_layers = sorted(
        (k for k in stacked if k.startswith("layer_")),
        key=lambda k: int(k.split("_")[1]))
    k_per = len(stage_layers)
    lead = jax.tree.leaves(stacked)[0].shape
    if n_chunks > 1:
        v, S = int(n_chunks), lead[1]
        if lead[0] != v:
            raise ValueError(
                f"n_chunks={n_chunks} does not match the chunked stage "
                f"leaves' leading dims {lead[:2]}; pass the value the "
                f"model was built with (pp_stages / mesh pp size)")
        for k in range(v):
            for r in range(S):
                for j, name in enumerate(stage_layers):
                    out[f"layer_{(k * S + r) * k_per + j}"] = \
                        jax.tree.map(lambda a: a[k, r], stacked[name])
        return out
    S = lead[0]
    for s in range(S):
        for j, name in enumerate(stage_layers):
            out[f"layer_{s * k_per + j}"] = jax.tree.map(
                lambda a: a[s], stacked[name])
    return out


def _make_norm(kind: str, eps: float, name: str):
    """One norm selector for block norms and the final norm — the two
    must never drift (a mismatch would silently skew logits)."""
    if kind == "rmsnorm":
        return nn.RMSNorm(dtype=jnp.float32, name=name, epsilon=eps)
    return nn.LayerNorm(dtype=jnp.float32, name=name, epsilon=eps)


def _apply_rope(x, pos, base: float):
    """Rotary position embedding (rotate-half convention).

    x: [..., T, H, D] (D even); pos: positions broadcastable against the
    T axis — ``arange(T)`` for the training forward, a scalar-as-[1] or
    per-row [B] vector for cached decode.  K is stored POST-rotation in
    the KV cache (absolute rotation per position; the relative-offset
    property emerges in the q.k dot product), so decode and forward see
    identical keys."""
    D = x.shape[-1]
    if D % 2:
        raise ValueError(
            f"rotary positions need an even head dim, got {D} "
            f"(hidden_size must be divisible by 2*num_heads)")
    half = D // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = pos[..., None].astype(jnp.float32) * freqs    # [..., T, half]
    cos = jnp.cos(ang)[..., None, :]                    # [..., T, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _flat_pools(pools):
    """View stacked KV pools ``[layers, N, KH, bs, D]`` (any pytree of
    them: arrays, or ``ops.flash_attention.QuantKV`` data + scales) as
    ONE block arena ``[layers*N, KH, bs, D]`` each; returns (flat, N).
    Only leading dimensions merge, so this is a bitcast of the donated
    buffer, under a mesh too (the pool shards on KH).  Layer ``i`` reads and
    writes through ``tables + i*N``: its blocks are ``i*N .. i*N+N-1``,
    its sink ``i*N``.  Nothing in a step program may slice a layer out
    or stack layers back: either makes the compiler copy the pool."""
    N = jax.tree_util.tree_leaves(pools)[0].shape[1]
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), pools), N


def _stacked_pools(flat, n_layers):
    """Inverse view of :func:`_flat_pools`: ``[layers, N, ...]``."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n_layers, -1) + a.shape[1:]), flat)


class DecoderAttention(nn.Module):
    """Causal self-attention with a training path and a cached decode path
    sharing the same projections (setup-style module).

    ``num_kv_heads < num_heads`` is grouped-query attention (MQA at 1):
    K/V project to fewer heads, shared by groups of query heads.  The
    TRAINING forward broadcasts K/V up to full width (same FLOPs as MHA
    — flash/ring paths work unchanged); the win is the DECODE cache,
    which stores only ``num_kv_heads`` heads: H/KV_H times smaller KV
    per token, which multiplies continuous-serving arena capacity and
    long-generation memory headroom the same way."""

    hidden_size: int
    num_heads: int
    num_kv_heads: Optional[int] = None
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None
    use_flash: Optional[bool] = None
    sp_strategy: str = "ring"
    # "learned": the LM adds position embeddings before the trunk;
    # "rope": q/k rotate here (applied pre-dispatch on global positions,
    # so flash/ring/GQA paths run unchanged)
    pos_encoding: str = "learned"
    rope_base: float = 10000.0
    use_bias: bool = True       # llama-family imports project bias-free
    # qwen2-style split: biased q/k/v with a bias-free o_proj/mlp
    # (None follows use_bias)
    qkv_bias: Optional[bool] = None
    # a head width of its own (None: hidden_size // num_heads), so that
    # num_heads * head_dim need not be the hidden size
    head_dim: Optional[int] = None
    # RMSNorm over the head width of every q and k head, learned scale,
    # before the rotary (the Qwen3 convention)
    qk_norm: bool = False
    ln_eps: float = 1e-6
    # learned sparse attention (indexer_topk > 0): an indexer of
    # indexer_heads query heads of indexer_head_dim and ONE index key a
    # token scores every earlier position, and a query attends its
    # indexer_topk best (ops.sparse_attention.index_scores).  The index
    # key is cached beside K and V (``decode_paged_sparse``)
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    indexer_topk: int = 0

    def setup(self):
        H = self.num_heads
        KH = self.num_kv_heads or H
        if H % KH:
            raise ValueError(
                f"num_heads {H} must be a multiple of num_kv_heads {KH}")
        D = self.head_dim or self.hidden_size // H
        self._h, self._kh, self._d = H, KH, D
        if self.qk_norm:
            self.q_norm = nn.RMSNorm(dtype=jnp.float32, name="q_norm",
                                     epsilon=self.ln_eps)
            self.k_norm = nn.RMSNorm(dtype=jnp.float32, name="k_norm",
                                     epsilon=self.ln_eps)
        if self.indexer_topk:
            IH, ID = self.indexer_heads, self.indexer_head_dim
            self.idx_query = nn.DenseGeneral((IH, ID), dtype=self.dtype,
                                             use_bias=False,
                                             name="idx_query")
            self.idx_key = nn.Dense(ID, dtype=self.dtype, use_bias=False,
                                    name="idx_key")
            self.idx_key_norm = nn.LayerNorm(dtype=jnp.float32,
                                             epsilon=self.ln_eps,
                                             name="idx_key_norm")
            self.idx_weight = nn.Dense(IH, dtype=self.dtype,
                                       use_bias=False, name="idx_weight")
        qkvb = self.use_bias if self.qkv_bias is None else self.qkv_bias
        self.query = nn.DenseGeneral((H, D), dtype=self.dtype,
                                     use_bias=qkvb,
                                     name="query")
        self.key = nn.DenseGeneral((KH, D), dtype=self.dtype,
                                   use_bias=qkvb, name="key")
        self.value = nn.DenseGeneral((KH, D), dtype=self.dtype,
                                     use_bias=qkvb,
                                     name="value")
        self.attn_out = nn.DenseGeneral(self.hidden_size, axis=(-2, -1),
                                        dtype=self.dtype,
                                        use_bias=self.use_bias,
                                        name="attn_out")

    def _expand_kv(self, t):
        """[B, T, KH, D] -> [B, T, H, D] by repeating each KV head over
        its query group (training path: keeps flash/ring unchanged)."""
        if self._kh == self._h:
            return t
        return jnp.repeat(t, self._h // self._kh, axis=2)

    def __call__(self, x, train: bool = False, return_kv: bool = False):
        """Training/scoring: [B, T, E] -> [B, T, E], causal.
        ``return_kv=True`` also returns this layer's K/V projections
        ``[B, T, KV_H, D]`` (KV-arena prefill for continuous batching)."""
        q, k, v = self.query(x), self.key(x), self.value(x)
        if self.qk_norm:
            q, k = self._normed_qk(q, k)
        if self.pos_encoding == "rope":
            t_pos = jnp.arange(x.shape[1])
            q = _apply_rope(q, t_pos, self.rope_base)
            k = _apply_rope(k, t_pos, self.rope_base)
        if self.indexer_topk:
            from analytics_zoo_tpu.ops.sparse_attention import \
                sparse_attention

            qi, ki, w = self._index_qkw(x, jnp.arange(x.shape[1]))
            o = sparse_attention(q, k, v, qi, w, ki,
                                 self.indexer_topk).astype(self.dtype)
        else:
            o = attention_dispatch(q, self._expand_kv(k),
                                   self._expand_kv(v),
                                   None, causal=True, mesh=self.mesh,
                                   use_flash=self.use_flash,
                                   sp_strategy=self.sp_strategy)
        out = self.attn_out(o)
        return (out, k, v) if return_kv else out

    def decode(self, x1, cache_k, cache_v, pos):
        """One cached decode step.

        x1: [B, 1, E] current-position hidden; cache_k/v: [B, L, KV_H,
        D] preallocated; pos: int32 current position — a SCALAR advances
        the whole batch in lockstep (generate/beam_search); a VECTOR [B]
        gives each row its own position (the continuous-batching engine,
        where co-resident requests are at different depths).  Returns
        (y1 [B, 1, E], new_cache_k, new_cache_v).
        """
        B = x1.shape[0]
        L = cache_k.shape[1]
        KH = self._kh
        G = self._h // KH                   # query heads per KV head
        q = self.query(x1)                              # [B, 1, H, D]
        k1 = self.key(x1)                               # [B, 1, KH, D]
        v1 = self.value(x1)
        if self.pos_encoding == "rope":
            # rotate at the CURRENT position; the cache already holds
            # post-rotation keys for earlier positions
            p = (jnp.reshape(pos, (1,)) if jnp.ndim(pos) == 0
                 else pos[:, None])
            q = _apply_rope(q, p, self.rope_base)
            k1 = _apply_rope(k1, p, self.rope_base)
        if jnp.ndim(pos) == 0:
            cache_k = lax.dynamic_update_slice(
                cache_k, k1.astype(cache_k.dtype), (0, pos, 0, 0))
            cache_v = lax.dynamic_update_slice(
                cache_v, v1.astype(cache_v.dtype), (0, pos, 0, 0))
            mask = (jnp.arange(L) <= pos)[None, None, None, None, :]
        else:
            # per-row scatter: row b writes its K/V at pos[b] and attends
            # positions <= pos[b] (O(B*L*KH*D) masked write — the same
            # bandwidth the attention read below already pays)
            hit = (jnp.arange(L)[None, :] == pos[:, None])[:, :, None, None]
            cache_k = jnp.where(hit, k1.astype(cache_k.dtype), cache_k)
            cache_v = jnp.where(hit, v1.astype(cache_v.dtype), cache_v)
            mask = (jnp.arange(L)[None, :]
                    <= pos[:, None])[:, None, None, None, :]
        scale = 1.0 / jnp.sqrt(self._d).astype(jnp.float32)
        # grouped attention: q regroups [B, 1, KH, G, D] so each KV head
        # serves its G query heads without materialising expanded KV
        qg = q.reshape(B, 1, KH, G, self._d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, cache_k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask, logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(cache_v.dtype),
                       cache_v, preferred_element_type=jnp.float32)
        o = o.reshape(B, 1, self._h, self._d)
        return self.attn_out(o.astype(self.dtype)), cache_k, cache_v

    def decode_k(self, xs, cache_k, cache_v, pos):
        """Cached decode of S tokens AT ONCE — the verify pass of
        speculative decoding (models/speculative.py): the S draft tokens
        run one MXU-friendly forward instead of S sequential steps.

        xs: [B, S, E] hiddens of the S new tokens; pos: [B] int32, row
        b's tokens occupy cache positions pos[b]..pos[b]+S-1.  Token j
        attends cache entries < its own position plus itself (block-
        causal against the cache, exactly the mask sequential decode
        would have produced).  Returns (ys [B, S, E], cache_k, cache_v)
        with all S K/V written; the CALLER decides how much of the write
        becomes durable by how far it advances pos (rejected tokens'
        entries are never attended once pos stops short of them, and the
        next round overwrites them).
        """
        B, S = xs.shape[0], xs.shape[1]
        L = cache_k.shape[1]
        KH = self._kh
        G = self._h // KH
        q = self.query(xs)                              # [B, S, H, D]
        ks = self.key(xs)                               # [B, S, KH, D]
        vs = self.value(xs)
        p = pos[:, None] + jnp.arange(S)[None, :]       # [B, S]
        if self.pos_encoding == "rope":
            q = _apply_rope(q, p, self.rope_base)
            ks = _apply_rope(ks, p, self.rope_base)
        # scatter the S new K/V rows to their per-row positions: one-hot
        # matmul [B,S,L] — O(B·S·L·KH·D), the bandwidth the attention
        # read below pays anyway (S is the small speculation depth)
        hit = (jnp.arange(L)[None, None, :] == p[:, :, None])  # [B,S,L]
        scat = hit.astype(cache_k.dtype)
        wrote = hit.any(axis=1)[:, :, None, None]              # [B,L,1,1]
        new_k = jnp.einsum("bsl,bshd->blhd", scat,
                           ks.astype(cache_k.dtype))
        new_v = jnp.einsum("bsl,bshd->blhd", scat,
                           vs.astype(cache_v.dtype))
        cache_k = jnp.where(wrote, new_k, cache_k)
        cache_v = jnp.where(wrote, new_v, cache_v)
        # token j sees cache position l iff l <= pos[b]+j
        mask = (jnp.arange(L)[None, None, :]
                <= p[:, :, None])[:, None, None, :, :]  # [B,1,1,S,L]
        scale = 1.0 / jnp.sqrt(self._d).astype(jnp.float32)
        qg = q.reshape(B, S, KH, G, self._d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, cache_k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask, logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(cache_v.dtype),
                       cache_v, preferred_element_type=jnp.float32)
        o = o.reshape(B, S, self._h, self._d)
        return self.attn_out(o.astype(self.dtype)), cache_k, cache_v

    def decode_paged(self, xs, pool_k, pool_v, tables, pos, limit=None,
                     kernel="gather", mesh=None, kv_sharded=True):
        """Cached decode of S tokens per row against a PAGED KV cache.

        Same contract as :meth:`decode_k` except the cache is one flat
        head-major block pool shared by every resident: pool_k/pool_v
        ``[N, KH, bs, D]`` (or QuantKV int8 pools of that geometry),
        tables ``[B, M]`` int32 mapping row b's logical block j to a
        physical pool block (the serving BlockPool keeps unallocated
        table entries pointed at the sink block 0).  xs: [B, S, E];
        pos: [B] int32, row b's tokens occupy logical positions
        pos[b]..pos[b]+S-1.  S=1 is the plain decode step; S>1 is the
        block-causal prefill/verify forward.  Returns (ys [B, S, E],
        pool_k, pool_v) with the S new K/V rows scattered through the
        tables (write precedes the attention read, so each token
        attends itself).  ``limit`` ([B] int32, optional) drops writes
        at positions >= limit[b] — chunked prefill's padding guard (see
        ops.flash_attention.paged_kv_update).  ``kernel`` selects the
        attention read path (``"gather"`` fallback or the ``"fused"``
        Pallas kernel — ops.flash_attention.paged_attention).  ``mesh``
        + ``kv_sharded`` (fused only) run the kernel per-chip under
        shard_map against a tp-sharded (or, hatch, replicated) pool —
        passed explicitly by the serving engine rather than read from
        ``self.mesh`` because the engine owns the pool placement.
        """
        from analytics_zoo_tpu.ops.flash_attention import (
            paged_attention, paged_kv_update)

        q = self.query(xs)                              # [B, S, H, D]
        ks = self.key(xs)                               # [B, S, KH, D]
        vs = self.value(xs)
        if self.pos_encoding == "rope":
            p = pos[:, None] + jnp.arange(xs.shape[1])[None, :]
            q = _apply_rope(q, p, self.rope_base)
            ks = _apply_rope(ks, p, self.rope_base)
        pool_k, pool_v = paged_kv_update(pool_k, pool_v, tables, pos,
                                         ks, vs, limit=limit)
        o = paged_attention(q, pool_k, pool_v, tables, pos,
                            kernel=kernel, mesh=mesh,
                            kv_sharded=kv_sharded)
        return self.attn_out(o.astype(self.dtype)), pool_k, pool_v

    def _normed_qk(self, q, k):
        return (self.q_norm(q).astype(self.dtype),
                self.k_norm(k).astype(self.dtype))

    def _index_qkw(self, x, pos):
        """The indexer's side of S tokens: queries ``[B, S, IH, ID]`` and
        the one index key a token ``[B, S, ID]`` (LayerNorm, then the
        rotary over the whole width, like q and k), and the head weights
        ``[B, S, IH]`` in float32."""
        qi = self.idx_query(x)
        ki = self.idx_key_norm(self.idx_key(x)).astype(self.dtype)
        if self.pos_encoding == "rope":
            qi = _apply_rope(qi, pos, self.rope_base)
            ki = _apply_rope(ki[:, :, None, :], pos,
                             self.rope_base)[:, :, 0]
        return qi, ki, self.idx_weight(x).astype(jnp.float32)

    def decode_paged_sparse(self, xs, pool_k, pool_v, pool_i, tables,
                            pos, limit=None):
        """:meth:`decode_paged` for a layer with an indexer: the same
        contract, with token-major K/V pools ``[N, 1, bs, KH*D]`` and the
        index-key arena ``pool_i`` ``[N, 1, bs, ID]``
        written and read through the same tables, and the attention over
        each query's selected positions only
        (ops.sparse_attention.paged_sparse_attention).  Returns (ys,
        pool_k, pool_v, pool_i, n_read [B] int32)."""
        from analytics_zoo_tpu.ops.flash_attention import paged_kv_update
        from analytics_zoo_tpu.ops.sparse_attention import (
            paged_index_update, paged_sparse_attention)

        q = self.query(xs)                              # [B, S, H, D]
        ks = self.key(xs)                               # [B, S, KH, D]
        vs = self.value(xs)
        if self.qk_norm:
            q, ks = self._normed_qk(q, ks)
        p = pos[:, None] + jnp.arange(xs.shape[1])[None, :]
        if self.pos_encoding == "rope":
            q = _apply_rope(q, p, self.rope_base)
            ks = _apply_rope(ks, p, self.rope_base)
        qi, ki, w = self._index_qkw(xs, p)
        # token-major pools [N, 1, bs, KH*D]: a token's KV heads lie side
        # by side, written as ONE head (paged_sparse_attention says why)
        row = lambda t: t.reshape(t.shape[:2] + (1, -1))
        pool_k, pool_v = paged_kv_update(pool_k, pool_v, tables, pos,
                                         row(ks), row(vs), limit=limit)
        pool_i = paged_index_update(pool_i, tables, pos, ki, limit=limit)
        o, n_read = paged_sparse_attention(
            q, pool_k, pool_v, pool_i, tables, pos, qi, w,
            self.indexer_topk)
        return (self.attn_out(o.astype(self.dtype)), pool_k, pool_v,
                pool_i, n_read)


class DecoderLayer(nn.Module):
    """Pre-LN causal decoder block (pre-LN trains stably at depth without
    the reference's warmup tricks; BERT keeps post-LN for ref parity)."""

    hidden_size: int
    num_heads: int
    intermediate_size: int
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None
    use_flash: Optional[bool] = None
    sp_strategy: str = "ring"
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    num_kv_heads: Optional[int] = None
    pos_encoding: str = "learned"
    rope_base: float = 10000.0
    # LayerNorm epsilon: flax's 1e-6 by default; importers of foreign
    # checkpoints (net/hf_net.py — GPT-2 uses 1e-5) must match it or
    # logits drift
    ln_eps: float = 1e-6
    # "layernorm" | "rmsnorm"; "gelu" | "swiglu" — the llama family is
    # rmsnorm + swiglu + bias-free projections (net/hf_net.py)
    norm: str = "layernorm"
    mlp: str = "gelu"
    use_bias: bool = True
    qkv_bias: Optional[bool] = None
    # see TransformerLM
    head_dim: Optional[int] = None
    qk_norm: bool = False
    experts: int = 0
    experts_per_token: int = 8
    expert_width: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    indexer_topk: int = 0

    def setup(self):
        self.ln_attn = _make_norm(self.norm, self.ln_eps, "ln_attn")
        self.attention = DecoderAttention(
            self.hidden_size, self.num_heads,
            num_kv_heads=self.num_kv_heads, dtype=self.dtype,
            mesh=self.mesh, use_flash=self.use_flash,
            sp_strategy=self.sp_strategy,
            pos_encoding=self.pos_encoding, rope_base=self.rope_base,
            use_bias=self.use_bias, qkv_bias=self.qkv_bias,
            head_dim=self.head_dim, qk_norm=self.qk_norm,
            ln_eps=self.ln_eps, indexer_heads=self.indexer_heads,
            indexer_head_dim=self.indexer_head_dim,
            indexer_topk=self.indexer_topk,
            name="attention")
        self.ln_ffn = _make_norm(self.norm, self.ln_eps,
                                 "ln_ffn")
        if self.experts > 0:
            from analytics_zoo_tpu.models.moe import DroplessMoE

            self.moe = DroplessMoE(self.experts, self.expert_width,
                                   top_k=self.experts_per_token,
                                   dtype=self.dtype, name="moe")
        elif self.num_experts > 0:
            from analytics_zoo_tpu.models.moe import MoEMLP

            self.moe = MoEMLP(self.num_experts, self.intermediate_size,
                              top_k=self.moe_top_k,
                              capacity_factor=self.moe_capacity_factor,
                              dtype=self.dtype, mesh=self.mesh,
                              name="moe")
        else:
            self.ffn_up = nn.Dense(self.intermediate_size,
                                   dtype=self.dtype,
                                   use_bias=self.use_bias, name="ffn_up")
            self.ffn_down = nn.Dense(self.hidden_size, dtype=self.dtype,
                                     use_bias=self.use_bias,
                                     name="ffn_down")
            if self.mlp == "swiglu":
                self.ffn_gate = nn.Dense(self.intermediate_size,
                                         dtype=self.dtype,
                                         use_bias=self.use_bias,
                                         name="ffn_gate")
        self.drop = nn.Dropout(self.dropout)

    def _mlp(self, x, train):
        if self.experts > 0:
            # dropless: no capacity, so no coupling to the batch (moe.py)
            h = self.moe(x)[0]
        elif self.num_experts > 0:
            # Per-token routing runs for both the [B, T, E] training
            # forward and the [B, 1, E] cached decode step.  NOTE: the
            # capacity pool differs (B*T tokens jointly vs B per decode
            # step), so under skewed routing decode logits can deviate
            # slightly from the teacher-forced forward — the same
            # batch-coupling property documented on MoEMLP; raise
            # moe_capacity_factor where that matters.
            h = self.moe(x, train)
        elif self.mlp == "swiglu":
            h = self.ffn_down(nn.silu(self.ffn_gate(x))
                              * self.ffn_up(x))
        else:
            h = self.ffn_down(nn.gelu(self.ffn_up(x)))
        return self.drop(h, deterministic=not train)

    def __call__(self, x, train: bool = False):
        a = self.attention(self.ln_attn(x).astype(self.dtype), train)
        x = x + self.drop(a, deterministic=not train)
        x = _constrain_seq(x, self.mesh)
        x = x + self._mlp(self.ln_ffn(x).astype(self.dtype), train)
        return _constrain_seq(x, self.mesh)

    def decode(self, x1, cache_k, cache_v, pos):
        a, ck, cv = self.attention.decode(
            self.ln_attn(x1).astype(self.dtype), cache_k, cache_v, pos)
        x1 = x1 + a
        x1 = x1 + self._mlp(self.ln_ffn(x1).astype(self.dtype), False)
        return x1, ck, cv

    def decode_k(self, xs, cache_k, cache_v, pos):
        a, ck, cv = self.attention.decode_k(
            self.ln_attn(xs).astype(self.dtype), cache_k, cache_v, pos)
        xs = xs + a
        xs = xs + self._mlp(self.ln_ffn(xs).astype(self.dtype), False)
        return xs, ck, cv

    def decode_paged(self, xs, pool_k, pool_v, tables, pos, limit=None,
                     kernel="gather", mesh=None, kv_sharded=True):
        a, pk, pv = self.attention.decode_paged(
            self.ln_attn(xs).astype(self.dtype), pool_k, pool_v,
            tables, pos, limit=limit, kernel=kernel, mesh=mesh,
            kv_sharded=kv_sharded)
        xs = xs + a
        xs = xs + self._mlp(self.ln_ffn(xs).astype(self.dtype), False)
        return xs, pk, pv

    def decode_paged_sparse(self, xs, pool_k, pool_v, pool_i, tables, pos,
                            limit=None, count=None):
        """:meth:`decode_paged` of a layer with an indexer
        (``DecoderAttention.decode_paged_sparse``).  Also returns what the
        tick's counters read: ``n_read [B]``, the positions whose K/V the
        attention read, and ``load [X]``, the assignments every expert got
        from the tokens where ``count`` ``[B, S]`` is true (a single zero
        for a layer without experts)."""
        a, pk, pv, pi, n_read = self.attention.decode_paged_sparse(
            self.ln_attn(xs).astype(self.dtype), pool_k, pool_v, pool_i,
            tables, pos, limit=limit)
        xs = xs + a
        h = self.ln_ffn(xs).astype(self.dtype)
        if self.experts > 0:
            y, load = self.moe(h, count)
        else:
            y, load = self._mlp(h, False), jnp.zeros((1,), jnp.int32)
        return xs + y, pk, pv, pi, n_read, load

    def forward_kv(self, x, train: bool = False):
        """``__call__`` that also returns this layer's K/V ``[B, T, H,
        D]`` — the prompt-prefill payload the continuous-batching engine
        writes into its KV arena.  Same math as ``__call__`` (constraints
        included), so prefilled logits equal the training forward's."""
        a, k, v = self.attention(self.ln_attn(x).astype(self.dtype),
                                 train, return_kv=True)
        x = x + self.drop(a, deterministic=not train)
        x = _constrain_seq(x, self.mesh)
        x = x + self._mlp(self.ln_ffn(x).astype(self.dtype), train)
        return _constrain_seq(x, self.mesh), k, v


class _LMStage(nn.Module):
    """One pipeline stage: a block of consecutive decoder layers with a
    plain ``x -> x`` signature (the GPipe stage contract)."""

    layers_per_stage: int
    hidden_size: int
    num_heads: int
    intermediate_size: int
    dtype: jnp.dtype = jnp.bfloat16
    use_flash: Optional[bool] = None
    num_kv_heads: Optional[int] = None
    pos_encoding: str = "learned"
    rope_base: float = 10000.0
    ln_eps: float = 1e-6
    norm: str = "layernorm"
    mlp: str = "gelu"
    use_bias: bool = True
    qkv_bias: Optional[bool] = None

    @nn.compact
    def __call__(self, x):
        for i in range(self.layers_per_stage):
            # stages run inside shard_map: no mesh constraints (manual
            # SPMD there), no dropout (no rng plumbing through the ticks)
            x = DecoderLayer(self.hidden_size, self.num_heads,
                             self.intermediate_size, dropout=0.0,
                             dtype=self.dtype, mesh=None,
                             use_flash=self.use_flash,
                             num_kv_heads=self.num_kv_heads,
                             pos_encoding=self.pos_encoding,
                             rope_base=self.rope_base,
                             ln_eps=self.ln_eps,
                             norm=self.norm, mlp=self.mlp,
                             use_bias=self.use_bias,
                             qkv_bias=self.qkv_bias,
                             name=f"layer_{i}")(x, False)
        return x


class TransformerLM(nn.Module):
    """Decoder-only LM with tied embeddings.

    ``__call__(tokens)`` -> next-token logits ``[B, T, V]`` (causal);
    ``decode_step`` runs one cached generation step (used by
    ``generate``).

    ``pp_stages > 0`` pipelines the trunk over the mesh's ``pp`` axis
    (SPMD GPipe, parallel/pipeline.py): ``num_layers`` must divide into
    ``pp_stages`` equal blocks, dropout must be 0, and generation is a
    training-cluster non-goal there (``decode_step`` raises — serve a
    non-pp restore of the same weights instead).
    """

    vocab_size: int
    hidden_size: int = 256
    num_layers: int = 4
    num_heads: int = 4
    intermediate_size: int = 1024
    max_position: int = 512
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None
    use_flash: Optional[bool] = None
    remat: bool = False
    pp_stages: int = 0
    pp_microbatches: int = 4
    # "gpipe" | "1f1b" | "interleaved": training schedule for the
    # pipelined trunk (parallel/pipeline.py — 1f1b bounds activation
    # residency at O(S); interleaved additionally needs pp_stages to be
    # a multiple v*S of the mesh's pp size and cuts the bubble v-fold,
    # with LM_PP_INTERLEAVED_PARTITION_RULES for the chunked layout)
    pp_schedule: str = "gpipe"
    sp_strategy: str = "ring"
    # MoE-LM: every moe_every-th layer gets an expert-parallel MoE FFN.
    # Cached decode routes per step (B tokens) while the forward routes
    # B*T jointly, so capacity-dropped tokens can differ between the two
    # under skew — see DecoderLayer._mlp / MoEMLP docstrings.
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    # decode routes only B tokens/step: raise this where batch-coupled
    # capacity drops matter (MoEMLP docstring)
    moe_capacity_factor: float = 1.25
    # Grouped-query attention: K/V project to this many heads (must
    # divide num_heads; None = MHA, 1 = MQA).  Training FLOPs are
    # unchanged (K/V broadcast up); the DECODE KV cache shrinks
    # num_heads/num_kv_heads-fold — allocate caches with `.kv_heads`.
    num_kv_heads: Optional[int] = None
    # "learned" (ref-style absolute table) | "rope" (rotary q/k — no
    # position table; max_position still bounds sequence/cache length)
    pos_encoding: str = "learned"
    rope_base: float = 10000.0
    # LayerNorm epsilon — foreign-checkpoint importers must match the
    # source model's (GPT-2: 1e-5; net/hf_net.py sets this)
    ln_eps: float = 1e-6
    # llama-family knobs (net/hf_net.py from_hf_llama): rmsnorm blocks,
    # SwiGLU MLP, bias-free projections, untied lm_head.  Defaults are
    # the GPT-2-shaped configuration every existing user of this class
    # already has.
    norm: str = "layernorm"         # "layernorm" | "rmsnorm"
    mlp: str = "gelu"               # "gelu" | "swiglu"
    use_bias: bool = True
    # qwen2-style: biased q/k/v despite bias-free o_proj/mlp
    qkv_bias: Optional[bool] = None
    tied_head: bool = True
    # ---- sparse-expert, sparse-attention decoders ----------------------
    # all default to the behaviour above.  ``head_dim``: a head width of
    # its own (None: hidden_size // num_heads).  ``qk_norm``: RMSNorm over
    # the head width of every q and k head.  ``experts`` > 0: every layer's
    # MLP is ``models.moe.DroplessMoE`` (``experts_per_token`` of
    # ``experts`` SiLU-gated experts of ``expert_width``; softmax, top-k,
    # renormalise; nothing dropped).  ``indexer_topk`` > 0: learned sparse
    # attention — ``indexer_heads`` index heads of ``indexer_head_dim``
    # score every earlier position and a query attends its
    # ``indexer_topk`` best; the index key is cached beside K and V, and
    # the paged engine reaches such a model through the ``*_sparse``
    # methods below (serving/continuous.py; docs/serving.md).
    head_dim: Optional[int] = None
    qk_norm: bool = False
    experts: int = 0
    experts_per_token: int = 8
    expert_width: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    indexer_topk: int = 0

    @property
    def kv_heads(self) -> int:
        """Heads actually stored in the KV cache (GQA-aware; every cache
        allocation site — generate/beam/engine — sizes with this)."""
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self) -> int:
        """Width of one attention head: ``head_dim`` where the model has
        one of its own, else ``hidden_size // num_heads``.  What every
        cache is sized by."""
        return self.head_dim or self.hidden_size // self.num_heads

    def paged_cache_geometry(self) -> dict:
        """What this model caches per token and layer in the paged
        engine, as ``{name: (heads, width)}`` of each block arena
        ``[layers, N, heads, block_size, width]``: K and V of ``kv_heads x
        head_size``; a model with an indexer keeps K and V TOKEN-major
        (one head of ``kv_heads * head_size``: its read gathers whole
        token rows) and adds the index key."""
        if not self.indexer_topk:
            return {"k": (self.kv_heads, self.head_size),
                    "v": (self.kv_heads, self.head_size)}
        row = (1, self.kv_heads * self.head_size)
        return {"k": row, "v": row, "index": (1, self.indexer_head_dim)}

    def setup(self):
        if self.pos_encoding not in ("learned", "rope"):
            raise ValueError(
                f"pos_encoding must be 'learned' or 'rope', got "
                f"{self.pos_encoding!r}")
        if (self.experts or self.indexer_topk or self.qk_norm
                or self.head_dim) and self.pp_stages > 0:
            raise ValueError(
                "head_dim / qk_norm / experts / indexer_topk are not "
                "built into pipelined trunks (pp_stages > 0)")
        if self.experts and self.moe_experts:
            raise ValueError("experts (dropless) and moe_experts "
                             "(capacity-bounded) are two expert layers: "
                             "set one")
        self.embed = nn.Embed(self.vocab_size, self.hidden_size,
                              name="embed")
        # rope rotates q/k inside attention: no absolute position table
        self.pos_embed = (
            nn.Embed(self.max_position, self.hidden_size,
                     name="pos_embed")
            if self.pos_encoding == "learned" else None)
        self.ln_f = _make_norm(self.norm, self.ln_eps, "ln_f")
        if not self.tied_head:
            self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                    dtype=jnp.float32, name="lm_head")
        if self.pp_stages > 0:
            from analytics_zoo_tpu.parallel.pipeline import GPipe

            if self.num_layers % self.pp_stages:
                raise ValueError(
                    f"num_layers {self.num_layers} must divide into "
                    f"pp_stages {self.pp_stages}")
            if self.dropout:
                raise ValueError("pp_stages needs dropout=0 (stages run "
                                 "without rng plumbing)")
            if self.remat:
                raise ValueError(
                    "remat is not applied to pipelined trunks (the GPipe "
                    "scan already bounds live activations to one "
                    "microbatch per stage); set remat=False")
            if self.moe_experts:
                raise ValueError(
                    "moe_experts is not supported with pp_stages (MoE "
                    "dispatch inside shard_map stages would not see the "
                    "ep axis); use MoE without pp, or pp without MoE")
            self.trunk = GPipe(
                stage=_LMStage(self.num_layers // self.pp_stages,
                               self.hidden_size, self.num_heads,
                               self.intermediate_size, dtype=self.dtype,
                               use_flash=self.use_flash,
                               num_kv_heads=self.num_kv_heads,
                               pos_encoding=self.pos_encoding,
                               rope_base=self.rope_base,
                               ln_eps=self.ln_eps,
                               norm=self.norm, mlp=self.mlp,
                               use_bias=self.use_bias,
                               qkv_bias=self.qkv_bias),
                n_stages=self.pp_stages,
                n_microbatches=self.pp_microbatches,
                schedule=self.pp_schedule,
                mesh=self.mesh, name="trunk")
            self.layers = ()
            return
        # remat checkpoints each block's training __call__ (recompute in
        # backward instead of storing activations); decode is untouched
        # (no gradients there)
        layer_cls = nn.remat(DecoderLayer, static_argnums=(2,),
                             methods=["__call__"]) if self.remat \
            else DecoderLayer
        self.layers = [
            layer_cls(self.hidden_size, self.num_heads,
                      self.intermediate_size, self.dropout,
                      dtype=self.dtype, mesh=self.mesh,
                      use_flash=self.use_flash,
                      sp_strategy=self.sp_strategy,
                      num_experts=(self.moe_experts if self.moe_experts > 0
                                   and (i + 1) % max(1, self.moe_every) == 0
                                   else 0),
                      moe_top_k=self.moe_top_k,
                      moe_capacity_factor=self.moe_capacity_factor,
                      num_kv_heads=self.num_kv_heads,
                      pos_encoding=self.pos_encoding,
                      rope_base=self.rope_base,
                      ln_eps=self.ln_eps,
                      norm=self.norm, mlp=self.mlp,
                      use_bias=self.use_bias, qkv_bias=self.qkv_bias,
                      head_dim=self.head_dim, qk_norm=self.qk_norm,
                      experts=self.experts,
                      experts_per_token=self.experts_per_token,
                      expert_width=self.expert_width,
                      indexer_heads=self.indexer_heads,
                      indexer_head_dim=self.indexer_head_dim,
                      indexer_topk=self.indexer_topk,
                      name=f"layer_{i}")
            for i in range(self.num_layers)]

    def _logits(self, x):
        if not self.tied_head:
            return self.lm_head(x.astype(jnp.float32))
        # tied head: f32 logits for a stable softmax/CE
        emb = self.embed.embedding.astype(jnp.float32)
        return jnp.einsum("bte,ve->btv", x.astype(jnp.float32), emb)

    def hidden_states(self, tokens, train: bool = False):
        """Final-LayerNorm hidden states [B, T, H] — the forward minus
        the vocab head.  ``LMWithFusedLoss`` consumes this to compute CE
        blockwise without ever materialising the [B, T, V] logits."""
        B, T = tokens.shape
        if T > self.max_position:
            raise ValueError(
                f"sequence length {T} exceeds max_position "
                f"{self.max_position} (out-of-range position lookups "
                "would silently return NaN/clamped rows)")
        x = self.embed(tokens)
        if self.pos_embed is not None:
            x = x + self.pos_embed(jnp.arange(T)[None])
        x = _constrain_seq(x.astype(self.dtype), self.mesh)
        if self.pp_stages > 0:
            x = self.trunk(x)
        else:
            for layer in self.layers:
                x = layer(x, train)
        return self.ln_f(x)

    def __call__(self, tokens, train: bool = False):
        return self._logits(self.hidden_states(tokens, train))

    def decode_step(self, tok, caches_k, caches_v, pos):
        """tok: [B] current tokens; caches_k/v: [n_layers, B, L,
        kv_heads, D] (GQA models cache only their KV heads); pos: scalar
        int32 (lockstep batch) or [B] vector (per-row positions,
        continuous batching).  Returns (logits [B, V], caches_k,
        caches_v)."""
        if self.pp_stages > 0:
            raise NotImplementedError(
                "cached decode is not pipelined; convert the params with "
                "models.lm.unstack_pp_params and generate on a "
                "pp_stages=0 TransformerLM of the same dimensions")
        self._no_arena_indexer()
        x = self.embed(tok)[:, None]
        if self.pos_embed is not None:
            x = x + (self.pos_embed(pos)[None, None]
                     if jnp.ndim(pos) == 0
                     else self.pos_embed(pos)[:, None])
        x = x.astype(self.dtype)
        ks, vs = [], []
        for i, layer in enumerate(self.layers):
            x, ck, cv = layer.decode(x, caches_k[i], caches_v[i], pos)
            ks.append(ck)
            vs.append(cv)
        logits = self._logits(self.ln_f(x))[:, 0]
        return logits, jnp.stack(ks), jnp.stack(vs)

    def verify_step(self, toks, caches_k, caches_v, pos):
        """Cached decode of S tokens per row in ONE forward — the
        speculative-decoding verify pass (models/speculative.py).

        toks: [B, S]; caches as in decode_step; pos: [B] int32, row b's
        tokens land at cache positions pos[b]..pos[b]+S-1.  Returns
        (logits [B, S, V], caches_k, caches_v).  All S K/V entries are
        written; advancing pos by fewer than S on the next call makes
        the surplus entries dead (never attended, later overwritten) —
        that is the rejection mechanism."""
        h, ck, cv = self.verify_hidden(toks, caches_k, caches_v, pos)
        return self._logits(h), ck, cv

    def verify_hidden(self, toks, caches_k, caches_v, pos):
        """``verify_step`` minus the vocab head: (hidden [B, S, H],
        caches).  Callers that consume ONE position per row (the greedy
        forward prefill) gather the hidden state first and apply the
        head to [B, 1, H] — materialising [B, S, V] logits for a long
        prompt is exactly the multi-GB residency LMWithFusedLoss exists
        to avoid."""
        if self.pp_stages > 0:
            raise NotImplementedError(
                "verify_step is not pipelined (same restriction as "
                "decode_step); convert with models.lm.unstack_pp_params")
        self._no_arena_indexer()
        B, S = toks.shape
        x = self.embed(toks)
        if self.pos_embed is not None:
            p = pos[:, None] + jnp.arange(S)[None, :]
            x = x + self.pos_embed(p)
        x = x.astype(self.dtype)
        ks, vs = [], []
        for i, layer in enumerate(self.layers):
            x, ck, cv = layer.decode_k(x, caches_k[i], caches_v[i], pos)
            ks.append(ck)
            vs.append(cv)
        return self.ln_f(x), jnp.stack(ks), jnp.stack(vs)

    def decode_step_paged(self, tok, pools_k, pools_v, tables, pos,
                          kernel="gather", mesh=None, kv_sharded=True):
        """One cached decode step against a PAGED KV cache.

        tok: [B] current tokens; pools_k/v: [n_layers, N, kv_heads, bs,
        D] (plain arrays or ops.flash_attention.QuantKV int8 pools) —
        ONE flat block pool per layer shared by all residents;
        tables: [B, M] int32 per-row block tables (logical block j ->
        physical pool block); pos: [B] int32 per-row positions.
        Returns (logits [B, V], pools_k, pools_v) with each row's new
        K/V written through its table at position pos[b] — attention
        reads only logical positions <= pos[b], so garbage in
        unwritten/sink blocks is never attended.  ``kernel`` picks the
        gather fallback or the fused Pallas paged-attention kernel;
        ``mesh``/``kv_sharded`` run the fused kernel per-chip under
        shard_map against the engine's tp-sharded (or replicated-hatch)
        pool layout (ops.flash_attention.paged_attention).
        """
        if self.pp_stages > 0:
            raise NotImplementedError(
                "cached decode is not pipelined; convert the params "
                "with models.lm.unstack_pp_params and generate on a "
                "pp_stages=0 TransformerLM of the same dimensions")
        x = self.embed(tok)[:, None]
        if self.pos_embed is not None:
            x = x + self.pos_embed(pos)[:, None]
        x = x.astype(self.dtype)
        (pk, pv), N = _flat_pools((pools_k, pools_v))
        for i, layer in enumerate(self.layers):
            x, pk, pv = layer.decode_paged(x, pk, pv, tables + i * N,
                                           pos, kernel=kernel,
                                           mesh=mesh,
                                           kv_sharded=kv_sharded)
        logits = self._logits(self.ln_f(x))[:, 0]
        pk, pv = _stacked_pools((pk, pv), len(self.layers))
        return logits, pk, pv

    def verify_step_paged(self, toks, pools_k, pools_v, tables, pos,
                          kernel="gather", mesh=None, kv_sharded=True):
        """``verify_step`` against a paged cache: S tokens per row in one
        block-causal forward, K/V scattered through the block tables.
        Returns (logits [B, S, V], pools_k, pools_v).

        Same rejection mechanism as :meth:`verify_step`, expressed in
        pages: all S entries are written through the table, and the
        caller advancing ``pos`` by fewer than S makes the surplus
        entries dead — the next verify overwrites them in place before
        the causal mask ever exposes them, so speculative rollback
        costs zero block copies (ops/flash_attention.paged_kv_update
        documents the write/clamp contract)."""
        h, pk, pv = self.verify_hidden_paged(toks, pools_k, pools_v,
                                             tables, pos, kernel=kernel,
                                             mesh=mesh,
                                             kv_sharded=kv_sharded)
        return self._logits(h), pk, pv

    def verify_hidden_paged(self, toks, pools_k, pools_v, tables, pos,
                            limit=None, kernel="gather", mesh=None,
                            kv_sharded=True):
        """``verify_step_paged`` minus the vocab head: (hidden [B, S,
        H], pools).  The paged-admission prefill consumes ONE position
        per row, gathers that hidden state, and applies the head to
        [B, 1, H] — same logits-residency rationale as
        :meth:`verify_hidden`.  ``limit`` ([B] int32, optional) drops
        K/V writes at positions >= limit[b] (padding columns of a
        chunk/suffix grid write nothing at all)."""
        if self.pp_stages > 0:
            raise NotImplementedError(
                "verify_step is not pipelined (same restriction as "
                "decode_step); convert with models.lm.unstack_pp_params")
        B, S = toks.shape
        x = self.embed(toks)
        if self.pos_embed is not None:
            p = pos[:, None] + jnp.arange(S)[None, :]
            x = x + self.pos_embed(p)
        x = x.astype(self.dtype)
        (pk, pv), N = _flat_pools((pools_k, pools_v))
        for i, layer in enumerate(self.layers):
            x, pk, pv = layer.decode_paged(x, pk, pv, tables + i * N,
                                           pos, limit=limit,
                                           kernel=kernel, mesh=mesh,
                                           kv_sharded=kv_sharded)
        pk, pv = _stacked_pools((pk, pv), len(self.layers))
        return self.ln_f(x), pk, pv

    def prefill_chunk(self, toks, caches_k, caches_v, pos, lens):
        """One CHUNKED-PREFILL step against the slot-arena cache: run a
        ``[B, C]`` chunk of each row's prompt block-causally at its own
        position offset (``verify_hidden`` — the same offset attention
        the speculative verify and prefix admission use), write the
        chunk's K/V into the per-row cache, and return each row's
        last-real-position logits ``[B, V]`` (head applied to
        ``[B, 1, H]`` — never a ``[B, C, V]`` cube).

        toks: [B, C] chunk tokens (right-padded); caches as in
        :meth:`decode_step`; pos: [B] int32 — row b's chunk starts at
        cache position pos[b] (its fill frontier); lens: [B] int32 true
        chunk lengths.  On the FINAL chunk of a prompt the returned
        logits are exactly the monolithic prefill's last-position
        logits, so the caller picks the request's first token from
        them; mid-prompt the return value is dead.  Padding columns
        write dead K/V past the frontier that the next chunk (or
        decode) overwrites before anything attends them — the arena
        rows are private, so unlike the paged twin no write-limit is
        needed."""
        h, ck, cv = self.verify_hidden(toks, caches_k, caches_v, pos)
        last_h = jnp.take_along_axis(h, (lens - 1)[:, None, None],
                                     axis=1)
        return self._logits(last_h)[:, 0], ck, cv

    def prefill_chunk_paged(self, toks, pools_k, pools_v, tables, pos,
                            lens, kernel="gather", mesh=None,
                            kv_sharded=True):
        """The paged twin of :meth:`prefill_chunk`: the chunk's K/V
        scatter through per-row block tables into the shared pool, with
        writes LIMITED to ``pos + lens`` — padding columns write
        nothing, so a narrow table window (sliced to the fill frontier
        for bounded compile shapes) can never clamp a padding write
        into a live block.  Also the whole of paged admission: a
        prompt's unshared suffix IS its one big chunk."""
        h, pk, pv = self.verify_hidden_paged(toks, pools_k, pools_v,
                                             tables, pos,
                                             limit=pos + lens,
                                             kernel=kernel, mesh=mesh,
                                             kv_sharded=kv_sharded)
        last_h = jnp.take_along_axis(h, (lens - 1)[:, None, None],
                                     axis=1)
        return self._logits(last_h)[:, 0], pk, pv

    def _no_arena_indexer(self):
        if self.indexer_topk:
            raise NotImplementedError(
                "a model with an indexer (indexer_topk > 0) caches an "
                "index key beside K and V, which only the paged cache "
                "holds: serve it with paged=True (the *_paged_sparse "
                "methods); the slot-arena decode, generate() and "
                "beam_search() have no place for it")

    # ---- a model with an indexer against the paged cache ---------------
    # Siblings of the three paged methods above, reached only where
    # ``indexer_topk`` is set: the key side of the cache is an
    # ``ops.sparse_attention.IndexedKeys`` (the K pool and the index-key
    # pool), and each returns, after the pools, what the tick's counters
    # read: ``n_read [B]`` (positions whose K/V the attention read for the
    # row, the most over the layers) and ``load [layers, X]`` (assignments
    # every expert got in every layer from the tokens of ``count``).

    def _paged_sparse_trunk(self, x, pools_k, pools_v, tables, pos,
                            limit, count):
        from analytics_zoo_tpu.ops.sparse_attention import IndexedKeys

        (pk, pv, pi), N = _flat_pools(
            (pools_k.k, pools_v, pools_k.index))
        n_read, loads = None, []
        for i, layer in enumerate(self.layers):
            x, pk, pv, pi, nr, load = layer.decode_paged_sparse(
                x, pk, pv, pi, tables + i * N, pos, limit=limit,
                count=count)
            n_read = nr if n_read is None else jnp.maximum(n_read, nr)
            loads.append(load)
        pk, pv, pi = _stacked_pools((pk, pv, pi), len(self.layers))
        return (self.ln_f(x), IndexedKeys(pk, pi), pv, n_read,
                jnp.stack(loads))

    def decode_step_paged_sparse(self, tok, pools_k, pools_v, tables, pos,
                                 count=None):
        """:meth:`decode_step_paged` of a model with an indexer.
        ``count`` ``[B]`` bool: the rows whose tokens the expert load
        counts (None: all).  Returns (logits [B, V], pools_k, pools_v,
        n_read, load)."""
        x = self.embed(tok)[:, None]
        if self.pos_embed is not None:
            x = x + self.pos_embed(pos)[:, None]
        h, pk, pv, n_read, load = self._paged_sparse_trunk(
            x.astype(self.dtype), pools_k, pools_v, tables, pos, None,
            None if count is None else count[:, None])
        return self._logits(h)[:, 0], pk, pv, n_read, load

    def prefill_chunk_paged_sparse(self, toks, pools_k, pools_v, tables,
                                   pos, lens, real=None):
        """:meth:`prefill_chunk_paged` of a model with an indexer: the
        chunk's K/V and index keys scatter through the tables, limited to
        ``pos + lens``; padding columns, and the rows where ``real``
        ``[B]`` is false, are not counted in the expert load.  Returns
        (last-real-position logits [B, V], pools_k, pools_v, n_read,
        load)."""
        B, S = toks.shape
        x = self.embed(toks)
        if self.pos_embed is not None:
            x = x + self.pos_embed(pos[:, None] + jnp.arange(S)[None, :])
        h, pk, pv, n_read, load = self._paged_sparse_trunk(
            x.astype(self.dtype), pools_k, pools_v, tables, pos,
            pos + lens,
            (jnp.arange(S)[None, :] < lens[:, None])
            & (True if real is None else real[:, None]))
        last_h = jnp.take_along_axis(h, (lens - 1)[:, None, None], axis=1)
        return self._logits(last_h)[:, 0], pk, pv, n_read, load

    def prefill(self, tokens):
        """Causal forward that ALSO returns every layer's K/V: ``(logits
        [B, T, V], ks [n_layers, B, T, H, D], vs)``.  One MXU-friendly
        forward replaces T sequential decode steps when a new request
        joins the continuous-batching KV arena."""
        if self.pp_stages > 0:
            raise NotImplementedError(
                "prefill is not pipelined (same restriction as "
                "decode_step); serve a pp_stages=0 restore instead")
        self._no_arena_indexer()
        B, T = tokens.shape
        if T > self.max_position:
            raise ValueError(
                f"sequence length {T} exceeds max_position "
                f"{self.max_position}")
        x = self.embed(tokens)
        if self.pos_embed is not None:
            x = x + self.pos_embed(jnp.arange(T)[None])
        x = _constrain_seq(x.astype(self.dtype), self.mesh)
        ks, vs = [], []
        for layer in self.layers:
            x, k, v = layer.forward_kv(x)
            ks.append(k)
            vs.append(v)
        return self._logits(self.ln_f(x)), jnp.stack(ks), jnp.stack(vs)


def top_p_filter(scaled, top_p):
    """Nucleus filter over the last axis: keep the smallest set of
    tokens whose (temperature-scaled) probability mass reaches
    ``top_p``; everything else goes to -inf.  The highest-probability
    token always survives (cumulative > p can exclude everything at
    tiny p otherwise).  top_p may be a scalar or broadcastable
    per-row [..., 1] array; values >= 1 or <= 0 disable the filter
    row-wise."""
    probs = jax.nn.softmax(scaled, axis=-1)
    sorted_probs = jnp.sort(probs, axis=-1)[..., ::-1]
    csum = jnp.cumsum(sorted_probs, axis=-1)
    # rank of the last kept token: first index where csum >= top_p
    keep_n = jnp.sum((csum < top_p).astype(jnp.int32), axis=-1,
                     keepdims=True) + 1
    kth = jnp.take_along_axis(sorted_probs,
                              jnp.minimum(keep_n - 1,
                                          scaled.shape[-1] - 1),
                              axis=-1)
    active = (top_p > 0.0) & (top_p < 1.0)
    return jnp.where(active & (probs < kth), -jnp.inf, scaled)


def _gen_state(model, prompt, max_new_tokens, prompt_len):
    """The prompt-length clamp + KV-cache allocation BOTH generate paths
    share — one definition, so cache sizing and the length-degradation
    rule can never drift between them (their token-identical guarantee
    depends on it)."""
    B, Pn = prompt.shape
    L = Pn + max_new_tokens
    plen = (jnp.full((B,), Pn, jnp.int32) if prompt_len is None
            else jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1, Pn))
    H = model.kv_heads                  # GQA: cache stores KV heads only
    D = model.head_size
    ck = jnp.zeros((model.num_layers, B, L, H, D),
                   jnp.dtype(model.dtype))
    return L, plen, ck, jnp.zeros_like(ck)


def _generate_forward_prefill(model, variables, prompt, max_new_tokens,
                              prompt_len, eos_id):
    """Greedy generation, forward-prefill variant (see generate()):
    one verify_step over the padded prompt + a max_new-step scan at
    per-row positions — the continuous engine's admission pattern
    applied to the batch path."""
    B, Pn = prompt.shape
    L, plen, ck, cv = _gen_state(model, prompt, max_new_tokens,
                                 prompt_len)
    # one block-causal forward writes K/V for every prompt position;
    # entries past a row's true length are dead (mask never reaches
    # them) and generation overwrites them in order.  Hidden-only: the
    # head applies to ONE gathered position per row, so [B, P, V]
    # logits are never materialised (that tensor is ~8 GB for a
    # llama-vocab model at P=2048).
    hidden, ck, cv = model.apply(
        variables, prompt, ck, cv, jnp.zeros((B,), jnp.int32),
        method=TransformerLM.verify_hidden)
    last_h = jnp.take_along_axis(
        hidden, (plen - 1)[:, None, None], axis=1)        # [B, 1, H]
    first_logits = model.apply(variables, last_h,
                               method=TransformerLM._logits)[:, 0]
    tok0 = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
    done0 = jnp.zeros((B,), bool)
    if eos_id is not None:
        done0 = tok0 == eos_id

    def step(carry, _):
        tok, pos, done, ck, cv = carry
        logits, ck, cv = model.apply(
            variables, tok, ck, cv, pos,
            method=TransformerLM.decode_step)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        # last write lands at plen+max_new-2 <= L-2: no clamp needed
        return (nxt, pos + 1, done, ck, cv), nxt

    if max_new_tokens == 1:
        return tok0[:, None]
    (_, _, _, _, _), toks = lax.scan(
        step, (tok0, plen, done0, ck, cv), None,
        length=max_new_tokens - 1)
    return jnp.concatenate([tok0[:, None], toks.transpose(1, 0)], axis=1)


def lm_loss(logits, tokens):
    """Shifted next-token CE (mean over B x (T-1))."""
    import optax

    return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]))


def fused_lm_loss(per_sample_losses, _tokens):
    """Estimator loss for ``LMWithFusedLoss`` models: the model output
    already IS per-sample CE, so the loss is just its mean."""
    return jnp.mean(per_sample_losses)


class LMWithFusedLoss(nn.Module):
    """Training wrapper that computes the shifted next-token CE
    BLOCKWISE over the sequence, never materialising the [B, T, V]
    logits tensor.

    Why: the plain path writes f32 logits (B=8, T=2048, V=32000 →
    2.1 GB), reads them through softmax-CE, and materialises the same
    shape again as dlogits in backward — several full HBM passes over
    multi-GB tensors per step, and an O(T·V) residency that forbids
    long-context training (T=8192 would need 8.4 GB for logits alone).
    Here each ``t_block`` slice runs head-matmul + CE inside a
    ``lax.scan`` whose body is ``jax.checkpoint``-ed: backward
    recomputes the block's logits from the (tiny) hidden slice, so peak
    residency is O(B · t_block · V) regardless of T.  Cost: one extra
    head matmul per block in backward — the standard remat trade, paid
    where the tensor is bandwidth-monstrous and the matmul is cheap.

    Contract: ``__call__(tokens, train) -> [B]`` per-sample mean CE
    (use ``loss=fused_lm_loss`` with the Estimator; ``predict`` on this
    wrapper returns losses, not logits — serve/generate with the inner
    ``lm`` instead).  ``mean(wrapper(tokens)) == lm_loss(lm(tokens),
    tokens)`` exactly (tested)."""

    lm: TransformerLM
    t_block: int = 512

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        import optax

        if not self.lm.tied_head:
            raise ValueError(
                "LMWithFusedLoss computes blockwise logits from the TIED "
                "embedding table; an untied-head model (tied_head=False, "
                "e.g. a llama import) would silently train the wrong "
                "projection — use loss=lm_loss on the plain model")
        h = self.lm.hidden_states(tokens, train)
        emb = self.lm.embed.embedding.astype(jnp.float32)
        hs = h[:, :-1].astype(jnp.float32)
        ys = tokens[:, 1:]
        B, n, H = hs.shape
        tb = min(int(self.t_block), n)
        pad = (-n) % tb
        if pad:
            hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
            ys = jnp.pad(ys, ((0, 0), (0, pad)))
        nb = (n + pad) // tb
        hb = hs.reshape(B, nb, tb, H).transpose(1, 0, 2, 3)
        yb = ys.reshape(B, nb, tb).transpose(1, 0, 2)
        mask = (jnp.arange(nb * tb) < n).astype(
            jnp.float32).reshape(nb, tb)

        def body(acc, blk):
            hx, yx, mx = blk
            logits = jnp.einsum("bth,vh->btv", hx, emb)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, yx)
            return acc + jnp.sum(ce * mx[None, :], axis=1), None

        acc0 = jnp.zeros((B,), jnp.float32)
        total, _ = lax.scan(jax.checkpoint(body), acc0, (hb, yb, mask))
        return total / n


def _arena_model(model, what: str) -> None:
    """``generate()`` and ``beam_search()`` thread a K/V arena through
    ``TransformerLM``'s cached methods; a model that keeps other state
    (models/hybrid_lm.py) is served by the paged engine alone."""
    if getattr(model, "state_layers", 0):
        raise NotImplementedError(
            f"{what} threads a K/V arena and nothing else: a model with "
            f"state-space layers keeps a recurrent state a row, which "
            f"only the paged + chunked ContinuousEngine carries "
            f"(docs/serving.md)")


def generate(model: TransformerLM, variables, prompt,
             max_new_tokens: int, prompt_len=None, *,
             temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0,
             rng=None, eos_id=None, prefill: str = "auto") -> jax.Array:
    """Generation with a threaded KV cache.

    prompt: [B, P] int32; ``prompt_len`` (optional [B] int32) gives each
    row's true prompt length for right-padded ragged batches (the serving
    path) — defaults to the full width P.  Returns [B, max_new_tokens]:
    row i's tokens generated after its own prompt end.

    ``prefill``: GREEDY decoding defaults to the FORWARD prefill — one
    block-causal ``verify_step`` over the whole (padded) prompt fills
    the cache in a single MXU-friendly forward, then a ``max_new``-step
    scan decodes at per-row positions: P + max_new sequential steps
    become max_new.  Token output is identical to the scan path
    (``decode_k`` is bitwise-equal to sequential decode; tested), and
    pad positions' K/V are dead entries the per-row mask never reaches.
    ``prefill="scan"`` forces the original single-scan path (prompt
    positions teacher-force; also what SAMPLED decoding always uses —
    its batch rng draws are tied to the lockstep scan and are kept
    exactly reproducible).

    Sampling: ``temperature=0`` (default) is greedy argmax;
    ``temperature>0`` samples from logits/temperature (pass ``rng``, a
    ``jax.random`` key — required then), optionally truncated to the
    ``top_k`` highest-probability tokens and/or the ``top_p`` nucleus
    (the smallest set of tokens whose probability mass reaches top_p;
    0 or >=1 disables).  Both filters compose (top_k first).

    ``eos_id``: once a row emits it (past its prompt), the rest of the
    row freezes at eos — the fixed-shape analog of stop-on-EOS (same
    contract as seq2seq.greedy_generate; output stays [B, max_new]).
    """
    _arena_model(model, "generate()")
    B, Pn = prompt.shape
    L = Pn + max_new_tokens
    if L > model.max_position:
        raise ValueError(f"prompt+new = {L} exceeds max_position "
                         f"{model.max_position}")
    if prefill not in ("auto", "forward", "scan"):
        raise ValueError(f"prefill must be auto|forward|scan, got "
                         f"{prefill!r}")
    can_forward = (temperature <= 0.0 and max_new_tokens > 0
                   and model.pp_stages == 0)
    if prefill == "forward" and not can_forward:
        # an explicit request that silently measured the scan path
        # would invalidate whatever comparison the caller is making
        raise ValueError(
            "prefill='forward' needs greedy decoding (temperature=0), "
            "max_new_tokens > 0, and pp_stages=0; use 'auto' to fall "
            "back silently")
    if prefill != "scan" and can_forward:
        return _generate_forward_prefill(model, variables, prompt,
                                         max_new_tokens, prompt_len,
                                         eos_id)
    # prompt_len outside [1, P] has no defined meaning (the scan must
    # start from SOME real token, and can't teacher-force past the row):
    # _gen_state clamps both ends so bad rows degrade to defined
    # behavior (length-1 / full-width prompt) instead of off-by-one
    # garbage — values are traced, so raising is not an option here.
    # Callers that can reject bad lengths per-request (serving) do so
    # before this.
    _, plen, ck0, cv0 = _gen_state(model, prompt, max_new_tokens,
                                   prompt_len)

    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 needs a jax.random key via rng=")

    def pick(logits, t):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = lax.top_k(scaled, top_k)[0][:, -1][:, None]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        if top_p > 0.0:
            scaled = top_p_filter(scaled, jnp.float32(top_p))
        key = jax.random.fold_in(rng, t)
        return jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32)

    def step(carry, t):
        tok, ck, cv, done = carry
        logits, ck, cv = model.apply(
            variables, tok, ck, cv, t, method=TransformerLM.decode_step)
        nxt = pick(logits, t)
        if eos_id is not None:
            # frozen-tail EOS: finished rows keep emitting eos (fixed
            # shapes; the caller trims)
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | ((nxt == eos_id) & (t + 1 >= plen))
        # rows still inside their own prompt replay it
        nxt = jnp.where(t + 1 < plen, prompt[:, jnp.minimum(t + 1, Pn - 1)],
                        nxt)
        return (nxt, ck, cv, done), nxt

    done0 = jnp.zeros((B,), bool)
    (_, _, _, _), toks = lax.scan(
        step, (prompt[:, 0], ck0, cv0, done0), jnp.arange(L - 1))
    # toks[t] is the token at position t+1; row i's generated span is
    # positions [plen_i, plen_i + max_new) -> rows plen_i-1 .. of toks
    toks = toks.transpose(1, 0)                       # [B, L-1]
    idx = jnp.clip(plen[:, None] - 1 + jnp.arange(max_new_tokens)[None],
                   0, L - 2)
    return jnp.take_along_axis(toks, idx, axis=1)
