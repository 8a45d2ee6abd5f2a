"""Transformer / BERT family.

Reference surface (SURVEY.md §2.4, ref: pipeline/api/keras/layers/
self_attention.py — Keras-API ``TransformerLayer`` and ``BERT`` layers, used
by tfpark NLP estimators): full-attention encoder blocks with word/position/
token-type embeddings and a pooler.

TPU-first re-design, not a translation:
- attention runs through ``ring_self_attention`` — sequence-sharded (``sp``)
  exact attention with ICI ppermute rotation — whenever the active mesh has
  an sp axis, full attention otherwise;
- all matmuls bfloat16 on the MXU, LayerNorm/softmax accumulate f32;
- weights carry tensor-parallel partition rules (qkv/up projections sharded
  on the output dim, out/down on the input dim — Megatron layout — so XLA
  inserts exactly one all-reduce per block per direction);
- activations are sharding-constrained to (dp, sp) so long sequences scale
  across the mesh (no reference counterpart; SURVEY §2.3 item 6).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.parallel.partition import with_sharding_constraint
from analytics_zoo_tpu.parallel.ring_attention import (
    full_attention, ring_self_attention)

# Megatron-style TP layout + sp activation sharding.
BERT_PARTITION_RULES = (
    (r"word_embeddings/embedding", P("tp", None)),
    (r"(query|key|value)/kernel", P(None, "tp")),
    (r"attn_out/kernel", P("tp", None)),
    (r"ffn_up/kernel", P(None, "tp")),
    (r"ffn_down/kernel", P("tp", None)),
    (r".*", P()),
)


# MoE-BERT (moe_experts > 0): expert weights over ep(+tp), attention and
# dense layers Megatron-tp as above.  moe.py imports transformer only
# inside a method, so this top-level import cannot cycle.
from analytics_zoo_tpu.models.moe import MOE_PARTITION_RULES as _MOE_RULES

BERT_MOE_PARTITION_RULES = _MOE_RULES + BERT_PARTITION_RULES


def flash_ok(use_flash: Optional[bool], seq_len: int) -> bool:
    """Fused-kernel dispatch policy — ONE home for the measured numbers.

    use_flash=None means auto: the kernel on a TPU from seq 512, XLA
    attention otherwise.  A Mosaic lowering failure is a failure — there
    is no switch that quietly gives way to XLA attention.
    Measured on v5e (BERT-base fine-tune through fit, bf16): XLA wins at
    seq 128 (+44%) and 256 (+15%); the Pallas kernel wins from seq 512
    (+20%), where attention turns HBM-bound and fusion pays.  At seq 2048
    (111M-param causal LM) the kernel is +94% and survives batch sizes
    whose full-attention logits OOM."""
    if use_flash is not None:
        return use_flash
    return jax.default_backend() == "tpu" and seq_len >= 512


def attention_dispatch(q, k, v, kv_mask, *, causal: bool,
                       mesh: Optional[Mesh],
                       use_flash: Optional[bool],
                       sp_strategy: str = "ring") -> jax.Array:
    """The three-way attention dispatch every attention layer shares:
    sequence-parallel attention (ring ppermute or ulysses all_to_all,
    ``sp_strategy``) when the mesh shards the sequence, the Pallas flash
    kernel where measured to win, XLA full attention otherwise."""
    if mesh is not None and "sp" in mesh.axis_names and \
            mesh.shape["sp"] > 1:
        return ring_self_attention(q, k, v, mesh, kv_mask, causal=causal,
                                   strategy=sp_strategy)
    if flash_ok(use_flash, q.shape[1]):
        from analytics_zoo_tpu.ops import (
            flash_attention, sharded_flash_attention)

        if mesh is not None and mesh.size > 1:
            return sharded_flash_attention(q, k, v, mesh, kv_mask,
                                           causal=causal)
        return flash_attention(q, k, v, kv_mask, causal=causal)
    return full_attention(q, k, v, kv_mask, causal=causal)


def _constrain_seq(x, mesh: Optional[Mesh]):
    """hidden states: [B, T, E] -> shard B over dp(+fsdp), T over sp."""
    if mesh is None:
        return x
    from analytics_zoo_tpu.parallel.mesh import batch_axes
    batch = batch_axes(mesh) or None
    seq = "sp" if "sp" in mesh.axis_names else None
    return with_sharding_constraint(x, P(batch, seq, None))


class MultiHeadAttention(nn.Module):
    """Self-attention; ring attention when the mesh has sp > 1, the fused
    Pallas flash kernel (ops.flash_attention) on single-sequence-shard TPU
    runs, XLA full attention otherwise.  use_flash=None means auto."""

    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None
    use_flash: Optional[bool] = None
    sp_strategy: str = "ring"

    @nn.compact
    def __call__(self, x, kv_mask=None, train: bool = False):
        B, T, E = x.shape
        H, D = self.num_heads, self.head_dim
        dense = lambda name: nn.DenseGeneral(
            (H, D), dtype=self.dtype, name=name)
        q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        o = attention_dispatch(q, k, v, kv_mask, causal=False,
                               mesh=self.mesh, use_flash=self.use_flash,
                               sp_strategy=self.sp_strategy)
        return nn.DenseGeneral(E, axis=(-2, -1), dtype=self.dtype,
                               name="attn_out")(o)


class TransformerLayer(nn.Module):
    """ref-parity: Keras-API TransformerLayer (post-LN encoder block).

    ``num_experts > 0`` swaps the dense FFN for an expert-parallel MoE
    block (models/moe.py) — a TPU-native extension with no reference
    counterpart; the residual connection carries capacity-dropped tokens."""

    hidden_size: int
    num_heads: int
    intermediate_size: int
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None
    use_flash: Optional[bool] = None
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    sp_strategy: str = "ring"

    @nn.compact
    def __call__(self, x, kv_mask=None, train: bool = False):
        H = self.num_heads
        D = self.hidden_size // H
        a = MultiHeadAttention(H, D, dtype=self.dtype, mesh=self.mesh,
                               use_flash=self.use_flash,
                               sp_strategy=self.sp_strategy,
                               name="attention")(x, kv_mask, train)
        a = nn.Dropout(self.dropout, deterministic=not train)(a)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x + a)
        x = _constrain_seq(x, self.mesh)
        if self.num_experts > 0:
            from analytics_zoo_tpu.models.moe import MoEMLP

            h = MoEMLP(self.num_experts, self.intermediate_size,
                       top_k=self.moe_top_k,
                       capacity_factor=self.moe_capacity_factor,
                       dtype=self.dtype, mesh=self.mesh,
                       name="moe")(x, train)
        else:
            h = nn.Dense(self.intermediate_size, dtype=self.dtype,
                         name="ffn_up")(x)
            h = nn.gelu(h)
            h = nn.Dense(self.hidden_size, dtype=self.dtype,
                         name="ffn_down")(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_ffn")(x + h)
        return _constrain_seq(x, self.mesh)


class BERT(nn.Module):
    """ref-parity: Keras-API BERT layer — returns (sequence, pooled)."""

    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab: int = 2
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None
    remat: bool = False
    use_flash: Optional[bool] = None
    # MoE-BERT: every `moe_every`-th layer gets an expert-parallel MoE FFN
    # (interleaved dense/MoE, the standard sparse-transformer layout)
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    sp_strategy: str = "ring"

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = False) -> Tuple[jax.Array, jax.Array]:
        B, T = input_ids.shape
        word = nn.Embed(self.vocab_size, self.hidden_size,
                        name="word_embeddings")(input_ids)
        pos = nn.Embed(self.max_position, self.hidden_size,
                       name="position_embeddings")(jnp.arange(T)[None])
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        typ = nn.Embed(self.type_vocab, self.hidden_size,
                       name="token_type_embeddings")(token_type_ids)
        x = nn.LayerNorm(dtype=jnp.float32, name="emb_ln")(word + pos + typ)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        x = _constrain_seq(x.astype(self.dtype), self.mesh)
        kv_mask = None if attention_mask is None else attention_mask > 0
        layer_cls = TransformerLayer
        if self.remat:
            layer_cls = nn.remat(TransformerLayer, static_argnums=(3,))
        for i in range(self.num_layers):
            moe = self.moe_experts if (
                self.moe_experts > 0 and
                (i + 1) % max(1, self.moe_every) == 0) else 0
            x = layer_cls(self.hidden_size, self.num_heads,
                          self.intermediate_size, self.dropout,
                          dtype=self.dtype, mesh=self.mesh,
                          use_flash=self.use_flash,
                          num_experts=moe, moe_top_k=self.moe_top_k,
                          sp_strategy=self.sp_strategy,
                          name=f"layer_{i}")(x, kv_mask, train)
        pooled = nn.tanh(nn.Dense(self.hidden_size, dtype=jnp.float32,
                                  name="pooler")(x[:, 0].astype(jnp.float32)))
        return x.astype(jnp.float32), pooled


class BERTForSequenceClassification(nn.Module):
    num_classes: int = 2
    bert: Optional[BERT] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = False):
        bert = self.bert if self.bert is not None else BERT(name="bert")
        _, pooled = bert(input_ids, token_type_ids, attention_mask, train)
        return nn.Dense(self.num_classes, name="classifier")(pooled)


class BERTForQuestionAnswering(nn.Module):
    """SQuAD head (config #3): start/end logits over sequence positions."""

    bert: Optional[BERT] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = False):
        bert = self.bert if self.bert is not None else BERT(name="bert")
        seq, _ = bert(input_ids, token_type_ids, attention_mask, train)
        logits = nn.Dense(2, name="qa_outputs")(seq)  # [B, T, 2]
        return logits  # start = [..., 0], end = [..., 1]


def qa_loss(logits, targets):
    """SQuAD loss: mean CE over start+end positions.
    targets: (start_positions, end_positions) int arrays [B]."""
    import optax

    start, end = targets
    ls = optax.softmax_cross_entropy_with_integer_labels(
        logits[..., 0], start.astype(jnp.int32))
    le = optax.softmax_cross_entropy_with_integer_labels(
        logits[..., 1], end.astype(jnp.int32))
    return jnp.mean(ls + le) / 2.0
