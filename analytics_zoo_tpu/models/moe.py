"""Mixture-of-Experts layers with expert parallelism over the ``ep`` axis.

The reference has no MoE and no expert parallelism (SURVEY.md §2.3 item 6:
the stack predates LLM-scale training).  Like ring attention (`parallel/
ring_attention.py`), this is a TPU-native extension: the mesh already
declares an ``ep`` axis (parallel/mesh.py CANONICAL_AXES) and this module
makes it real.

TPU-first design, not a port of any GPU MoE runtime:

- **Einsum dispatch, not gather/scatter.**  Tokens are routed through dense
  one-hot dispatch/combine tensors (the Switch-Transformer formulation), so
  the whole layer is three einsums + a softmax — static shapes, MXU-friendly,
  and XLA turns the token→expert regrouping into exactly the ``all_to_all``
  the sharding implies.  A scatter-based router would serialise on TPU.
- **Sharding-implied collectives.**  Expert weights are sharded
  ``P("ep", ...)`` (stacked expert dim over the ep axis) and expert
  activations are constrained to ``P("ep", ...)``; with tokens sharded over
  ``dp``, XLA inserts the dispatch/return all_to_alls over ICI.  No manual
  collective calls.
- **Capacity-bounded, f32 router.**  Router logits/softmax in float32
  (bf16 routing is unstable), experts compute in bfloat16 on the MXU.
  Per-expert capacity = ``ceil(top_k * tokens/experts * capacity_factor)``;
  overflow tokens fall through the residual connection (standard Switch
  behavior) rather than introducing data-dependent shapes.

The auxiliary load-balancing loss is sown into the ``"losses"`` collection;
``Estimator`` collects that collection in its train step, so MoE models
train through the ordinary ``fit()`` path with no special wiring.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.parallel.partition import with_sharding_constraint

# Expert weights: stacked expert dim over ep, Megatron tp layout within each
# expert (up-projection sharded on the output dim, down on the input dim).
# Patterns match the MoE-unique PARAM names (not the instance path), so the
# rules apply under any module name, not just name="moe".  Compose with
# BERT_PARTITION_RULES for a full MoE transformer.
MOE_PARTITION_RULES = (
    (r"w_up$", P("ep", None, "tp")),
    (r"w_down$", P("ep", "tp", None)),
    (r"b_up$", P("ep", None)),
    (r"b_down$", P("ep", None)),
    (r"router/kernel", P()),
)


def load_balancing_loss(router_probs: jax.Array,
                        expert_index: jax.Array,
                        num_experts: int) -> jax.Array:
    """Switch-Transformer aux loss: ``E * sum_e f_e * p_e`` where ``f_e`` is
    the fraction of tokens whose top-1 choice is expert e and ``p_e`` the
    mean router probability for e.  Equals 1.0 under perfect balance."""
    f = jnp.mean(
        jax.nn.one_hot(expert_index, num_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(router_probs.astype(jnp.float32), axis=0)
    return num_experts * jnp.sum(f * p)


class MoEMLP(nn.Module):
    """Token-choice top-k MoE feed-forward block.

    Input ``[B, T, E]`` (or ``[N, E]``) → same shape.  Each token is routed
    to its ``top_k`` experts; each expert is a gelu MLP
    ``E -> intermediate_size -> E`` computed in ``dtype`` on the MXU.
    Tokens over an expert's capacity are dropped (their contribution is 0 —
    callers keep a residual connection so dropped tokens pass through).

    Capacity-bounded routing makes outputs weakly BATCH-COUPLED: tokens
    compete for expert slots, so a row's output can shift slightly with
    its batchmates (including padding rows at serving time).  This is
    inherent to capacity-style MoE, not a bug; raise ``capacity_factor``
    where batch-composition independence matters more than compute.

    Measured bound (tests/test_moe.py::
    test_moe_decode_capacity_agreement_bound — skew-trained MoE-LM,
    decode pools B=32 tokens/step vs the forward's B*T jointly): greedy
    decode-vs-forward max |logit delta| is 1.98 at capacity_factor=0.25
    and 1.19 at 1.0, yet greedy-token agreement stayed 100% (residuals
    absorb the drops); at capacity_factor=2.0 both paths serve every
    token and the logits are IDENTICAL (delta 0.0).  So CF=2 is the
    "exact decode parity" setting for skewed routing, not just a >=99%
    heuristic.
    """

    num_experts: int
    intermediate_size: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        orig_shape = x.shape
        E = orig_shape[-1]
        X, F, K = self.num_experts, self.intermediate_size, self.top_k
        if not 1 <= K <= X:
            raise ValueError(f"top_k={K} must be in [1, {X}]")
        xt = x.reshape(-1, E)                       # [N, E] tokens
        N = xt.shape[0]

        # --- routing (f32) -------------------------------------------------
        logits = nn.Dense(X, dtype=jnp.float32, param_dtype=jnp.float32,
                          use_bias=False, name="router")(
            xt.astype(jnp.float32))                 # [N, X]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)       # [N, K]
        # renormalise the selected gates so contributions sum to 1
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        if train:
            aux = load_balancing_loss(probs, gate_idx[:, 0], X)
            self.sow("losses", "moe_aux_loss",
                     self.aux_loss_weight * aux,
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)

        # --- capacity-bounded one-hot dispatch ----------------------------
        capacity = max(K, math.ceil(K * N / X * self.capacity_factor))
        # [N, K, X] assignment one-hots, k-major priority order
        assign = jax.nn.one_hot(gate_idx, X, dtype=jnp.float32)
        # position of each (token, k) within its expert's queue: cumsum over
        # the flattened (k, token) order so k=0 choices get priority
        flat = assign.transpose(1, 0, 2).reshape(K * N, X)  # [K*N, X]
        pos_flat = jnp.cumsum(flat, axis=0) - flat          # arrivals before
        pos = pos_flat.reshape(K, N, X).transpose(1, 0, 2)  # [N, K, X]
        within = (pos < capacity) * assign                  # keep in-capacity
        pos_id = jnp.sum(pos * assign, axis=-1).astype(jnp.int32)   # [N, K]
        slot_oh = jax.nn.one_hot(pos_id, capacity, dtype=jnp.float32)
        # dispatch [N, X, C]: token n occupies slot pos_id[n,k] of expert
        dispatch = jnp.einsum("nkx,nkc->nxc", within, slot_oh)
        combine = jnp.einsum("nkx,nk,nkc->nxc", within, gate_vals, slot_oh)

        # --- expert computation (bf16, ep-sharded) ------------------------
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (X, E, F), jnp.float32)
        b_up = self.param("b_up", nn.initializers.zeros, (X, F), jnp.float32)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (X, F, E), jnp.float32)
        b_down = self.param("b_down", nn.initializers.zeros, (X, E),
                            jnp.float32)

        ein = xt.astype(self.dtype)
        expert_in = jnp.einsum("nxc,ne->xce", dispatch.astype(self.dtype),
                               ein)                        # [X, C, E]
        expert_in = self._constrain(expert_in, tp_last=False)
        h = jnp.einsum("xce,xef->xcf", expert_in,
                       w_up.astype(self.dtype)) + \
            b_up.astype(self.dtype)[:, None, :]
        h = nn.gelu(h)
        h = self._constrain(h, tp_last=True)
        out_e = jnp.einsum("xcf,xfe->xce", h,
                           w_down.astype(self.dtype)) + \
            b_down.astype(self.dtype)[:, None, :]
        out_e = self._constrain(out_e, tp_last=False)
        y = jnp.einsum("nxc,xce->ne", combine.astype(self.dtype), out_e)
        return y.reshape(orig_shape).astype(x.dtype)

    def _constrain(self, t, *, tp_last: bool):
        """Expert-major activations: stacked expert dim over ep.  Only the
        intermediate ``h`` ([X, C, F]) carries tp on its last dim — its F
        dim matches w_up's tp-sharded output / w_down's tp-sharded input, so
        the up-projection shards and the down-projection reduce-scatters
        over tp.  ``expert_in``/``out_e`` end in the model dim E, which the
        weights keep replicated; constraining E onto tp would force a
        reshard collective around every einsum for no compute split."""
        if self.mesh is None or "ep" not in self.mesh.axis_names:
            return t
        tp = "tp" if (tp_last and "tp" in self.mesh.axis_names) else None
        return with_sharding_constraint(t, P("ep", None, tp))


class DroplessMoE(nn.Module):
    """Token-choice top-k experts with NO capacity: every token reaches
    every expert it chose, so a row's output does not depend on its
    batchmates (``MoEMLP`` above drops tokens over capacity and stays for
    what trains with it).

    Router logits and softmax over ALL experts in float32, ``lax.top_k``,
    the chosen gates renormalised to sum to 1 (softmax, then top-k, then
    renormalise).  The ``N * top_k`` assignments are sorted by expert and
    the three SiLU-gated, bias-free projections run as grouped matmuls
    over the contiguous groups (``jax.lax.ragged_dot``: on the TPU one
    kernel that multiplies each assignment through its own expert's
    matrix and touches no expert that got no token), then un-sorted and
    combined with the gates in float32.

    Parameters: ``router [E, X]``, ``w_gate``/``w_up`` ``[X, E, F]``,
    ``w_down`` ``[X, F, E]``.
    """

    num_experts: int
    expert_width: int
    top_k: int = 8
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, count=None):
        """x ``[..., E]`` -> ``(y [..., E], load [X] int32)``: ``load[e]``
        is how many assignments expert e got from the tokens where
        ``count`` (bool ``x.shape[:-1]``; None counts all) is true —
        padding is computed like any token but not counted."""
        E = x.shape[-1]
        X, F, K = self.num_experts, self.expert_width, self.top_k
        if not 1 <= K <= X:
            raise ValueError(f"top_k={K} must be in [1, {X}]")
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (E, X), jnp.float32)
        w_gate = self.param("w_gate", init, (X, E, F), jnp.float32)
        w_up = self.param("w_up", init, (X, E, F), jnp.float32)
        w_down = self.param("w_down", init, (X, F, E), jnp.float32)
        xt = x.reshape(-1, E)
        N = xt.shape[0]
        probs = jax.nn.softmax(
            jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32)),
            axis=-1)                                        # [N, X] f32
        gates, chosen = jax.lax.top_k(probs, K)             # [N, K]
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        expert = chosen.reshape(-1)                         # [N*K]
        order = jnp.argsort(expert)          # stable: by expert, then token
        sizes = jnp.zeros((X,), jnp.int32).at[expert].add(1)
        xs = xt.astype(self.dtype)[order // K]              # [N*K, E]
        rd = lambda a, w: jax.lax.ragged_dot(
            a, w.astype(self.dtype), sizes,
            preferred_element_type=jnp.float32)
        h = jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up)
        ys = rd(h.astype(self.dtype), w_down)               # [N*K, E] f32
        ya = ys[jnp.argsort(order)].reshape(N, K, E)        # un-sorted
        y = jnp.einsum("nk,nke->ne", gates, ya)
        if count is None:
            load = sizes
        else:
            load = jnp.zeros((X,), jnp.int32).at[expert].add(
                jnp.repeat(count.reshape(-1), K).astype(jnp.int32))
        return y.reshape(x.shape).astype(x.dtype), load


class MoETransformerLayer(nn.Module):
    """Post-LN encoder block with an MoE FFN (attention as in
    models/transformer.py).  Residual connections mean capacity-dropped
    tokens degrade gracefully to identity."""

    hidden_size: int
    num_heads: int
    intermediate_size: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, kv_mask=None, train: bool = False):
        from analytics_zoo_tpu.models.transformer import (
            MultiHeadAttention, _constrain_seq)

        H = self.num_heads
        a = MultiHeadAttention(H, self.hidden_size // H, dtype=self.dtype,
                               mesh=self.mesh, name="attention")(
            x, kv_mask, train)
        a = nn.Dropout(self.dropout, deterministic=not train)(a)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x + a)
        x = _constrain_seq(x, self.mesh)
        h = MoEMLP(self.num_experts, self.intermediate_size,
                   top_k=self.top_k, capacity_factor=self.capacity_factor,
                   dtype=self.dtype, mesh=self.mesh, name="moe")(x, train)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_ffn")(x + h)
        return _constrain_seq(x, self.mesh)


class MoETransformerClassifier(nn.Module):
    """Small MoE encoder classifier — the e2e surface for tests/examples
    (embeds token ids, N MoE blocks, mean-pool, linear head)."""

    vocab_size: int
    num_classes: int
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    intermediate_size: int = 128
    num_experts: int = 4
    top_k: int = 2
    dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, token_ids, train: bool = False):
        x = nn.Embed(self.vocab_size, self.hidden_size,
                     name="embed")(token_ids).astype(self.dtype)
        for i in range(self.num_layers):
            x = MoETransformerLayer(
                self.hidden_size, self.num_heads, self.intermediate_size,
                self.num_experts, top_k=self.top_k, dtype=self.dtype,
                mesh=self.mesh, name=f"layer_{i}")(x, None, train)
        pooled = jnp.mean(x.astype(jnp.float32), axis=1)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="classifier")(pooled)


# Classifier rules: MoE expert layout + Megatron attention TP.
MOE_CLASSIFIER_PARTITION_RULES = MOE_PARTITION_RULES + (
    (r"(query|key|value)/kernel", P(None, "tp")),
    (r"attn_out/kernel", P("tp", None)),
    (r".*", P()),
)
