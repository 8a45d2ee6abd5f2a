"""Pipeline parallelism over the ``pp`` mesh axis (SPMD GPipe).

No reference counterpart (SURVEY.md §2.3 item 6: the reference is a CPU
data-parallel stack); like ring attention this is a TPU-native extension
that makes the mesh's declared ``pp`` axis real.

TPU-first shape of the solution: pipelining is expressed as ONE jitted SPMD
program, not a runtime scheduler.  Stage parameters are stacked on a leading
stage dim sharded ``P("pp")``; inside ``shard_map`` each pp rank holds its
stage's weights, a ``lax.scan`` runs the GPipe tick schedule, and
activations hop rank→rank over ICI via ``lax.ppermute``.  Every rank
computes every tick (bubble ticks compute masked garbage) — the standard
static-SPMD pipeline trade: bubble fraction (S-1)/(M+S-1) for S stages and
M microbatches.  The whole schedule differentiates through scan/ppermute,
so the SAME code is forward and backward pipelining; XLA overlaps the
ppermute hop with the next tick's compute.

Three training schedules: autodiff through ``pipeline_apply`` yields
GPipe (all-forward-then-all-backward, activation residency grows with
M); ``pipeline_value_and_grad`` / ``pipeline_apply_1f1b`` run flat 1F1B
(combined forward/backward ticks, residency bounded at 2S microbatches
per rank via stage-level remat); and ``n_chunks=v > 1`` /
``pipeline_apply_interleaved`` run INTERLEAVED 1F1B (v virtual model
chunks per rank, round-robin placement, wrap-around ppermute).  The
trades are explicit: flat 1F1B idles (2S-2)/(M+2S-2) of its slots —
about twice GPipe's bubble at equal M — but its O(S) memory bound lets
M grow to amortise the bubble where GPipe's O(M) residency cannot
(``pipeline_1f1b_stats``); interleaving then cuts the flat bubble to
S+(S-2)/v flat-tick equivalents for v× the residual-ring memory and
ppermute traffic (``interleaved_1f1b_stats``).

Composes with the batch axes: batch stays sharded over dp/fsdp (each pp
rank sees its dp-local batch).  Stage-INTERNAL tensor parallelism does
NOT compose: stages execute inside shard_map, where a tp-sharded weight
is simply all-gathered per tick (at-rest memory, no compute split) — pair
pp with dp/fsdp, and use tp on the non-pipelined parts of the model.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.parallel.partition import PartitionRules

StageFn = Callable[[Any, jax.Array], jax.Array]


def _check_stacked(stacked_params, S: int) -> None:
    """Each rank consumes exactly one stage of the stacked params; a
    stack whose leading dim differs from the pp axis size would silently
    drop (or wrap) stages after sharding.  Shared by every pipelined
    entry point so validation can never drift between them."""
    shapes = [jnp.shape(leaf) for leaf in jax.tree.leaves(stacked_params)]
    bad = {s[0] if s else None for s in shapes} - {S}
    if bad:
        raise ValueError(
            f"stacked_params leading dim(s) {sorted(bad, key=str)} != pp "
            f"axis size {S}; every leaf must stack exactly one slice per "
            f"pp rank")


def sequential_apply(stage_fn: StageFn, stacked_params: Any,
                     x: jax.Array) -> jax.Array:
    """Reference semantics: apply the S stacked stages in order (what the
    pipeline must equal).  Used on meshes without a pp axis."""

    def body(a, p):
        return stage_fn(p, a), None

    out, _ = lax.scan(body, x, stacked_params)
    return out


def pipeline_apply(stage_fn: StageFn, stacked_params: Any, x: jax.Array,
                   mesh: Mesh, n_microbatches: int, *,
                   batch_axes: Sequence[str] = ("dp", "fsdp"),
                   pp_axis: str = "pp") -> jax.Array:
    """Run ``x`` through S pipelined stages; equals ``sequential_apply``.

    stage_fn: ``(one_stage_params, act) -> act`` — shape- and
    dtype-preserving, per-sample (no cross-batch mixing: microbatching
    changes what a batch is).
    stacked_params: pytree with leading dim S on every leaf (S =
    ``mesh.shape[pp_axis]``), to be sharded ``P("pp")``.
    x: global batch ``[B, ...]``; each rank splits its local batch into
    ``gcd(n_microbatches, local_batch)`` microbatches (the knob is
    perf-only — a non-dividing value degrades the bubble, never errors).
    """
    S = int(mesh.shape[pp_axis]) if pp_axis in mesh.axis_names else 1
    if S == 1:
        return sequential_apply(stage_fn, stacked_params, x)
    _check_stacked(stacked_params, S)
    M = int(n_microbatches)
    batch = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    xspec = P(batch, *([None] * (x.ndim - 1)))
    pspec = jax.tree.map(lambda _: P(pp_axis), stacked_params)

    def ranked(params, xl):
        idx = lax.axis_index(pp_axis)
        p_local = jax.tree.map(lambda a: a[0], params)  # [1,...] -> [...]
        b = xl.shape[0]
        # n_microbatches is a performance knob, never a correctness
        # constraint: when it doesn't divide the per-rank batch (e.g. the
        # Estimator's tiny init batch), fall back to the nearest divisor
        m_eff = math.gcd(M, b)
        mb = xl.reshape((m_eff, b // m_eff) + xl.shape[1:])
        ticks = m_eff + S - 1

        def tick(carry, t):
            state_in, out_buf = carry
            inject = lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, m_eff - 1), 0, keepdims=False)
            cur = jnp.where(idx == 0, inject, state_in)
            y = stage_fn(p_local, cur)
            # the last rank finished microbatch t-(S-1) this tick
            w = t - (S - 1)
            valid = (idx == S - 1) & (w >= 0)
            wc = jnp.clip(w, 0, m_eff - 1)
            slot = lax.dynamic_index_in_dim(out_buf, wc, 0, keepdims=False)
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(valid, y, slot), wc, 0)
            nxt = lax.ppermute(y, pp_axis,
                               [(i, i + 1) for i in range(S - 1)])
            return (nxt, out_buf), None

        # Scan carries must be pp-VARYING from tick 0: the loop writes
        # ppermute/axis_index-derived values into them, and shard_map's
        # vma type system rejects an invariant->varying carry (same
        # constraint ring_attention.py works around).  lax.pvary marks
        # the zeros as device-varying without computing anything.
        def vary(z):
            try:
                return lax.pcast(z, pp_axis, to="varying")
            except (AttributeError, TypeError):
                return z + (idx * 0).astype(z.dtype)
        carry = (vary(jnp.zeros_like(mb[0])), vary(jnp.zeros_like(mb)))
        (_, out_buf), _ = lax.scan(tick, carry, jnp.arange(ticks))
        # outputs live on the last rank only; psum broadcasts them so the
        # result is pp-invariant (loss/metrics compute identically on all
        # ranks — same contract as data parallelism)
        out = lax.psum(jnp.where(idx == S - 1, out_buf, 0.0), pp_axis)
        return out.reshape(xl.shape).astype(xl.dtype)

    return jax.shard_map(ranked, mesh=mesh, in_specs=(pspec, xspec),
                         out_specs=xspec)(stacked_params, x)


def interleaved_1f1b_stats(n_stages: int, n_microbatches: int,
                           n_chunks: int) -> dict:
    """Static schedule facts for ``pipeline_value_and_grad(...,
    n_chunks=v)`` — the interleaved (virtual-stage) 1F1B schedule.

    Each of the S pp ranks holds ``v`` model chunks placed round-robin
    (logical stage ``j = k*S + r`` is chunk ``k`` of rank ``r``), so a
    microbatch crosses every rank ``v`` times.  One combined tick does
    one forward AND one backward unit per rank, but a unit is now a
    CHUNK — 1/v of a rank's model slice — so a tick costs 1/v of a flat
    tick.  Ramp-up/down shrinks accordingly: measured in flat-tick
    equivalents the schedule spends ``M + S + (S-2)/v`` versus flat
    1F1B's ``M + 2S - 2`` — strictly better for S >= 3, v >= 2, and
    approaching HALF the flat bubble as v grows.  The price is v×: the
    residual ring holds ``2*v*S`` chunk inputs per rank (vs 2S), and
    activations hop ranks v times per microbatch (wrap-around ppermute
    traffic) instead of once — the standard interleaved-schedule trade
    (bubble ↓, memory + ICI traffic ↑).  Residency stays M-independent,
    which is what lets M grow to amortise what bubble remains."""
    S, M, v = int(n_stages), int(n_microbatches), int(n_chunks)
    L = v * S
    g_last, q_last = (M - 1) // S, (M - 1) % S
    ticks = g_last * L + q_last + 2 * L - 1        # chunk-sized ticks
    flat = pipeline_1f1b_stats(S, M)
    return {
        "ticks": ticks,
        "flat_tick_equivalents": ticks / v,
        "flat_1f1b_ticks": flat["ticks"],
        "bubble_fraction": (ticks - v * M) / ticks,
        "flat_bubble_fraction": flat["bubble_fraction"],
        "residual_slots": 2 * L,                   # chunk inputs per rank
        "flat_residual_slots": flat["residual_slots"],
    }


def pipeline_1f1b_stats(n_stages: int, n_microbatches: int) -> dict:
    """Static schedule facts for ``pipeline_value_and_grad`` (asserted by
    tests, cited in docs).  The lockstep combined-tick schedule runs
    ``M + 2S - 2`` ticks (each tick does one forward AND one backward
    unit per rank) and keeps at most ``2S`` microbatch activations
    resident per rank — versus the GPipe-autodiff path, whose transposed
    scan stores all ``M``.  Honest accounting: a rank does useful work in
    M of its M+2S-2 forward slots and M of its backward slots, so the
    idle fraction is ``(2S-2)/(M+2S-2)`` — about TWICE GPipe's
    ``(S-1)/(M+S-1)`` at the same M.  This schedule buys the O(S) memory
    bound by paying bubble, and the memory bound is exactly what lets M
    grow to amortise it (``gpipe_bubble_fraction`` included for the
    comparison)."""
    S, M = int(n_stages), int(n_microbatches)
    return {
        "ticks": M + 2 * S - 2,
        "residual_slots": 2 * S,
        "gpipe_resident_microbatches": M,
        "bubble_fraction": (2 * S - 2) / (M + 2 * S - 2),
        "gpipe_bubble_fraction": (S - 1) / (M + S - 1),
    }


def _make_vary(pp_axis, batch):
    """Device-variance marker shared by the 1F1B paths.  Two reasons to
    mark values varying: (1) scan carries pick up pp-varying (ppermute/
    axis_index) and batch-varying (dp-sharded activations) values, and
    an invariant->varying carry fails shard_map's vma typecheck; (2)
    params must be batch-VARYING before jax.vjp, else autodiff
    auto-psums the param cotangent across dp on EVERY tick (one
    all-reduce per tick, and n_dp-scaled grads after a later mean)."""

    def vary(z):
        for ax in (pp_axis,) + tuple(batch or ()):
            try:
                z = lax.pcast(z, ax, to="varying")
            except (AttributeError, TypeError):
                # no lax.pcast on this JAX: force variance on THIS axis
                # arithmetically and keep looping — falling out early
                # would leave params batch-invariant (see (2) above)
                z = z + (lax.axis_index(ax) * 0).astype(z.dtype)
            except ValueError:
                pass        # already varying on ax
        return z

    return vary


def _f1b_ticks(stage_fn, p_local, mb, aux, S, m_eff, idx, pp_axis, vary,
               head):
    """The shared flat-1F1B tick engine (both ``pipeline_value_and_grad``
    and ``pipeline_apply_1f1b``'s backward run it): rank r forwards
    microbatch m at tick m+r and backwards it at tick m+2S-2-r, with
    the last rank's backward fused into its forward tick; activations
    hop r->r+1 and activation-grads r->r-1 via ppermute; backward units
    recompute their stage forward from the saved stage INPUT
    (stage-level remat, residual ring of 2S slots).

    ``aux``: per-microbatch rows consumed by ``head(y, aux_row) ->
    (loss_scalar, gy_seed)`` — the loss head for value_and_grad, or a
    passthrough of the stored output cotangent for the custom-vjp
    backward.  Evaluated at the last rank's fwd microbatch (where
    m_b == m_f, so the seed aligns with the backward unit).

    Returns ``(gacc, dxbuf, lossbuf)``: raw per-rank sums over this
    rank's microbatches — ALL scaling (1/M, dp mean vs sum) belongs to
    the caller."""
    R = 2 * S
    ticks = m_eff + 2 * S - 2

    def tick(carry, t):
        act_in, gract_in, resbuf, gacc, dxbuf, lossbuf = carry
        m_f = t - idx                       # fwd microbatch index
        m_b = t - (2 * S - 2 - idx)         # bwd microbatch index
        valid_f = (m_f >= 0) & (m_f < m_eff)
        valid_b = (m_b >= 0) & (m_b < m_eff)
        mfc = jnp.clip(m_f, 0, m_eff - 1)
        mbc = jnp.clip(m_b, 0, m_eff - 1)
        # ---- forward unit ----
        inject = lax.dynamic_index_in_dim(mb, mfc, 0, keepdims=False)
        cur = jnp.where(idx == 0, inject, act_in)
        y = stage_fn(p_local, cur)
        # save this stage's INPUT for the recompute-backward
        slot_f = mfc % R
        old = lax.dynamic_index_in_dim(resbuf, slot_f, 0, keepdims=False)
        resbuf = lax.dynamic_update_index_in_dim(
            resbuf, jnp.where(valid_f, cur, old), slot_f, 0)
        arow = lax.dynamic_index_in_dim(aux, mfc, 0, keepdims=False)
        loss_m, gy = head(y, arow)
        # ---- backward unit (stage-level remat) ----
        a_saved = lax.dynamic_index_in_dim(resbuf, mbc % R, 0,
                                           keepdims=False)
        g_use = jnp.where(idx == S - 1, gy.astype(gract_in.dtype),
                          gract_in)
        _, vjp = jax.vjp(stage_fn, p_local, a_saved)
        dp, da = vjp(g_use.astype(y.dtype))
        gacc = jax.tree.map(
            lambda g, d: g + jnp.where(valid_b, d, 0.0).astype(g.dtype),
            gacc, dp)
        # rank 0's da is the input cotangent for microbatch m_b
        dslot = lax.dynamic_index_in_dim(dxbuf, mbc, 0, keepdims=False)
        dxbuf = lax.dynamic_update_index_in_dim(
            dxbuf, jnp.where((idx == 0) & valid_b, da, dslot), mbc, 0)
        lslot = lax.dynamic_index_in_dim(lossbuf, mfc, 0, keepdims=False)
        lossbuf = lax.dynamic_update_index_in_dim(
            lossbuf, jnp.where((idx == S - 1) & valid_f, loss_m, lslot),
            mfc, 0)
        # ---- hops: activations r->r+1, activation-grads r->r-1 ----
        act_out = lax.ppermute(y, pp_axis,
                               [(i, i + 1) for i in range(S - 1)])
        gract_out = lax.ppermute(da, pp_axis,
                                 [(i + 1, i) for i in range(S - 1)])
        return (act_out, gract_out, resbuf, gacc, dxbuf, lossbuf), None

    z_mb = jnp.zeros_like(mb[0])
    carry = (vary(z_mb), vary(z_mb),
             vary(jnp.zeros((R,) + z_mb.shape, z_mb.dtype)),
             jax.tree.map(lambda p: vary(jnp.zeros_like(p)), p_local),
             vary(jnp.zeros_like(mb)),
             vary(jnp.zeros((m_eff,), jnp.float32)))
    (_, _, _, gacc, dxbuf, lossbuf), _ = lax.scan(
        tick, carry, jnp.arange(ticks))
    return gacc, dxbuf, lossbuf


def _f1b_ticks_interleaved(stage_fn, p_chunks, mb, aux, S, v, m_eff, idx,
                           pp_axis, vary, head):
    """The interleaved (virtual-stage) 1F1B tick engine.  Rank ``r``
    holds chunks ``k = 0..v-1`` (stacked leading dim of ``p_chunks``);
    logical stage ``j = k*S + r`` — round-robin placement, so the
    rank→rank hop is always one step and wraps S-1 → 0 between chunks.

    Schedule: microbatch ``m = g*S + q`` forwards through logical stage
    ``j`` at tick ``u_f = g*v*S + k*S + q + r`` and backwards at
    ``u_b = u_f + 2*(L-1-j)`` (``L = v*S``); the last logical stage's
    backward fuses with its forward tick.  Both maps are bijections per
    (rank, tick) — ``u_f - r`` decomposes uniquely base-(S, v, ·) and
    ``u_b + r - 2L + 2 = (g*v - k)*S + q`` uniquely too — so every rank
    runs exactly one fwd and one bwd CHUNK unit per tick.  With v = 1
    this is precisely the flat schedule of ``_f1b_ticks``; kept separate
    because the flat engine's non-wrapping ppermute and 2S ring are the
    proven baseline the tests compare against.

    Backward units recompute their chunk forward from the saved chunk
    INPUT (chunk-level remat) held in a ring of ``2L`` slots — slot
    ``(u_f - r) mod 2L`` is collision-free because a saved input lives
    at most ``2(L-1)`` fwd-issues.  Returns raw per-rank ``(gacc [v,...],
    dxbuf, lossbuf)`` sums; all scaling belongs to the caller."""
    L = v * S
    R = 2 * L
    g_last, q_last = (m_eff - 1) // S, (m_eff - 1) % S
    ticks = g_last * L + q_last + 2 * L - 1

    def tick(carry, t):
        act_in, gract_in, resbuf, gacc, dxbuf, lossbuf = carry
        # ---- forward unit (shared bijection: _fwd_wave) ----
        w_f, k_f, m_fc, valid_f = _fwd_wave(t, idx, S, v, m_eff)
        inject = lax.dynamic_index_in_dim(mb, m_fc, 0, keepdims=False)
        cur = jnp.where((idx == 0) & (k_f == 0), inject, act_in)
        y = stage_fn(_chunk_at(p_chunks, k_f), cur)
        slot_f = jnp.mod(w_f, R)
        old = lax.dynamic_index_in_dim(resbuf, slot_f, 0, keepdims=False)
        resbuf = lax.dynamic_update_index_in_dim(
            resbuf, jnp.where(valid_f, cur, old), slot_f, 0)
        arow = lax.dynamic_index_in_dim(aux, m_fc, 0, keepdims=False)
        loss_m, gy = head(y, arow)
        # ---- backward unit: w = t + r - 2L + 2 = (g*v - k)*S + q ----
        w_b = t + idx - 2 * L + 2
        q_b = jnp.mod(w_b, S)
        h = (w_b - q_b) // S
        k_b = jnp.mod(-h, v)
        m_b = ((h + k_b) // v) * S + q_b
        valid_b = (m_b >= 0) & (m_b < m_eff)
        m_bc = jnp.clip(m_b, 0, m_eff - 1)
        # where this bwd unit's forward saved its input:
        # u_f = t - 2*(L-1-j_b), j_b = k_b*S + idx  =>  w = u_f - idx
        w_fb = t + idx + 2 * k_b * S - 2 * L + 2
        a_saved = lax.dynamic_index_in_dim(
            resbuf, jnp.mod(w_fb, R), 0, keepdims=False)
        is_last_b = (idx == S - 1) & (k_b == v - 1)   # fused with fwd tick
        g_use = jnp.where(is_last_b, gy.astype(gract_in.dtype), gract_in)
        _, vjp = jax.vjp(stage_fn, _chunk_at(p_chunks, k_b), a_saved)
        dp, da = vjp(g_use.astype(y.dtype))
        gacc = jax.tree.map(
            lambda g, d: lax.dynamic_update_index_in_dim(
                g,
                lax.dynamic_index_in_dim(g, k_b, 0, keepdims=False)
                + jnp.where(valid_b, d, 0.0).astype(g.dtype),
                k_b, 0),
            gacc, dp)
        dslot = lax.dynamic_index_in_dim(dxbuf, m_bc, 0, keepdims=False)
        dxbuf = lax.dynamic_update_index_in_dim(
            dxbuf,
            jnp.where((idx == 0) & (k_b == 0) & valid_b, da, dslot),
            m_bc, 0)
        lslot = lax.dynamic_index_in_dim(lossbuf, m_fc, 0, keepdims=False)
        lossbuf = lax.dynamic_update_index_in_dim(
            lossbuf,
            jnp.where((idx == S - 1) & (k_f == v - 1) & valid_f,
                      loss_m, lslot),
            m_fc, 0)
        # ---- hops: WRAP-AROUND — rank S-1's chunk-k output is rank 0's
        # chunk-(k+1) input one tick later (and symmetrically backward)
        act_out = lax.ppermute(y, pp_axis,
                               [(i, (i + 1) % S) for i in range(S)])
        gract_out = lax.ppermute(da, pp_axis,
                                 [((i + 1) % S, i) for i in range(S)])
        return (act_out, gract_out, resbuf, gacc, dxbuf, lossbuf), None

    z_mb = jnp.zeros_like(mb[0])
    carry = (vary(z_mb), vary(z_mb),
             vary(jnp.zeros((R,) + z_mb.shape, z_mb.dtype)),
             jax.tree.map(lambda p: vary(jnp.zeros_like(p)), p_chunks),
             vary(jnp.zeros_like(mb)),
             vary(jnp.zeros((m_eff,), jnp.float32)))
    (_, _, _, gacc, dxbuf, lossbuf), _ = lax.scan(
        tick, carry, jnp.arange(ticks))
    return gacc, dxbuf, lossbuf


def pipeline_value_and_grad(stage_fn: StageFn, loss_fn, stacked_params,
                            x: jax.Array, labels, mesh: Mesh,
                            n_microbatches: int, *,
                            batch_axes: Sequence[str] = ("dp", "fsdp"),
                            pp_axis: str = "pp", n_chunks: int = 1):
    """One interleaved-1F1B training tick-schedule: loss AND gradients of
    ``mean(loss_fn(stage_S(...stage_1(x)), labels))`` in a single
    shard_map scan.

    Why not just ``jax.grad(pipeline_apply)``?  Autodiff transposes the
    forward scan into an all-forward-then-all-backward schedule (GPipe):
    every one of the M microbatches' stage activations stays resident
    until its backward runs, so peak memory grows with M — and M is
    exactly the knob one raises to shrink the bubble.  1F1B starts
    microbatch m's backward as soon as its last-stage forward finishes,
    bounding resident activations at 2S per rank regardless of M
    (``pipeline_1f1b_stats``).  The backward unit recomputes its stage
    forward from the saved stage INPUT (stage-level remat — the
    standard trade), so each (microbatch, stage) costs fwd + fwd + vjp
    instead of fwd + vjp.

    Schedule (flat/non-interleaved 1F1B, combined F+B ticks): rank r
    forwards microbatch ``m`` at tick ``m + r`` and backwards it at tick
    ``m + 2S - 2 - r``; the last rank's backward fuses with its forward
    (same tick), activations hop r->r+1 and activation-grads hop r->r-1
    via ``lax.ppermute`` each tick.

    Args mirror ``pipeline_apply`` plus ``labels`` ([B, ...], same
    leading batch dim as x) and ``loss_fn(y_mb, label_mb) -> scalar``
    (MEAN over the microbatch).  Returns ``(loss, grads, dx)`` where
    ``grads`` matches ``stacked_params`` (sharded P(pp) like the
    params) and ``dx`` is the loss gradient w.r.t. ``x`` (feeds
    embedding/pre-trunk backward when composed manually).

    ``n_chunks=v > 1`` selects the INTERLEAVED schedule: stacked_params
    must carry ``v * S`` stages (logical order on the leading dim);
    stage ``j`` is placed on rank ``j % S`` (round-robin), cutting the
    bubble from ``2S - 2`` to ``S + (S-2)/v`` flat-tick equivalents at
    the cost of a ``2vS``-slot residual ring and v× the ppermute
    traffic (``interleaved_1f1b_stats``).  Math is identical — same
    oracle, same tests.
    """
    S = int(mesh.shape[pp_axis]) if pp_axis in mesh.axis_names else 1
    if S == 1:
        def seq_loss(p, xx):
            return loss_fn(sequential_apply(stage_fn, p, xx), labels)

        loss, (gp, gx) = jax.value_and_grad(seq_loss, argnums=(0, 1))(
            stacked_params, x)
        return loss, gp, gx
    v = int(n_chunks)
    if v < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    _check_stacked(stacked_params, v * S)
    M = int(n_microbatches)
    batch = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    xspec = P(batch, *([None] * (x.ndim - 1)))
    lspec = P(batch, *([None] * (jnp.ndim(labels) - 1)))
    if v > 1:
        return _value_and_grad_interleaved(
            stage_fn, loss_fn, stacked_params, x, labels, mesh, M, S, v,
            batch, xspec, lspec, pp_axis)
    pspec = jax.tree.map(lambda _: P(pp_axis), stacked_params)

    def ranked(params, xl, ll):
        idx = lax.axis_index(pp_axis)
        b = xl.shape[0]
        m_eff = math.gcd(M, b)
        mb = xl.reshape((m_eff, b // m_eff) + xl.shape[1:])
        lb = ll.reshape((m_eff, b // m_eff) + ll.shape[1:])
        vary = _make_vary(pp_axis, batch)
        p_local = jax.tree.map(lambda a: vary(a[0]), params)

        def head(y, lbl):
            """Last rank: per-microbatch loss + dL/dy."""
            return jax.value_and_grad(lambda yy: loss_fn(yy, lbl))(y)

        gacc, dxbuf, lossbuf = _f1b_ticks(
            stage_fn, p_local, mb, lb, S, m_eff, idx, pp_axis, vary, head)
        # per-microbatch means -> global mean; grads scale by 1/M
        n_b = 1
        for ax in (batch or ()):
            n_b *= int(mesh.shape[ax])
        loss = lax.psum(jnp.where(idx == S - 1, jnp.sum(lossbuf), 0.0),
                        pp_axis) / m_eff
        # d(global mean)/dx on this rank = (1/n_dp) d(local mean)/dx
        dx = lax.psum(jnp.where(idx == 0, dxbuf, 0.0),
                      pp_axis).reshape(xl.shape) / (m_eff * n_b)
        grads = jax.tree.map(lambda g: g / m_eff, gacc)
        if batch:
            # each data-parallel rank saw its own local batch: the global
            # mean loss/grad is the mean across them (dx stays sharded —
            # it IS per-rank)
            loss = lax.pmean(loss, batch)
            grads = jax.tree.map(lambda g: lax.pmean(g, batch), grads)
        grads = jax.tree.map(lambda g: g[None], grads)
        return loss, grads, dx.astype(xl.dtype)

    loss, grads, dx = jax.shard_map(
        ranked, mesh=mesh, in_specs=(pspec, xspec, lspec),
        out_specs=(P(), pspec, xspec))(stacked_params, x, labels)
    return loss, grads, dx


def _chunk_params(stacked_params, v: int, S: int):
    """[L, ...] logical-order stack -> [v, S, ...] so ``P(None, pp)``
    realises round-robin placement (leaf[k, r] = logical stage k*S+r —
    C-order reshape is exactly that map)."""
    return jax.tree.map(
        lambda a: a.reshape((v, S) + a.shape[1:]), stacked_params)


def _chunk_at(p, k):
    """Select chunk ``k`` from a [v, ...]-stacked local param tree."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, k, 0, keepdims=False), p)


def _fwd_wave(t, idx, S, v, m_eff):
    """The interleaved (rank, tick) -> forward-unit bijection, shared by
    the combined and forward-only engines: ``w = t - r`` decomposes
    base-(S, v, ·) into (q, k, g); microbatch m = g*S + q.  Returns
    ``(w_f, k_f, m_fc, valid_f)`` with m clipped for safe indexing."""
    L = v * S
    w_f = t - idx
    q_f = jnp.mod(w_f, S)
    k_f = jnp.mod((w_f - q_f) // S, v)
    m_f = (w_f // L) * S + q_f
    valid_f = (w_f >= 0) & (m_f < m_eff)
    return w_f, k_f, jnp.clip(m_f, 0, m_eff - 1), valid_f


def _fwd_ticks_interleaved(stage_fn, p_chunks, mb, S, v, m_eff, idx,
                           pp_axis, vary):
    """Forward-only interleaved schedule: ``(v*M + S - 1)/v`` flat-tick
    equivalents versus GPipe's ``M + S - 1`` — the ramp shrinks v× for
    inference too.  Same (rank, tick) -> (chunk, microbatch) bijection
    as the combined engine."""
    L = v * S
    g_last, q_last = (m_eff - 1) // S, (m_eff - 1) % S
    ticks = g_last * L + (v - 1) * S + q_last + S

    def tick(carry, t):
        act_in, out_buf = carry
        w_f, k_f, m_fc, valid_f = _fwd_wave(t, idx, S, v, m_eff)
        inject = lax.dynamic_index_in_dim(mb, m_fc, 0, keepdims=False)
        cur = jnp.where((idx == 0) & (k_f == 0), inject, act_in)
        y = stage_fn(_chunk_at(p_chunks, k_f), cur)
        write = (idx == S - 1) & (k_f == v - 1) & valid_f
        slot = lax.dynamic_index_in_dim(out_buf, m_fc, 0, keepdims=False)
        out_buf = lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(write, y, slot), m_fc, 0)
        act_out = lax.ppermute(y, pp_axis,
                               [(i, (i + 1) % S) for i in range(S)])
        return (act_out, out_buf), None

    carry = (vary(jnp.zeros_like(mb[0])), vary(jnp.zeros_like(mb)))
    (_, out_buf), _ = lax.scan(tick, carry, jnp.arange(ticks))
    return out_buf


def pipeline_apply_interleaved(stage_fn: StageFn, stacked_params,
                               x: jax.Array, mesh: Mesh,
                               n_microbatches: int, n_chunks: int, *,
                               batch_axes: Sequence[str] = ("dp", "fsdp"),
                               pp_axis: str = "pp",
                               chunked: bool = False) -> jax.Array:
    """Interleaved-schedule forward with an O(S)-residency interleaved
    BACKWARD, composable with ordinary autodiff (the ``GPipe`` module's
    ``schedule="interleaved"`` path — same contract as
    ``pipeline_apply_1f1b``, smaller bubble on both passes).

    ``stacked_params``: [L, ...] logical-order stages (L = n_chunks *
    pp size), or already [v, S, ...]-chunked when ``chunked=True`` (the
    module stores them chunked so the round-robin placement is the
    at-rest sharding — no per-step reshard)."""
    S = int(mesh.shape[pp_axis]) if pp_axis in mesh.axis_names else 1
    v = int(n_chunks)
    if S == 1:
        if chunked:
            stacked_params = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), stacked_params)
        return sequential_apply(stage_fn, stacked_params, x)
    M = int(n_microbatches)
    batch = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    if chunked:
        bad = {jnp.shape(leaf)[:2] for leaf in
               jax.tree.leaves(stacked_params)} - {(v, S)}
        if bad:
            raise ValueError(
                f"chunked=True expects [n_chunks={v}, pp={S}, ...] "
                f"leading dims on every leaf, got {sorted(bad)}; pass "
                f"the flat [L, ...] logical-order stack with "
                f"chunked=False to have it chunked here")
        p_chunked = stacked_params
    else:
        _check_stacked(stacked_params, v * S)
        p_chunked = _chunk_params(stacked_params, v, S)
    pspec = jax.tree.map(lambda _: P(None, pp_axis), p_chunked)

    @jax.custom_vjp
    def apply(params, xx):
        xspec = P(batch, *([None] * (xx.ndim - 1)))

        def ranked(p, xl):
            idx = lax.axis_index(pp_axis)
            b = xl.shape[0]
            m_eff = math.gcd(M, b)
            mb = xl.reshape((m_eff, b // m_eff) + xl.shape[1:])
            vary = _make_vary(pp_axis, batch)
            p_chunks = jax.tree.map(lambda a: vary(a[:, 0]), p)
            out_buf = _fwd_ticks_interleaved(
                stage_fn, p_chunks, mb, S, v, m_eff, idx, pp_axis, vary)
            out = lax.psum(jnp.where(idx == S - 1, out_buf, 0.0), pp_axis)
            return out.reshape(xl.shape).astype(xl.dtype)

        return jax.shard_map(ranked, mesh=mesh, in_specs=(pspec, xspec),
                             out_specs=xspec)(params, xx)

    def fwd(params, xx):
        return apply(params, xx), (params, xx)

    def bwd(res, gy):
        params, xx = res
        xspec = P(batch, *([None] * (xx.ndim - 1)))

        def ranked(p, xl, gl):
            idx = lax.axis_index(pp_axis)
            b = xl.shape[0]
            m_eff = math.gcd(M, b)
            mb = xl.reshape((m_eff, b // m_eff) + xl.shape[1:])
            gb = gl.reshape((m_eff, b // m_eff) + gl.shape[1:])
            vary = _make_vary(pp_axis, batch)
            p_chunks = jax.tree.map(lambda a: vary(a[:, 0]), p)

            def head(y, g_seed):
                # bwd seeds from the STORED output cotangent (no loss)
                return jnp.float32(0.0), g_seed

            gacc, dxbuf, _ = _f1b_ticks_interleaved(
                stage_fn, p_chunks, mb, gb, S, v, m_eff, idx, pp_axis,
                vary, head)
            # gy carries the outer scaling; dparams is the raw SUM over
            # microbatches and dp ranks (params are dp-replicated)
            if batch:
                gacc = jax.tree.map(lambda g: lax.psum(g, batch), gacc)
            grads = jax.tree.map(lambda g: g[:, None], gacc)
            dx = lax.psum(jnp.where(idx == 0, dxbuf, 0.0),
                          pp_axis).reshape(xl.shape)
            return grads, dx.astype(xl.dtype)

        # cotangents match apply's inputs: the CHUNKED tree (autodiff of
        # the outer _chunk_params reshape maps them back to [L, ...])
        return jax.shard_map(
            ranked, mesh=mesh, in_specs=(pspec, xspec, xspec),
            out_specs=(pspec, xspec))(params, xx, gy)

    apply.defvjp(fwd, bwd)
    return apply(p_chunked, x)


def _value_and_grad_interleaved(stage_fn, loss_fn, stacked_params, x,
                                labels, mesh, M, S, v, batch, xspec,
                                lspec, pp_axis):
    """Interleaved-schedule body of ``pipeline_value_and_grad``: params
    [L, ...] reshape to [v, S, ...] so ``P(None, pp)`` realises the
    round-robin placement (leaf[k, r] = logical stage k*S + r); each
    rank sees its own [v, ...] chunk stack inside shard_map.  Scaling
    contract is identical to the flat path."""
    p_resh = _chunk_params(stacked_params, v, S)
    pspec = jax.tree.map(lambda _: P(None, pp_axis), p_resh)

    def ranked(params, xl, ll):
        idx = lax.axis_index(pp_axis)
        b = xl.shape[0]
        m_eff = math.gcd(M, b)
        mb = xl.reshape((m_eff, b // m_eff) + xl.shape[1:])
        lb = ll.reshape((m_eff, b // m_eff) + ll.shape[1:])
        vary = _make_vary(pp_axis, batch)
        p_chunks = jax.tree.map(lambda a: vary(a[:, 0]), params)

        def head(y, lbl):
            return jax.value_and_grad(lambda yy: loss_fn(yy, lbl))(y)

        gacc, dxbuf, lossbuf = _f1b_ticks_interleaved(
            stage_fn, p_chunks, mb, lb, S, v, m_eff, idx, pp_axis, vary,
            head)
        n_b = 1
        for ax in (batch or ()):
            n_b *= int(mesh.shape[ax])
        loss = lax.psum(jnp.where(idx == S - 1, jnp.sum(lossbuf), 0.0),
                        pp_axis) / m_eff
        dx = lax.psum(jnp.where(idx == 0, dxbuf, 0.0),
                      pp_axis).reshape(xl.shape) / (m_eff * n_b)
        grads = jax.tree.map(lambda g: g / m_eff, gacc)
        if batch:
            loss = lax.pmean(loss, batch)
            grads = jax.tree.map(lambda g: lax.pmean(g, batch), grads)
        grads = jax.tree.map(lambda g: g[:, None], grads)
        return loss, grads, dx.astype(xl.dtype)

    loss, grads, dx = jax.shard_map(
        ranked, mesh=mesh, in_specs=(pspec, xspec, lspec),
        out_specs=(P(), pspec, xspec))(p_resh, x, labels)
    grads = jax.tree.map(lambda g, a: g.reshape(a.shape), grads,
                         stacked_params)
    return loss, grads, dx


def pipeline_apply_1f1b(stage_fn: StageFn, stacked_params, x: jax.Array,
                        mesh: Mesh, n_microbatches: int, *,
                        batch_axes: Sequence[str] = ("dp", "fsdp"),
                        pp_axis: str = "pp") -> jax.Array:
    """``pipeline_apply`` with an O(S)-residency BACKWARD, composable
    with ordinary autodiff (``jax.grad`` through models that embed the
    pipelined trunk, e.g. the Estimator's train step).

    custom_vjp shape: the forward is the plain forward pipeline and
    saves ONLY ``(stacked_params, x)`` across the autodiff boundary —
    no per-microbatch activations.  The backward replays the forward
    interleaved with backward units (the ``pipeline_value_and_grad``
    tick schedule, seeded by the incoming output cotangent instead of a
    loss head), so resident activations stay bounded at 2S microbatches
    per rank while autodiff through ``pipeline_apply`` would hold all
    M.  Compute cost: one extra forward per (microbatch, stage) versus
    the stored-activation path — the remat trade, paid where M is large
    precisely because memory no longer scales with it."""
    S = int(mesh.shape[pp_axis]) if pp_axis in mesh.axis_names else 1
    if S == 1:
        return sequential_apply(stage_fn, stacked_params, x)
    M = int(n_microbatches)
    batch = tuple(a for a in batch_axes if a in mesh.axis_names) or None

    @jax.custom_vjp
    def apply(params, xx):
        return pipeline_apply(stage_fn, params, xx, mesh, M,
                              batch_axes=batch_axes, pp_axis=pp_axis)

    def fwd(params, xx):
        return apply(params, xx), (params, xx)

    def bwd(res, gy):
        params, xx = res
        xspec = P(batch, *([None] * (xx.ndim - 1)))
        pspec = jax.tree.map(lambda _: P(pp_axis), params)

        def ranked(p_stk, xl, gl):
            idx = lax.axis_index(pp_axis)
            b = xl.shape[0]
            m_eff = math.gcd(M, b)
            mb = xl.reshape((m_eff, b // m_eff) + xl.shape[1:])
            gb = gl.reshape((m_eff, b // m_eff) + gl.shape[1:])
            vary = _make_vary(pp_axis, batch)
            p_local = jax.tree.map(lambda a: vary(a[0]), p_stk)

            def head(y, g_seed):
                # the last rank seeds its backward from the STORED output
                # cotangent of the microbatch it just forwarded (m_b ==
                # m_f there); no loss is computed in the bwd pass
                return jnp.float32(0.0), g_seed

            gacc, dxbuf, _ = _f1b_ticks(
                stage_fn, p_local, mb, gb, S, m_eff, idx, pp_axis, vary,
                head)
            # gy already carries the outer scaling (e.g. the loss mean):
            # dparams is the raw SUM of contributions — across this
            # rank's microbatches, and across dp ranks for the
            # dp-replicated params
            if batch:
                gacc = jax.tree.map(lambda g: lax.psum(g, batch), gacc)
            grads = jax.tree.map(lambda g: g[None], gacc)
            dx = lax.psum(jnp.where(idx == 0, dxbuf, 0.0),
                          pp_axis).reshape(xl.shape)
            return grads, dx.astype(xl.dtype)

        return jax.shard_map(
            ranked, mesh=mesh, in_specs=(pspec, xspec, xspec),
            out_specs=(pspec, xspec))(params, xx, gy)

    apply.defvjp(fwd, bwd)
    return apply(stacked_params, x)


def pp_stage_rules(inner: PartitionRules = (), *,
                   n_chunks: int = 1) -> PartitionRules:
    """Partition rules for GPipe's stacked stage params: prepend the stage
    dim ``"pp"`` to each stage-internal rule, then shard everything else's
    stage dim.  ``inner`` patterns should be stage-scoped (they are matched
    against paths under ``stages/``).  ``n_chunks > 1`` matches the
    interleaved layout ([v, S, ...]-chunked leaves): the pp shard moves to
    dim 1 so each rank holds its round-robin chunks at rest."""
    if n_chunks > 1:
        out = [(pat, P(None, "pp", *tuple(spec))) for (pat, spec) in inner]
        out.append((r"stages/", P(None, "pp")))
        return tuple(out)
    out = [(pat, P("pp", *tuple(spec))) for (pat, spec) in inner]
    out.append((r"stages/", P("pp")))
    return tuple(out)


class GPipe(nn.Module):
    """Flax wrapper: S copies of a stage module run as a pipeline.

    ``stage`` is a template module whose ``__call__(x)`` is shape- and
    dtype-preserving and per-sample (Dense/LayerNorm/attention fine;
    BatchNorm or dropout belong outside the pipelined trunk — stages run
    without rng/mutable plumbing).  Params are created stacked ``[S, ...]``
    (path prefix ``stages/``) so ``pp_stage_rules`` shards them; on meshes
    without pp > 1 the stages run sequentially — same math, one device.
    """

    stage: nn.Module
    n_stages: int
    n_microbatches: int = 4
    mesh: Optional[Mesh] = None
    # "gpipe": autodiff through the forward scan (activation residency
    # grows with n_microbatches); "1f1b": custom-vjp interleaved
    # backward, residency bounded at 2S microbatches per rank at one
    # extra recompute-forward per (microbatch, stage); "interleaved":
    # 1f1b with n_stages/pp virtual chunks per rank (round-robin
    # placement, bubble S+(S-2)/v vs 2S-2 — interleaved_1f1b_stats)
    schedule: str = "gpipe"

    def _n_chunks(self) -> int:
        """Chunks per rank for the interleaved schedule: pipelined when
        the pp axis divides n_stages (v = n_stages / S), sequential
        otherwise (same fallback contract as the other schedules)."""
        S = self.mesh.shape.get("pp", 1) if self.mesh is not None else 1
        if self.schedule == "interleaved" and S > 1 \
                and self.n_stages % S == 0 and self.n_stages > S:
            return self.n_stages // S
        return 1

    @nn.compact
    def __call__(self, x):
        if self.schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"schedule must be 'gpipe', '1f1b' or 'interleaved', "
                f"got {self.schedule!r}")
        template = self.stage.clone(parent=None)
        v = self._n_chunks()

        def init_stacked(rng) -> Any:
            keys = jax.random.split(rng, self.n_stages)
            probe = x[:1]
            st = jax.vmap(
                lambda k: template.init(k, probe)["params"])(keys)
            if v > 1:       # chunked-at-rest: round-robin placement IS
                #             the sharding (pp_stage_rules(n_chunks=v))
                st = jax.tree.map(
                    lambda a: a.reshape(
                        (v, self.n_stages // v) + a.shape[1:]), st)
            return st

        params = self.param("stages", init_stacked)

        def fn(p, a):
            return template.apply({"params": p}, a)

        if v > 1:
            return pipeline_apply_interleaved(
                fn, params, x, self.mesh, self.n_microbatches, v,
                chunked=True)
        if self.mesh is not None and \
                self.mesh.shape.get("pp", 1) == self.n_stages and \
                self.n_stages > 1:
            # interleaved with v == 1 chunk per rank IS flat 1f1b
            run = (pipeline_apply_1f1b
                   if self.schedule in ("1f1b", "interleaved")
                   else pipeline_apply)
            return run(fn, params, x, self.mesh, self.n_microbatches)
        return sequential_apply(fn, params, x)
