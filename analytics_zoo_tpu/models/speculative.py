"""Speculative decoding: a small draft model proposes k tokens, the
target model verifies all of them in ONE cached forward.

Beyond-parity extension (the reference has no generative serving at
all).  Why it fits the TPU: sequential decode is latency-bound — each
token is a tiny matmul plus a host round-trip — while the verify pass
is a [B, k+1]-token forward that actually feeds the MXU, and it cuts
host round-trips per emitted token by the acceptance rate.

Greedy contract: the emitted sequence is EXACTLY what greedy decoding
of the target model alone would produce (the classic speculative
guarantee specialised to argmax — a draft token is accepted iff it
equals the target's argmax given the accepted prefix, so every emitted
token is the target's argmax; tested against models.lm.generate).

Mechanics per round, per row (pointer ``ptr`` = number of durable cache
entries, starting at prompt_len - 1):

  draft   : k greedy cached steps from ``last`` -> proposals d_0..d_{k-1}
  verify  : target ``verify_step`` on [last, d_0..d_{k-1}] at positions
            ptr..ptr+k (k+1 logits in one forward)
  accept  : a = longest prefix with argmax_j == d_j; emit argmaxes
            t_0..t_a (a accepted tokens + 1 free target token — the
            correction when a < k, the bonus when a == k)
  advance : both pointers += a+1.  Cache entries written past the new
            pointer are DEAD: the attention mask never reaches them and
            the next round overwrites them — rejection costs no
            bookkeeping (models/lm.py decode_k).

Rows advance at different rates (per-row pointers, as in the continuous
engine); finished rows re-verify their frozen ``last`` harmlessly and
emit nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from analytics_zoo_tpu.models.lm import TransformerLM


def accept_proposals(logits, d, last, done, *, k, eos_id,
                     budget=None):
    """The speculative acceptance rule — ONE definition shared by
    batch ``speculative_generate`` and the continuous engine's
    spec-round programs (arena AND paged), so the greedy contract can
    never drift between surfaces.

    ``logits`` [B, k+1, V] are the target's verify outputs for inputs
    [last, d_0..d_{k-1}]; ``d`` [B, k] the draft proposals; ``last``
    [B] each row's previous emitted token; ``done`` [B] frozen rows.
    ``budget`` optionally clips emission to each row's remaining token
    allowance (batch generate; the engine drops surplus host-side).

    Returns ``(t, n_emit, new_last, done)``: ``t`` [B, k+1] the target
    argmaxes with everything after a row's first in-window eos frozen
    AT eos (the emitted prefix of a row therefore never needs host
    patching), ``n_emit`` [B] in 0..k+1 (0 only for done rows or an
    exhausted budget), ``new_last`` the last emitted token (the old
    ``last`` where nothing emitted)."""
    t = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, k+1]
    match = (t[:, :k] == d)                             # [B, k]
    a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    n_emit = a + 1                                      # t_0..t_a
    if budget is not None:
        n_emit = jnp.minimum(n_emit, budget)
    if eos_id is not None:
        js = jnp.arange(k + 1)[None, :]
        is_eos = (t == eos_id) & (js < n_emit[:, None])
        first_eos = jnp.where(is_eos.any(axis=1),
                              jnp.argmax(is_eos, axis=1), k + 1)
        n_emit = jnp.minimum(n_emit, first_eos + 1)
        # frozen tail on-device: everything after a row's first eos
        # reads as eos (emitted entries sit at js <= first_eos, so
        # freezing changes no emitted value)
        t = jnp.where(js > first_eos[:, None], jnp.int32(eos_id), t)
    n_emit = jnp.where(done, 0, n_emit)
    new_last = jnp.where(
        n_emit > 0,
        jnp.take_along_axis(t, jnp.maximum(n_emit - 1, 0)[:, None],
                            axis=1)[:, 0],
        last)
    if eos_id is not None:
        done = done | ((n_emit > 0) & (new_last == eos_id))
    return t, n_emit, new_last, done


def _prefill_caches(model, variables, prompt, L):
    """One batched causal forward (TransformerLM.prefill) padded into an
    L-long cache — NOT Pn sequential decode steps; the prompt is the
    one place generation gets a full MXU-friendly forward for free.
    Ragged rows' tail entries (past their true length) are dead until
    the advancing pointer overwrites them."""
    _, ks, vs = model.apply(variables, prompt,
                            method=TransformerLM.prefill)
    pad = L - ks.shape[2]
    ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return (ks.astype(jnp.dtype(model.dtype)),
            vs.astype(jnp.dtype(model.dtype)))


@functools.partial(jax.jit, static_argnames=(
    "model", "draft_model", "k", "max_new", "eos_id"))
def _spec_round(model, variables, draft_model, draft_variables,
                carry, *, k, max_new, eos_id):
    (last, tck, tcv, ptr, dck, dcv, dptr,
     out, gen_len, done) = carry
    B = last.shape[0]

    # ---- draft: k proposals via k+1 greedy cached steps ---------------
    # k+1 feeds (last, d_0..d_{k-1}) so the draft writes the SAME k+1
    # cache entries the target's verify does: after a full-acceptance
    # round the durable range includes d_{k-1}'s KV, which only the
    # (k+1)-th feed computes (the extra feed's OUTPUT is discarded).
    def dstep(c, _):
        tok, dck, dcv, p = c
        logits, dck, dcv = draft_model.apply(
            draft_variables, tok, dck, dcv, p,
            method=TransformerLM.decode_step)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, dck, dcv, p + 1), nxt

    (_, dck, dcv, _), d = lax.scan(
        dstep, (last, dck, dcv, dptr), None, length=k + 1)
    d = d.T[:, :k]                                      # [B, k]

    # ---- verify: one (k+1)-token cached forward of the target ---------
    inputs = jnp.concatenate([last[:, None], d], axis=1)  # [B, k+1]
    logits, tck, tcv = model.apply(
        variables, inputs, tck, tcv, ptr,
        method=TransformerLM.verify_step)

    # ---- accept the longest matching prefix (shared rule) -------------
    t, n_emit, new_last, done = accept_proposals(
        logits, d, last, done, k=k, eos_id=eos_id,
        budget=max_new - gen_len)

    # ---- scatter emitted tokens into the output buffer ----------------
    js = jnp.arange(k + 1)[None, :]
    dest = gen_len[:, None] + js                        # [B, k+1]
    live = js < n_emit[:, None]
    hit = (jnp.arange(max_new)[None, None, :]
           == dest[:, :, None]) & live[:, :, None]     # [B, k+1, max_new]
    out = jnp.where(hit.any(axis=1), jnp.einsum(
        "bjm,bj->bm", hit.astype(jnp.int32), t), out)

    # ---- advance ------------------------------------------------------
    # next round's first input is the last EMITTED token (computed by
    # accept_proposals); its KV is not durable yet (pointer stops just
    # before it), mirroring decode_step
    ptr = ptr + n_emit
    dptr = dptr + n_emit
    gen_len = gen_len + n_emit
    done = done | (gen_len >= max_new)
    return ((new_last, tck, tcv, ptr, dck, dcv, dptr,
             out, gen_len, done),
            n_emit)


def speculative_generate(model: TransformerLM, variables,
                         draft_model: TransformerLM, draft_variables,
                         prompt, max_new_tokens: int, *, k: int = 4,
                         eos_id: Optional[int] = None,
                         prompt_len=None):
    """Greedy generation of ``max_new_tokens`` with draft-model
    speculation.  Returns (tokens [B, max_new_tokens] int32, stats dict)
    where stats reports rounds and mean accepted-per-round — the
    speedup diagnostic.  Output rows equal models.lm.generate(greedy)
    on the target model exactly, including the eos contract: after a
    row's first ``eos_id`` the row FREEZES at eos (fixed-shape
    stop-on-EOS, same as generate()).
    """
    if model.vocab_size != draft_model.vocab_size:
        raise ValueError(
            f"draft vocab {draft_model.vocab_size} != target vocab "
            f"{model.vocab_size}: speculative tokens must share one id "
            f"space")
    prompt = jnp.asarray(prompt, jnp.int32)
    B, Pn = prompt.shape
    L = Pn + max_new_tokens + k + 1
    for m, which in ((model, "target"), (draft_model, "draft")):
        if L > m.max_position:
            raise ValueError(
                f"prompt+new+k = {L} exceeds {which} max_position "
                f"{m.max_position}")
    plen = (jnp.full((B,), Pn, jnp.int32) if prompt_len is None
            else jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1, Pn))

    tck, tcv = _prefill_caches(model, variables, prompt, L)
    dck, dcv = _prefill_caches(draft_model, draft_variables, prompt, L)
    last = jnp.take_along_axis(prompt, (plen - 1)[:, None], axis=1)[:, 0]
    carry = (last, tck, tcv, plen - 1, dck, dcv, plen - 1,
             jnp.zeros((B, max_new_tokens), jnp.int32),
             jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool))

    rounds = 0
    emitted = 0
    # worst case every round emits 1 token (all rejections)
    for _ in range(max_new_tokens):
        carry, n_emit = _spec_round(
            model, variables, draft_model, draft_variables, carry,
            k=k, max_new=max_new_tokens, eos_id=eos_id)
        rounds += 1
        # one fetch per round for BOTH loop controls (emit count and
        # the all-done flag) instead of two separate blocking reads
        n_round, all_done = jax.device_get((jnp.sum(n_emit),
                                            carry[-1].all()))
        emitted += int(n_round)
        if bool(all_done):
            break
    out = carry[7]
    if eos_id is not None:
        # generate() parity: after a row's first eos the row FREEZES at
        # eos (fixed-shape stop-on-EOS, models/lm.py generate docstring)
        o = np.asarray(out)
        m = np.cumsum(o == eos_id, axis=1)
        o = np.where((m - (o == eos_id)) > 0, eos_id, o)
        out = jnp.asarray(o, jnp.int32)
    stats = {"rounds": rounds,
             "emitted_tokens": emitted,
             "batch": B,
             # per-row totals let callers exclude phantom rows (serving
             # pads batches to buckets; those rows aren't traffic)
             "per_row_emitted": np.asarray(carry[8]),
             "mean_accepted_per_round":
                 emitted / max(1, rounds * B)}
    return out, stats
