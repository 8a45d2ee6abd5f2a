"""Continuous batching for generative serving.

SURVEY.md §2.6's TPU mapping names "continuous batching" as the serving
bar; the reference's Flink engine (upstream ``serving/engine/``) stops at
request-level micro-batching — a batch of prompts runs its whole
generation before the next batch starts, so a 2-token request convoys
behind a 32-token neighbour.  This module is the beyond-parity engine:

- A fixed-size **slot arena**: KV caches ``[n_layers, S, L, H, D]`` for
  ``S`` co-resident requests, allocated once.  Static shapes — the decode
  step compiles exactly once, no matter how requests come and go.
- **In-flight joining**: a new request PREFILLS with one MXU-friendly
  forward (``TransformerLM.prefill``) and its K/V are spliced into a free
  slot while other slots are mid-generation; the next engine tick decodes
  all residents together at their own positions (``decode_step`` with a
  per-row position vector).
- **Slot recycling**: a request that hits EOS or its token budget frees
  its slot immediately; the next waiting request takes it on the same
  tick.  Stale cache entries need no scrubbing — a resident only attends
  positions ``<= pos`` it has itself written (prompt prefill + its own
  decode steps), so a recycled slot never reads its predecessor's K/V.

Per-request results match ``models.lm.generate`` run solo: same frozen
tail EOS semantics, same ``[max_new_tokens]`` output shape (eos-padded),
greedy or per-request-temperature sampling with ``generate``-compatible
position-folded rngs.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import (Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.learn.inference_model import (
    _next_bucket, filter_prompt_buckets)
from analytics_zoo_tpu.models.hybrid_lm import HYBRID_COUNTERS, HybridLM
from analytics_zoo_tpu.models.lm import (TransformerLM,
                                         top_p_filter)
from analytics_zoo_tpu.models.speculative import accept_proposals
from analytics_zoo_tpu.ops.flash_attention import (KV_SCALE_DTYPE,
                                                   QuantKV)
from analytics_zoo_tpu.ops.sparse_attention import IndexedKeys
from analytics_zoo_tpu.ops.ssm import HybridCache
from analytics_zoo_tpu.serving.frontdoor import (PRIORITIES, QosPolicy,
                                                 WeightedWaitQueue)
from analytics_zoo_tpu.serving import policy as scheduler_policy
from analytics_zoo_tpu.serving.paged_cache import (BlockPool,
                                                   SINK_BLOCK,
                                                   block_bytes,
                                                   index_block_bytes,
                                                   split_block_budget)
from analytics_zoo_tpu.serving.flight import FlightRecorder
from analytics_zoo_tpu.serving.kv_store import (HostKVStore, TIER_HBM,
                                                TIER_HOST)
from analytics_zoo_tpu.serving.telemetry import Telemetry

logger = logging.getLogger("analytics_zoo_tpu")


# the flight record's fields of a model with a sparse-attention indexer,
# in the order the step program returns them (docs/observability.md)
DSA_COUNTERS = ("dsa_ctx_tokens", "dsa_read_tokens", "moe_assignments",
                "moe_max_load")
# (those of a model with state-space layers are ``HYBRID_COUNTERS``; of
# either tuple the last is a maximum and the others are sums)


def _zeros_like(x):
    """``jnp.zeros_like`` that also accepts the quantized KV pools
    (``QuantKV`` pytrees — int8 data + per-row scales): every leaf is
    zeroed independently."""
    return jax.tree_util.tree_map(jnp.zeros_like, x)


def _kv_label(dtype) -> str:
    """Short storage-mode label for a floating cache dtype, matching
    the ``paged_cache.KV_DTYPE_BYTES`` keys where one exists."""
    return {"bfloat16": "bf16", "float32": "f32",
            "float16": "f16", "float64": "f64"}.get(
        jnp.dtype(dtype).name, jnp.dtype(dtype).name)


class DeadlineExceeded(RuntimeError):
    """Terminal error for a request shed at ADMISSION because its
    end-to-end deadline already passed — distinct on purpose from the
    supervisor's in-flight deadline give-up (``plan_redispatch``'s
    error verdict), so the two show up separately on /metrics and in
    postmortems.  The message always starts with ``deadline_exceeded``
    so the wire error event is greppable and the SSE stream can name
    the event type."""


class _Req(NamedTuple):
    """One waiting-queue entry — named fields, because positional
    indexing across three consumers silently breaks when a field is
    added."""

    uri: str
    prompt: np.ndarray
    on_done: Optional[Callable]
    on_error: Optional[Callable]
    temperature: float
    rng_seed: Optional[int]
    max_new: int
    prefix: Optional[int]
    top_p: float
    # front-door fields (serving/frontdoor.py) — appended with defaults
    # so positional construction at older arity keeps working
    on_token: Optional[Callable] = None
    priority: str = "standard"
    tenant: str = ""
    enq_t: float = 0.0
    # prefill/decode disaggregation (docs/serving_memory.md): on the
    # SOURCE engine, ``handoff_cb(state)`` fires once the prefill's
    # first token lands — the row exports instead of decoding here.  On
    # the DESTINATION engine, ``handoff_state`` carries the exported
    # chain (submit_handoff); admission adopts it instead of prefilling.
    handoff_cb: Optional[Callable] = None
    handoff_state: Optional[dict] = None
    # end-to-end deadline (docs/serving_qos.md "Overload & brownout"):
    # an absolute ``time.monotonic`` instant; 0.0 = no deadline.
    # Admission sheds entries already past it BEFORE any prefill work.
    deadline_t: float = 0.0


@dataclass
class _Slot:
    uri: str
    plen: int
    max_new: int
    tokens: List[int] = field(default_factory=list)
    on_done: Optional[Callable] = None
    on_error: Optional[Callable] = None
    temperature: float = 0.0
    rng_seed: Optional[int] = None
    top_p: float = 0.0
    # streaming: fires per generated token from the pump thread
    # (``on_token(uri, token, index)``) — the index survives preemption
    # dedup because a readmitted row regenerates tokens
    # deterministically at the same positions
    on_token: Optional[Callable] = None
    # paged mode: the original request (requeued verbatim on
    # preemption) and an admission sequence number (the preemption
    # victim is always the LATEST admission — earliest admissions keep
    # making forward progress, so preemption can never livelock)
    req: Optional[_Req] = None
    admit_seq: int = 0
    # chunked-prefill state machine: a slot admits as "PREFILLING" and
    # feeds its prompt to the cache chunk by chunk (fill_pos = next
    # cache position to write, starting past any spliced/shared
    # prefix); the tick its last chunk lands it emits its first token
    # and flips to "DECODE".  ``full`` holds the not-yet-fed tokens
    # (positions base..plen-1); ``hashes``/``n_pub`` track which full
    # prompt blocks the paged path has already published for sharing.
    state: str = "DECODE"
    fill_pos: int = 0
    base: int = 0
    full: Optional[np.ndarray] = None
    hashes: Optional[list] = None
    n_pub: int = 0


class _WeightedJit:
    """A jitted engine program whose leading operands are model weights.

    The weights ride as ARGUMENTS.  A program that closes over them has
    every parameter lowered into its HLO as a constant: at 1.5 B
    parameters that is ~3 GB per program variant to embed, hash for the
    compile cache, compile, and hold in HBM a second time — the engine
    builds several such programs.  Call sites keep the weight-free
    signature (``donate_argnums`` count from the first non-weight
    operand), and ``_cache_size`` keeps ``lint.trace_guard`` counting
    compiles by walking the engine's attributes."""

    def __init__(self, fn: Callable, weights: tuple,
                 donate_argnums: Tuple[int, ...] = (), **jit_kwargs):
        self._weights = weights
        self._jit = jax.jit(
            fn, donate_argnums=tuple(i + len(weights)
                                     for i in donate_argnums),
            **jit_kwargs)

    def __call__(self, *args, **kwargs):
        return self._jit(*self._weights, *args, **kwargs)

    def lower(self, *args, **kwargs):
        """``jax.jit(...).lower`` with the weights in front: inspect the
        compiled program (``.compile().memory_analysis()``) without
        running it; operands may be ``jax.ShapeDtypeStruct``s."""
        return self._jit.lower(*self._weights, *args, **kwargs)

    def _cache_size(self) -> int:
        return self._jit._cache_size()


class ContinuousEngine:
    """Slot-arena generation engine over one ``TransformerLM``.

    Host-side control loop + three jitted device programs: the step
    program (advance every slot ``ticks_per_step`` tokens at per-slot
    positions in one lax.scan call; compiled per (n_ticks, sampled) via
    ``_get_step``), the bucketed batched prefill (one forward for ALL
    joiners sharing a prompt bucket), and the per-slot K/V splice.  The
    arena buffers are donated through step/insert so XLA updates them in
    place instead of copying ``S*L`` of KV per token.

    **KV memory.** The cache stores only ``model.kv_heads`` heads per
    position: a grouped-query model (``num_kv_heads < num_heads``)
    shrinks every resident's K/V ``num_heads/num_kv_heads``-fold, which
    is proportionally more co-resident requests for the same HBM
    (``capacity_report()`` quantifies it); ``cache_dtype`` narrows it
    further (e.g. a bfloat16 cache under an f32 model halves it again —
    attention upcasts via the einsums' f32 accumulation).

    **``paged=True``** replaces the per-slot arena with a block-pool
    cache (serving/paged_cache.py): K/V live in one flat pool of
    ``block_size``-token blocks, each resident holds only the blocks it
    has actually filled (via a per-slot block table), full prompt
    blocks are hash-indexed so requests sharing a prompt prefix attach
    to the same physical blocks copy-free (subsuming the manual
    ``register_prefix`` splice), and when the pool runs dry the engine
    PREEMPTS the latest admission back to the queue front instead of
    OOMing — its partial tokens are discarded and regenerate
    deterministically on readmission (greedy argmax, and sampled rows
    fold the rng by absolute position).  ``cache_metrics()`` reports
    occupancy/hit-rate/preemptions.  A ``draft_model`` composes with
    paged (and with chunked, and with both): the draft pages its own
    K/V through a SECOND pool tenant — its own block tables and
    allocator over a proportionally small slice of HBM — and the
    verify step writes k+1 positions through the paged write path,
    rolling rejected positions back by pointer (never by block copy).
    Remaining paged limitation (ROADMAP open item): no mesh; paged
    ``register_prefix`` must run before the pump starts (it updates
    the donated pool buffers — racing a live ``step()`` is undefined).

    Not thread-safe by itself: ``submit`` may be called from any thread,
    but ``step``/``drain`` must run on ONE pump thread (the serving loop).
    """

    def __init__(self, model: TransformerLM, variables, *,
                 max_new_tokens: int, max_slots: int = 8,
                 prompt_buckets: Sequence[int] = (16, 32, 64, 128),
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 ticks_per_step: int = 1,
                 cache_dtype=None,
                 kernel: str = "gather",
                 kv_dtype: Optional[str] = None,
                 mesh=None, partition_rules=None,
                 draft_model: Optional[TransformerLM] = None,
                 draft_variables=None, speculation_k: int = 4,
                 paged: bool = False, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 draft_n_blocks: Optional[int] = None,
                 hbm_fraction: Optional[float] = None,
                 enable_prefix_cache: bool = True,
                 elastic_pool: bool = False,
                 kv_host_store_bytes: int = 0,
                 prefix_directory=None,
                 replica_id: int = 0,
                 fault_injector=None,
                 chunked: bool = False,
                 tick_token_budget: Optional[int] = None,
                 telemetry: Optional[Telemetry] = None,
                 qos: Optional[QosPolicy] = None,
                 flight: Optional[FlightRecorder] = None,
                 flight_capacity: int = 2048):
        """``mesh`` (with a ``tp`` axis) serves a model LARGER than one
        chip's HBM: weights shard per ``partition_rules`` (default
        ``LM_PARTITION_RULES`` — Megatron layout), the KV arena shards
        over tp on the kv-heads axis (each chip holds 1/tp of every
        slot's cache), and slot bookkeeping (tok/pos/done) replicates.
        XLA propagates the shardings through the jitted step/prefill/
        splice programs — decode runs as one SPMD program with the tp
        collectives the weight layout implies.

        ``draft_n_blocks`` (paged + draft only) overrides the draft
        tenant's pool size, which otherwise matches ``n_blocks`` — the
        draft's K/V is cheap (per-block bytes scale with its
        layers x kv_heads x head_dim), so equal counts cost little; a
        smaller override is mainly a test lever for draft-pool-dry
        preemption.

        ``kernel`` picks the paged-attention read path:
        ``"gather"`` (default) is the materialising ``jnp.take``
        reference, ``"fused"`` the Pallas kernel that streams KV
        blocks HBM→VMEM per grid step (interpret mode off-TPU, so
        greedy parity holds on CPU too).  ``kv_dtype`` picks the
        TARGET pool's storage: ``None`` follows ``cache_dtype``,
        ``"bf16"`` forces a bfloat16 pool, ``"int8"`` stores
        quantized blocks with per-row bfloat16 scales (~1.9x more
        blocks at equal HBM; both kernels dequantize on read).  Both
        knobs require ``paged=True``; the draft tenant's pool stays
        in ``cache_dtype`` (it is already small)."""
        if model.pp_stages > 0:
            raise ValueError("continuous batching serves pp_stages=0 "
                             "models (models.lm.unstack_pp_params)")
        self.model = model
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        # ---- telemetry (always-on; serving/telemetry.py) ---------------
        # one facade per engine unless the serving layer passes its own
        # (to merge registries under one scrape).  Every hook below is
        # host-side floats/ints only: nothing telemetry does enters a
        # jitted program, so it can neither sync the device nor retrace.
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry()
        # the cycle's lap clock (telemetry.LapClock): step() and its
        # tick paths name every phase they pass through
        self._lap = self.telemetry.clock.lap
        # called once per device call, right after it is enqueued and
        # before the host waits for its result (_dispatched): the
        # serving pump hangs its token flush here, so that the broker's
        # fan-out runs while the device works.  None without a pump.
        self.after_dispatch: Optional[Callable[[], None]] = None
        # ---- flight recorder (serving/flight.py) -----------------------
        # always-on bounded ring of per-tick state snapshots — the
        # incident lookback a diagnostic bundle ships.  One plain dict
        # of host ints per tick (no device reads beyond what telemetry
        # already sampled), so greedy outputs are bitwise-identical
        # with it on or off.  ``flight_capacity=0`` disables it (the
        # overhead benchmark's lever); a shared recorder can be passed
        # in so the serving layer can bundle it after an engine crash.
        self.flight = flight if flight is not None else (
            FlightRecorder(flight_capacity) if flight_capacity > 0
            else None)
        self._tick_kind = "decode"
        self._alloc_fail_streak = 0
        # cumulative-counter baselines for the per-tick deltas the
        # flight record carries
        self._flight_last = {"preempt": 0, "compiles": 0, "chunks": 0,
                             "budget_tokens": 0, "alloc_fail": 0,
                             "draft_alloc_fail": 0, "spec_proposed": 0,
                             "spec_accepted": 0, "pool_resizes": 0,
                             "handoffs_out": 0, "handoffs_in": 0,
                             "kv_spills": 0, "kv_readmits": 0,
                             "deadline_sheds": 0, "flush_events": 0,
                             "flush_overlapped": 0}
        # ---- overload brownout + deadline admission (policy.py) --------
        # per-tick engine state the broker's plan_brownout controller
        # pushes via set_brownout(); 0/off by default, and every gate
        # below checks the level first, so an engine nobody browns out
        # makes bit-identical decisions to the pre-brownout engine.
        self._brownout_level = 0
        self._brownout_enabled = False
        self._brownout_clamp = 0
        self._deadline_seen = False
        self._deadline_sheds = 0
        # ---- speculative mode (draft arena) ----------------------------
        # the slot arena is ALREADY per-row-positioned, which is exactly
        # what per-slot acceptance rates need: each verify round advances
        # every slot by its own accepted count.  Greedy-only (a sampled
        # slot's speculative contract needs rejection sampling — not
        # implemented; submit() rejects temperature > 0 in this mode).
        self.draft_model = draft_model
        self._draft_variables = draft_variables
        self._spec_k = int(speculation_k) if draft_model is not None else 0
        if draft_model is not None:
            if draft_variables is None:
                raise ValueError("draft_model needs draft_variables")
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.vocab_size} != target "
                    f"vocab {model.vocab_size}")
            if draft_model.pp_stages > 0:
                raise ValueError("draft must be pp_stages=0")
            if self._spec_k < 1:
                raise ValueError("speculation_k must be >= 1")
        # speculative verify writes k+1 entries past the pointer and
        # looks up positions there, so the bucket limit tightens by k+1
        # and must fit BOTH models' position tables
        eff_max_pos = model.max_position if draft_model is None else \
            min(model.max_position, draft_model.max_position)
        self.prompt_buckets = filter_prompt_buckets(
            prompt_buckets, eff_max_pos,
            max_new_tokens + (self._spec_k + 1 if draft_model else 0))
        self.max_prompt_width = self.prompt_buckets[-1]
        S = int(max_slots)
        L = self.max_prompt_width + self.max_new_tokens \
            + (self._spec_k + 1 if draft_model is not None else 0)
        self._S, self._L = S, L
        # GQA models store only kv_heads in the cache: the arena shrinks
        # num_heads/kv_heads-fold, which is more co-resident requests
        # for the same HBM.  cache_dtype narrows it further (e.g.
        # bfloat16 arena under an f32 model: 2x more slots; attention
        # reads upcast via the einsums' f32 accumulation).
        H = getattr(model, "kv_heads", model.num_heads)
        # the model says how wide a head is: a model with a head_dim of
        # its own has num_heads * head_size != hidden_size
        D = getattr(model, "head_size", None) \
            or model.hidden_size // model.num_heads
        # ---- a model with a sparse-attention indexer -------------------
        # caches an index key beside K and V (models/lm.py); the engine
        # reaches it through sibling step programs and holds its key-side
        # pools as ONE pytree (IndexedKeys), so everything below that
        # donates, zeroes, resizes or measures "the K pool" covers the
        # index keys too.  A model without one takes none of these
        # branches: its programs are what they were.
        self._dsa = bool(getattr(model, "indexer_topk", 0))
        # ---- a model with state-space layers (models/hybrid_lm.py) -----
        # keeps a fixed-size recurrent state a SLOT and state-space layer
        # beside the paged K/V of its attention layers; the engine holds
        # both as ONE pytree in the K pool's place (ops.ssm.HybridCache)
        # and reaches the model through sibling step programs.  No other
        # model takes these branches.
        self._ssm = bool(getattr(model, "state_layers", 0))
        # the counters such a model's step programs return, booked to the
        # tick's flight record (``_note_counters``); none for any other
        self._counter_names = (DSA_COUNTERS if self._dsa
                               else HYBRID_COUNTERS if self._ssm else ())
        self._counts = dict.fromkeys(self._counter_names, 0)
        self._passes = 0
        # validate cache_dtype EAGERLY with a serving-level message — a
        # bad value must not surface as a bare jnp.dtype TypeError deep
        # inside arena allocation
        if cache_dtype is None:
            cdtype = jnp.dtype(model.dtype)
        else:
            try:
                cdtype = jnp.dtype(cache_dtype)
            except TypeError:
                raise ValueError(
                    f"cache_dtype {cache_dtype!r} is not a dtype the KV "
                    f"cache can be allocated with; pass a floating "
                    f"dtype like 'bfloat16' or 'float32' (or None to "
                    f"follow model.dtype "
                    f"{jnp.dtype(model.dtype).name})") from None
            if not jnp.issubdtype(cdtype, jnp.floating):
                raise ValueError(
                    f"cache_dtype {cache_dtype!r} resolves to "
                    f"{cdtype.name}, which is not a floating dtype — "
                    f"K/V projections cannot be stored in it without "
                    f"corrupting attention")
        # ---- paged-attention kernel / KV storage knobs -----------------
        # both only change how the PAGED read/write path runs; default
        # (gather + cache_dtype storage) is bit-for-bit the pre-knob
        # behavior.
        if kernel not in ("gather", "fused"):
            raise ValueError(f"kernel must be 'gather' or 'fused', got "
                             f"{kernel!r}")
        if kv_dtype not in (None, "bf16", "int8"):
            raise ValueError(f"kv_dtype must be None, 'bf16' or "
                             f"'int8', got {kv_dtype!r}")
        if not paged and (kernel != "gather" or kv_dtype is not None):
            raise ValueError(
                f"kernel={kernel!r} / kv_dtype={kv_dtype!r} require "
                f"paged=True: both select the paged-attention path "
                f"(the arena engine has no block pool to apply them to)")
        if elastic_pool and not paged:
            raise ValueError(
                "elastic_pool=True requires paged=True: the arena "
                "engine has no block pool to grow or shrink")
        # ---- tiered KV memory (serving/kv_store.py) --------------------
        # a host-RAM second tier for evicted prefix chains plus an
        # optional fleet-wide prefix directory.  Both default OFF —
        # kv_host_store_bytes=0 and prefix_directory=None leave every
        # pool hook None, bit-identical to the single-tier engine.
        if kv_host_store_bytes < 0:
            raise ValueError(
                f"kv_host_store_bytes must be >= 0, got "
                f"{kv_host_store_bytes}")
        if (kv_host_store_bytes > 0 or prefix_directory is not None) \
                and not paged:
            raise ValueError(
                "kv_host_store_bytes / prefix_directory require "
                "paged=True: the tiered KV store spills and re-admits "
                "BLOCK CHAINS (the arena engine has no blocks to "
                "spill)")
        if kv_host_store_bytes > 0 and draft_model is not None:
            raise ValueError(
                "kv_host_store_bytes does not compose with a draft "
                "model: speculative mode runs two pool tenants in "
                "lockstep and re-admitting only the target tenant's "
                "chain would desynchronize them — serve the host tier "
                "on non-speculative replicas")
        if self._dsa:
            unsupported = [what for what, on in (
                ("paged=False (the slot arena has no index-key cache)",
                 not paged),
                ("a tp mesh (the index-key pool has one head to shard)",
                 mesh is not None and int(mesh.shape.get("tp", 1)) > 1),
                ("kv_dtype='int8'", kv_dtype == "int8"),
                ("a draft model", draft_model is not None),
                ("the host tier (kv_host_store_bytes / "
                 "prefix_directory)",
                 kv_host_store_bytes > 0 or prefix_directory is not None),
            ) if on]
            if unsupported:
                raise ValueError(
                    "a model with a sparse-attention indexer "
                    "(indexer_topk > 0) is served by the paged engine on "
                    "one chip with a bf16/float cache; not supported "
                    "with it: " + "; ".join(unsupported)
                    + " (docs/serving.md)")
        if self._ssm:
            unsupported = [what for what, on in (
                ("paged=False (the slot arena's programs carry no "
                 "recurrent state)", not paged),
                ("chunked=False (monolithic admission carries no state "
                 "from the prompt to the decode rows)", not chunked),
                ("enable_prefix_cache=True (a chain of KV block hashes "
                 "does not describe a prefix whose state-space layers "
                 "keep one state a slot: a matched block would skip "
                 "tokens the state has to see)", enable_prefix_cache),
                ("a tp mesh (the state arena is not sharded)",
                 mesh is not None and int(mesh.shape.get("tp", 1)) > 1),
                ("kv_dtype='int8'", kv_dtype == "int8"),
                ("a draft model (a rejected proposal would have to roll "
                 "the state back)", draft_model is not None),
                ("the host tier (kv_host_store_bytes / "
                 "prefix_directory)",
                 kv_host_store_bytes > 0 or prefix_directory is not None),
                ("elastic_pool=True", elastic_pool),
            ) if on]
            if unsupported:
                raise ValueError(
                    "a model with state-space layers (HybridLM) is served "
                    "by the paged + chunked engine on one chip with a "
                    "bf16/float cache and the prefix cache off; not "
                    "supported with it: " + "; ".join(unsupported)
                    + " (docs/serving.md)")
        self.kernel = kernel
        if kv_dtype == "bf16":
            # explicit storage request wins over cache_dtype/model dtype
            cdtype = jnp.dtype(jnp.bfloat16)
        self._kv_int8 = kv_dtype == "int8"
        self.kv_dtype = "int8" if self._kv_int8 else _kv_label(cdtype)
        self.mesh = mesh
        # ---- mesh: weights shard FIRST, for EVERY engine mode ----------
        # The mesh is WHERE THE ENGINE LIVES: weights, KV storage and
        # every program run on its devices — a one-device mesh is how a
        # replica is pinned to its own chip (serving/server.py).
        # arena, paged, chunked, and speculative engines all ride the
        # same Megatron-layout rules; the per-mode KV storage below only
        # decides how the cache itself is laid out.  _kv_tp records
        # whether the chosen rules actually put "tp" on the k/v
        # projection outputs — the KV storage (arena OR block pool) must
        # match what they emit, or every tick pays resharding
        # collectives the layout never required.
        tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        self._tp = tp
        self._kv_tp = self._dkv_tp = False
        if mesh is not None:
            from analytics_zoo_tpu.models.lm import LM_PARTITION_RULES
            from analytics_zoo_tpu.parallel.partition import state_sharding

            if H % tp and partition_rules is None:
                raise ValueError(
                    f"kv_heads={H} must divide by tp={tp} to shard the "
                    f"KV cache under the default LM_PARTITION_RULES; "
                    f"narrow-KV (MQA/GQA) models pass partition_rules "
                    f"with the key/value kernels replicated (P()) — the "
                    f"KV storage then replicates too")
            rules = partition_rules or LM_PARTITION_RULES
            shardings = state_sharding(mesh, variables, rules)
            # sharded-from-BIRTH: materialising full weights on one chip
            # first would OOM exactly the beyond-one-chip models this
            # path exists for
            variables = jax.device_put(variables, shardings)
            self._kv_tp = H % tp == 0 and self._kv_kernels_tp_sharded(
                shardings)
            if draft_model is not None:
                # the draft shards under the SAME rules (same
                # architecture, same regexes); a draft whose kv_heads
                # don't divide tp replicates its k/v kernels per-dim
                # (match_partition_rules' divisibility fallback) and its
                # KV storage follows suit
                dshardings = state_sharding(mesh, draft_variables, rules)
                draft_variables = jax.device_put(draft_variables,
                                                 dshardings)
                self._draft_variables = draft_variables
                dH = getattr(draft_model, "kv_heads",
                             draft_model.num_heads)
                self._dkv_tp = dH % tp == 0 and \
                    self._kv_kernels_tp_sharded(dshardings)
        # the engine's own device(s): every memory_stats read below is
        # of these, whichever chip the engine was given
        if mesh is not None:
            self._devices = list(mesh.devices.flat)
        else:
            leaf = jax.tree.leaves(variables)[0]
            self._devices = (sorted(leaf.devices(), key=lambda d: d.id)
                             if isinstance(leaf, jax.Array)
                             else [jax.devices()[0]])
        # ---- paged mode (block-pool cache, serving/paged_cache.py) -----
        self.paged = bool(paged)
        self._preemptions = 0
        self._peak_resident = 0
        self._admit_seq = 0
        self._pool: Optional[BlockPool] = None
        self._pk = self._pv = None
        self._paged_prefixes: Dict[int, tuple] = {}
        self._dpool: Optional[BlockPool] = None
        self._dpk = self._dpv = None
        # tiered-KV state (None/0 = tier off on every path)
        self._kv_store: Optional[HostKVStore] = None
        self._prefix_directory = prefix_directory
        self._replica_id = int(replica_id)
        # chaos harness (serving/fault.py): None = injection off, and
        # every hook below is a no-op — bit-identical behavior
        self._fault = fault_injector
        self._kv_spills = 0
        self._kv_spill_bytes = 0
        self._kv_readmits = 0
        self._kv_readmit_tokens_saved = 0
        # deferred device work recorded by pool callbacks / readmission
        # while ``_pool_lock`` is held — the pump thread drains both
        # BEFORE the next device write to the pool (tpulint TZ102/TZ103:
        # no D2H/H2D under the pool lock)
        self._pending_spills: List[Tuple[int, int]] = []   # (block, hash)
        self._pending_readmits: List[tuple] = []    # (blocks, kcat, vcat)
        if self.paged:
            bs = int(block_size)
            if bs < 1:
                raise ValueError(f"block_size must be >= 1, got {bs}")
            M = -(-L // bs)         # logical blocks per row, ceil(L/bs)
            # int8 rows cost D + 2 bytes (1/elt + a bf16 scale) vs
            # 2D for bf16 — block_bytes() is the shared ledger the
            # budget split and the capacity report both bill at
            if self._kv_int8:
                per_block = block_bytes(model.num_layers, bs, H, D,
                                        "int8")
            elif self._ssm:
                # only the attention layers keep K/V in the pool
                per_block = 2 * model.kv_layers * bs * H * D \
                    * cdtype.itemsize
            else:
                per_block = 2 * model.num_layers * bs * H * D \
                    * cdtype.itemsize
            if self._dsa:
                # one block id holds K, V and the index keys: the budget
                # is divided over them by bytes a token
                per_block += index_block_bytes(
                    model.num_layers, bs, model.indexer_head_dim,
                    cdtype.itemsize)
            draft_per_block = 0
            if draft_model is not None:
                DHp = getattr(draft_model, "kv_heads",
                              draft_model.num_heads)
                DDp = draft_model.head_size
                draft_per_block = 2 * draft_model.num_layers * bs \
                    * DHp * DDp * cdtype.itemsize
            self._per_block_bytes = per_block
            self._draft_per_block_bytes = draft_per_block
            if n_blocks is None:
                hbm = self._hbm_stats() if hbm_fraction is not None \
                    else None
                if hbm is not None:
                    # with a draft the byte budget covers BOTH tenants:
                    # the common block count splits it proportionally
                    # to per-block cost (the draft's slice is small)
                    n_blocks = max(M + 1, split_block_budget(
                        int(hbm["bytes_limit"] * float(hbm_fraction)),
                        (per_block, draft_per_block)
                        if draft_model is not None else (per_block,)))
                else:
                    if hbm_fraction is not None:
                        logger.info(
                            "hbm_fraction=%s does not apply on the CPU "
                            "backend (no device memory to take a "
                            "fraction of); the CPU sizing rule is "
                            "arena-equivalent, S*M+1 blocks",
                            hbm_fraction)
                    # arena-equivalent capacity: every slot can run to
                    # full length — paged still wins whenever real
                    # traffic doesn't (shorter prompts, prefix sharing)
                    n_blocks = S * M + 1
            n_blocks = int(n_blocks)
            if n_blocks < M + 1:
                raise ValueError(
                    f"n_blocks={n_blocks} cannot hold one full-length "
                    f"sequence: need >= {M + 1} ({M} logical blocks of "
                    f"{bs} positions + the sink block 0)")
            self._bs, self._M = bs, M
            # host tier + directory hooks precede pool creation: the
            # pool fires them from inside allocate()/shrink()/insert()
            if kv_host_store_bytes > 0:
                self._kv_store = HostKVStore(
                    int(kv_host_store_bytes),
                    evict_cb=self._store_evicted)
            self._pool = BlockPool(
                n_blocks, bs, enable_prefix_cache,
                event_cb=self.telemetry.pool_event,
                name="target",
                kv_dtype=self.kv_dtype,
                bytes_per_block=per_block,
                spill_cb=(self._spill_block
                          if self._kv_store is not None else None),
                index_cb=(self._pool_index_event
                          if self._prefix_directory is not None
                          else None))
            # pool-mutation guard: admission/growth run on the pump
            # thread, but unregister_prefix releases from client threads
            self._pool_lock = threading.Lock()
            # HEAD-MAJOR pool layout [layers, N, KH, bs, D]: the fused
            # kernel's block specs carve (1, 1, bs, D) tiles per
            # (table[b, j], head) grid step, which only squeezes
            # LEADING singletons — Mosaic-clean on TPU (jax's own paged
            # kernel uses the same order).  int8 pools are QuantKV
            # pytrees (int8 data + per-(block, position, head) bf16
            # scales) — every jitted program moves them like arrays.
            shape = (model.num_layers, n_blocks, H, bs, D)
            # mesh: the pool shards over tp on the kv-heads dim exactly
            # like the arena — [layers, N, KH/tp, bs, D] per chip,
            # allocated sharded-from-birth.  Bookkeeping (BlockPool,
            # block tables) stays host-side and replicated — allocation,
            # prefix hashing, preemption, and pointer-rollback verify
            # are all table rewrites, mesh-oblivious by construction —
            # and the jitted decode/chunk/verify programs reach the
            # pool through XLA's sharding propagation.
            pool_sh = scale_sh = None
            if mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                hax = "tp" if self._kv_tp else None
                pool_sh = NamedSharding(mesh,
                                        P(None, None, hax, None, None))
                scale_sh = NamedSharding(mesh, P(None, None, hax, None))
            if self._kv_int8:
                self._pk = QuantKV(
                    jnp.zeros(shape, jnp.int8, device=pool_sh),
                    jnp.ones(shape[:-1], KV_SCALE_DTYPE,
                             device=scale_sh))
                self._pv = QuantKV(
                    jnp.zeros(shape, jnp.int8, device=pool_sh),
                    jnp.ones(shape[:-1], KV_SCALE_DTYPE,
                             device=scale_sh))
            elif self._dsa:
                # the arenas the model says it caches, [layers, N, heads,
                # bs, width] each: K and V token-major, and the index keys
                geo = model.paged_cache_geometry()
                arena = lambda name: jnp.zeros(
                    (model.num_layers, n_blocks, geo[name][0], bs,
                     geo[name][1]), cdtype, device=pool_sh)
                self._pk = IndexedKeys(arena("k"), arena("index"))
                self._pv = arena("v")
            elif self._ssm:
                # the K/V pool of the attention layers alone, and beside
                # it one state and one convolution window a SLOT and
                # state-space layer (an array a layer: a step program
                # updates each in place, and none is sliced out of a
                # stack).  The state is float32 whatever the cache's
                # dtype: it is summed into at every token
                geo = model.state_geometry()
                kv_shape = (model.kv_layers, n_blocks, H, bs, D)
                self._pk = HybridCache(
                    jnp.zeros(kv_shape, cdtype),
                    tuple(jnp.zeros((S,) + geo["ssm"], jnp.float32)
                          for _ in range(model.state_layers)),
                    tuple(jnp.zeros((S,) + geo["conv"], cdtype)
                          for _ in range(model.state_layers)))
                self._pv = jnp.zeros(kv_shape, cdtype)
                self._ssm_row_bytes = model.state_layers * sum(
                    int(np.prod(geo[k])) * isz for k, isz in
                    (("ssm", 4), ("conv", cdtype.itemsize)))
            else:
                self._pk = jnp.zeros(shape, cdtype, device=pool_sh)
                self._pv = jnp.zeros(shape, cdtype, device=pool_sh)
            # per-slot block tables; SINK everywhere a row holds no
            # block, so stray writes land in storage nothing attends
            self._tables = np.full((S, M), SINK_BLOCK, np.int32)
            self._row_blocks: List[List[int]] = [[] for _ in range(S)]
            if draft_model is not None:
                # the draft is a second POOL TENANT: its own physical
                # block arena, block tables, and host allocator (block
                # ids from one pool mean nothing in the other).  The
                # draft position pointer tracks the target's, so a
                # row's draft table grows in LOCKSTEP with its target
                # table — same block count, per-block bytes scaled by
                # the draft's layers x kv_heads x head_dim.
                dnb = n_blocks if draft_n_blocks is None \
                    else int(draft_n_blocks)
                if dnb < M + 1:
                    raise ValueError(
                        f"draft_n_blocks={dnb} cannot hold one "
                        f"full-length sequence: need >= {M + 1} "
                        f"({M} logical blocks of {bs} positions + the "
                        f"sink block 0)")
                self._dpool = BlockPool(
                    dnb, bs, enable_prefix_cache,
                    event_cb=self.telemetry.pool_event, name="draft",
                    kv_dtype=_kv_label(cdtype),
                    bytes_per_block=draft_per_block)
                dpool_sh = None
                if mesh is not None:
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P
                    dpool_sh = NamedSharding(
                        mesh, P(None, None,
                                "tp" if self._dkv_tp else None,
                                None, None))
                self._dpk = jnp.zeros(
                    (draft_model.num_layers, dnb, DHp, bs, DDp),
                    cdtype, device=dpool_sh)
                self._dpv = jnp.zeros(
                    (draft_model.num_layers, dnb, DHp, bs, DDp),
                    cdtype, device=dpool_sh)
                self._dtables = np.full((S, M), SINK_BLOCK, np.int32)
                self._drow_blocks: List[List[int]] = [
                    [] for _ in range(S)]
        # ---- elastic pool (opt-in; docs/serving_memory.md) -------------
        # probe free HBM AFTER weights + initial pool allocation to set
        # the grow ceiling; grow/shrink execute in resize_pool() on the
        # pump thread, block-granular, at the eviction boundary
        # (BlockPool.shrink never evicts a referenced block).
        self.elastic_pool = bool(elastic_pool)
        self._pool_resizes = 0
        self._pool_resize_clamps = 0
        # prefill/decode disaggregation traffic (paged only): rows this
        # engine exported at first-token time / adopted from a donor
        self._handoffs_out = 0
        self._handoffs_in = 0
        self._autoresize_last_fails = 0
        self._pool_floor = (self._M + 1) if self.paged else 0
        self._pool_ceiling = 0
        self._resize_step = 0
        if self.elastic_pool:
            # resize steps snap to a coarse granularity so the jitted
            # programs see FEW distinct pool shapes (each new shape
            # compiles once, then caches)
            self._resize_step = max(self._bs, n_blocks // 8)
            ceiling = n_blocks
            hbm = self._hbm_stats()
            per = self._per_block_bytes + self._draft_per_block_bytes
            if hbm is not None:
                # leave 20% of the probed headroom for activations /
                # compile scratch — the elastic pool must never be the
                # reason a forward OOMs
                free = max(0, hbm["bytes_limit"] - hbm["bytes_in_use"])
                ceiling = max(ceiling, n_blocks + (int(free * 0.8) // per))
            else:
                # CPU backend (no device memory to probe): cap at
                # arena-equivalent capacity — every slot can run to
                # full length
                ceiling = max(ceiling, S * self._M + 1)
            self._pool_ceiling = int(ceiling)
        # kv-bytes-per-token: all-layer, both-tenant HBM cost of ONE
        # cached token position — the gauge/flight-record figure that
        # makes bf16 and int8 runs comparable at a glance.
        if self.paged:
            self._kv_bytes_per_token = \
                (self._per_block_bytes
                 + self._draft_per_block_bytes) // self._bs
        else:
            bpt = 2 * model.num_layers * H * D * cdtype.itemsize
            if draft_model is not None:
                dH = getattr(draft_model, "kv_heads",
                             draft_model.num_heads)
                dD = draft_model.head_size
                bpt += 2 * draft_model.num_layers * dH * dD \
                    * cdtype.itemsize
            self._kv_bytes_per_token = bpt
        # ---- chunked prefill (token-budget tick scheduler) -------------
        # chunked=True replaces monolithic admission prefill with
        # incremental chunks packed alongside decodes under a per-tick
        # token budget — long prompts stop stalling active decoders.
        self.chunked = bool(chunked)
        self._prefill_stall_ticks = 0
        self._prefill_preemptions = 0
        self._budget_tokens_used = 0
        self._budget_ticks = 0
        self.tick_token_budget: Optional[int] = None
        if self.chunked:
            if tick_token_budget is None:
                # default: roughly one decode-bucket of MXU work — all S
                # decode rows plus at least one smallest-bucket chunk
                # (and at least one paged block) fit in a tick.  A
                # speculative decode row costs k+1 verify positions, so
                # the default scales with the row's true footprint
                per_row = self._spec_k + 1
                budget = max(self.prompt_buckets[0] + per_row * S,
                             2 * per_row * S)
                if self.paged:
                    budget = max(budget, self._bs)
            else:
                budget = int(tick_token_budget)
                if budget < self.prompt_buckets[0]:
                    raise ValueError(
                        f"tick_token_budget={budget} is below the "
                        f"smallest chunk bucket "
                        f"{self.prompt_buckets[0]}: no prefill chunk "
                        f"could ever be scheduled and admission would "
                        f"livelock; raise the budget or add a smaller "
                        f"prompt bucket")
                if self.paged and budget < self._bs:
                    raise ValueError(
                        f"tick_token_budget={budget} is below "
                        f"block_size={self._bs}: a chunk could never "
                        f"cover one paged block per tick; raise the "
                        f"budget or shrink block_size")
            self.tick_token_budget = budget
            # chunk widths reuse the prompt buckets (bounded compile
            # count), trimmed to what the budget can ever schedule
            self._chunk_buckets = tuple(
                b for b in self.prompt_buckets if b <= budget)
            # arena chunk attention reads a [kb, read_len] cache window
            # that tracks the fill frontier — pow2 buckets keep the
            # compile count O(log L) instead of one per frontier
            rb: List[int] = []
            v = 8
            while v < L:
                rb.append(v)
                v *= 2
            rb.append(L)
            self._read_buckets = tuple(rb)
        if self.paged:
            self._ck = self._cv = None  # pool replaces the slot arena
        elif mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # the arena must MATCH what the kv projections emit under
            # the chosen rules (weights sharded above) — custom rules
            # that replicate the k/v kernels (even on a divisible-heads
            # model) need a replicated arena, or every decode step pays
            # resharding collectives the layout never required
            kv_sh = NamedSharding(
                mesh, P(None, None, None, "tp", None) if self._kv_tp
                else P())
            # allocate sharded-from-BIRTH, like the weights above
            self._ck = jnp.zeros((model.num_layers, S, L, H, D), cdtype,
                                 device=kv_sh)
            self._cv = jnp.zeros((model.num_layers, S, L, H, D), cdtype,
                                 device=kv_sh)
        else:
            self._ck = jnp.zeros((model.num_layers, S, L, H, D), cdtype)
            self._cv = jnp.zeros_like(self._ck)
        self._variables = variables
        self.ticks_per_step = max(1, int(ticks_per_step))
        # host-side per-slot state (device copies travel as step args)
        self._tok = np.zeros(S, np.int32)
        self._pos = np.zeros(S, np.int32)
        self._done = np.zeros(S, bool)
        self._slots: List[Optional[_Slot]] = [None] * S
        self._free = collections.deque(range(S))
        self._lock = threading.Lock()
        # QoS off (default): a plain FIFO deque — bit-identical
        # admission and grant order to the pre-front-door engine.  QoS
        # on: a weighted stride scheduler with the same deque surface,
        # so every admission/requeue call site below is mode-blind.
        self._qos = qos
        self._waiting = (WeightedWaitQueue(qos) if qos is not None
                         else collections.deque())
        self._step_count = 0

        Lmax = L
        # static under jit: every paged program below compiles in the
        # selected read kernel (gather reference / fused Pallas).  The
        # fused kernel under a mesh runs per-chip via shard_map against
        # the pool's placement (tp-sharded kv heads, or the replicated
        # KH % tp hatch) — kmesh/kv_tp are compile-time constants too.
        kern = self.kernel
        kmesh = self._kernel_mesh()
        kv_tp = self._kv_tp

        def pick_next(logits, pos, done, temps, seeds, topps,
                      use_sample, use_topp):
            """One token per row from per-row logits — ONE definition so
            the arena and paged step programs can never drift (their
            greedy-parity guarantee depends on it).  Sampling folds the
            rng by absolute position, so a preempted-and-readmitted row
            regenerates identical tokens."""
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            if use_sample:              # static: greedy-only compile

                def sample_row(seed, t, tp, lg, p):
                    key = jax.random.fold_in(jax.random.key(seed), p)
                    scaled = lg.astype(jnp.float32) / jnp.maximum(
                        t, 1e-6)
                    if use_topp:        # static: no sort when unused
                        scaled = top_p_filter(scaled, tp)
                    return jax.random.categorical(key, scaled).astype(
                        jnp.int32)

                sampled = jax.vmap(sample_row)(seeds, temps, topps,
                                               logits, pos)
                nxt = jnp.where(temps > 0.0, sampled, nxt)
            if eos_id is not None:
                nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                done = done | (nxt == eos_id)
            return nxt, done

        def step_fn(variables, ck, cv, tok, pos, done, temps, seeds,
                    topps, n_ticks, use_sample, use_topp):
            """Advance every slot ``n_ticks`` tokens in ONE device call
            (a lax.scan) — each extra tick saves a host round-trip.  A slot
            that hits EOS mid-chunk freezes exactly like generate()'s
            frozen tail: it keeps stepping, fed eos.  Returns tokens
            [n_ticks, S] in emission order."""

            def one(carry, _):
                tok, pos, done, ck, cv = carry
                logits, ck, cv = model.apply(
                    variables, tok, ck, cv, pos,
                    method=TransformerLM.decode_step)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
                return (nxt, pos, done, ck, cv), nxt

            (tok, pos, done, ck, cv), toks = jax.lax.scan(
                one, (tok, pos, done, ck, cv), None, length=n_ticks)
            return toks, tok, pos, done, ck, cv

        def step_fn_paged(variables, pk, pv, tok, pos, done, tables,
                          temps, seeds, topps, n_ticks, use_sample,
                          use_topp):
            """The paged twin of ``step_fn``: decode through per-slot
            block tables against the shared pool.  Rows holding no
            blocks (free/done slots — their table rows are all SINK)
            write and read only the sink block's garbage, which their
            frozen/ignored outputs never surface."""

            def one(carry, _):
                tok, pos, done, pk, pv = carry
                logits, pk, pv = model.apply(
                    variables, tok, pk, pv, tables, pos, kernel=kern,
                    mesh=kmesh, kv_sharded=kv_tp,
                    method=TransformerLM.decode_step_paged)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
                return (nxt, pos, done, pk, pv), nxt

            (tok, pos, done, pk, pv), toks = jax.lax.scan(
                one, (tok, pos, done, pk, pv), None, length=n_ticks)
            return toks, tok, pos, done, pk, pv

        def dsa_stats(ctx, n_read, load):
            """What a tick of a model with an indexer says of itself, as
            four int32: Σ over the decode rows of the positions in
            context and of the positions whose K/V the attention read,
            the assignments the experts got, and the most any one expert
            got in one layer (``load`` [layers, X])."""
            return jnp.stack([jnp.sum(ctx), jnp.sum(n_read),
                              jnp.sum(load[0]), jnp.max(load)]
                             ).astype(jnp.int32)

        def dsa_live(done, tables):
            """The rows that decode this tick: not frozen (a finished or
            PREFILLING row is) and holding blocks (an empty slot's table
            is all sink)."""
            return ~done & jnp.any(tables != SINK_BLOCK, axis=1)

        def step_fn_paged_dsa(variables, pk, pv, tok, pos, done, tables,
                              temps, seeds, topps, n_ticks, use_sample,
                              use_topp):
            """``step_fn_paged`` of a model with an indexer: ``pk`` is an
            ``IndexedKeys``, and the tick's four counters come back after
            ``done`` (summed over the scan's ticks; the maximum load is
            the largest of them).  Rows that do not decode (``dsa_live``)
            count for nothing."""

            def one(carry, _):
                tok, pos, done, pk, pv = carry
                live = dsa_live(done, tables)
                logits, pk, pv, n_read, load = model.apply(
                    variables, tok, pk, pv, tables, pos, live,
                    method=TransformerLM.decode_step_paged_sparse)
                stats = dsa_stats(jnp.where(live, pos + 1, 0),
                                  jnp.where(live, n_read, 0), load)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
                return (nxt, pos, done, pk, pv), (nxt, stats)

            (tok, pos, done, pk, pv), (toks, stats) = jax.lax.scan(
                one, (tok, pos, done, pk, pv), None, length=n_ticks)
            stats = jnp.concatenate([jnp.sum(stats[:, :3], axis=0),
                                     jnp.max(stats[:, 3:], axis=0)])
            return toks, tok, pos, done, stats, pk, pv

        def step_fn_paged_ssm(variables, pk, pv, tok, pos, done, tables,
                              temps, seeds, topps, n_ticks, use_sample,
                              use_topp):
            """``step_fn_paged`` of a model with state-space layers: ``pk``
            is a ``HybridCache`` whose state arenas are indexed by the
            slot, and the counters (``HYBRID_COUNTERS``, summed over the
            scan's ticks, the last the largest of them) come back after
            ``done``.  Only the rows that decode (``dsa_live``) advance
            their state."""

            def one(carry, _):
                tok, pos, done, pk, pv = carry
                logits, pk, pv, stats = model.apply(
                    variables, tok, pk, pv, tables, pos,
                    dsa_live(done, tables), kernel=kern,
                    method=HybridLM.decode_step_paged_ssm)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
                return (nxt, pos, done, pk, pv), (nxt, stats)

            (tok, pos, done, pk, pv), (toks, stats) = jax.lax.scan(
                one, (tok, pos, done, pk, pv), None, length=n_ticks)
            stats = jnp.concatenate([jnp.sum(stats[:, :-1], axis=0),
                                     jnp.max(stats[:, -1:], axis=0)])
            return toks, tok, pos, done, stats, pk, pv

        # one compiled program per (n_ticks, sampled) pair — n_ticks is
        # bounded by ticks_per_step, so the cache stays small
        self._step_cache: Dict[Tuple[int, bool, bool],
                               Callable] = {}

        def get_step(n: int, sampled: bool,
                     use_topp: bool = False) -> Callable:
            key = (n, sampled, use_topp)
            if key not in self._step_cache:
                # cache miss = a program variant XLA must build; in
                # steady state this event never fires again (the trace
                # timeline makes a late one — a retrace — stand out)
                self.telemetry.jit_build("step", key)
                fn = step_fn_paged if self.paged else step_fn
                if self._dsa:
                    fn = step_fn_paged_dsa
                elif self._ssm:
                    fn = step_fn_paged_ssm
                self._step_cache[key] = _WeightedJit(
                    partial(fn, n_ticks=n, use_sample=sampled,
                            use_topp=use_topp),
                    (variables,), donate_argnums=(0, 1))
            return self._step_cache[key]

        self._get_step = get_step

        def paged_admit_fn(variables, pk, pv, suffixes, slens, tables,
                           pos):
            """Paged admission prefill: each row's (unshared) prompt
            suffix runs block-causally against pool K/V its table
            already maps — prefix-matched blocks behind ``pos`` read as
            if this row had prefilled them itself.  Monolithic
            admission IS one maximal chunk, so this is just
            ``prefill_chunk_paged``: writes limited to ``pos + slens``
            (suffix padding writes nothing), padding ROWS carry
            all-sink tables, and the return is each row's
            last-real-position logits (the head applied to [kb, 1, H]
            — never the [kb, sb, V] cube)."""
            return model.apply(
                variables, suffixes, pk, pv, tables, pos, slens,
                kernel=kern, mesh=kmesh, kv_sharded=kv_tp,
                method=TransformerLM.prefill_chunk_paged)

        def paged_admit_dsa_fn(variables, pk, pv, suffixes, slens,
                                tables, pos):
            """``paged_admit_fn`` of a model with an indexer."""
            last, pk, pv, _, _ = model.apply(
                variables, suffixes, pk, pv, tables, pos, slens,
                method=TransformerLM.prefill_chunk_paged_sparse)
            return last, pk, pv

        self._paged_admit = _WeightedJit(
            paged_admit_dsa_fn if self._dsa else paged_admit_fn,
            (variables,), donate_argnums=(0, 1))

        def prefill_fn(variables, prompts, plens):
            """Batched joiner prefill: [k, Pb] prompts in ONE forward
            (bursts amortise the admission cost k-fold); returns each
            row's last-real-position logits + stacked K/V."""
            logits, ks, vs = model.apply(variables, prompts,
                                         method=TransformerLM.prefill)
            last = jnp.take_along_axis(
                logits, (plens - 1)[:, None, None], axis=1)[:, 0]
            return last, ks, vs

        self._prefill = _WeightedJit(prefill_fn, (variables,))

        def insert_fn(ck, cv, ks, vs, slot):
            ck = jax.lax.dynamic_update_slice(
                ck, ks.astype(ck.dtype), (0, slot, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, vs.astype(cv.dtype), (0, slot, 0, 0, 0))
            return ck, cv

        self._insert = jax.jit(insert_fn, donate_argnums=(0, 1))

        # ---- fused chunked tick (decode + prefill chunks, ONE call) ----
        S_arena = S

        def fused_fn(variables, ck, cv, tok, pos, done, temps, seeds,
                     topps, ctoks, cpos, clens, cslots, ctemps, cseeds,
                     ctopps, with_decode, use_sample, use_topp,
                     read_len):
            """One budget-bounded tick: decode EVERY slot once (bitwise
            the unfused 1-tick step — PREFILLING rows ride along frozen,
            their one garbage write at the fill frontier is overwritten
            by their own chunk below, in this same program), then run
            the tick's prefill chunks block-causally at their fill
            offsets via ``prefill_chunk`` on a compact ``[kb,
            read_len]`` cache window (gathered/scattered exactly like
            ``_prefix_admit``: padding rows carry the out-of-range slot
            index S — reads clamp, writes drop).  Returns the decode
            picks AND each chunk row's next-token pick: a prompt's
            first token is chosen the tick its last chunk lands, with
            the same rng position-fold as ``_pick_first``."""
            if with_decode:
                logits, ck, cv = model.apply(
                    variables, tok, ck, cv, pos,
                    method=TransformerLM.decode_step)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
            else:
                nxt = tok
            read_idx = jnp.minimum(cslots, S_arena - 1)
            rows_k = jnp.take(ck, read_idx, axis=1)[:, :, :read_len]
            rows_v = jnp.take(cv, read_idx, axis=1)[:, :, :read_len]
            clog, rows_k, rows_v = model.apply(
                variables, ctoks, rows_k, rows_v, cpos, clens,
                method=TransformerLM.prefill_chunk)
            ck = ck.at[:, cslots, :read_len].set(
                rows_k.astype(ck.dtype), mode="drop")
            cv = cv.at[:, cslots, :read_len].set(
                rows_v.astype(cv.dtype), mode="drop")
            cnxt, _ = pick_next(
                clog, cpos + clens - 1,
                jnp.zeros(clens.shape, jnp.bool_), ctemps, cseeds,
                ctopps, use_sample, use_topp)
            return nxt, pos, done, cnxt, ck, cv

        def fused_paged_fn(variables, pk, pv, tok, pos, done, tables,
                           temps, seeds, topps, ctoks, cpos, clens,
                           ctabs, ctemps, cseeds, ctopps, with_decode,
                           use_sample, use_topp):
            """The paged twin: chunks scatter through NARROW per-row
            tables (``ctabs`` [kb, Mb], host-sliced to the fill
            frontier, bucketed) — ``prefill_chunk_paged`` limits writes
            to ``cpos + clens`` so padding columns write nothing and
            the narrow window can never clamp a stray write into a
            live block.  Padding rows carry all-sink tables."""
            if with_decode:
                logits, pk, pv = model.apply(
                    variables, tok, pk, pv, tables, pos, kernel=kern,
                    mesh=kmesh, kv_sharded=kv_tp,
                    method=TransformerLM.decode_step_paged)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
            else:
                nxt = tok
            # the chunk's first WRITE to the pool needs no value that
            # the decode rows' last READ of it produces, so nothing
            # orders the two and a compiler may keep a copy of the
            # pool for the read (XLA:CPU does).  Ordering them by a
            # value does: token ids are >= 0, so this adds 0.
            wpos = (cpos + jnp.minimum(jnp.min(nxt), 0) if with_decode
                    else cpos)
            clog, pk, pv = model.apply(
                variables, ctoks, pk, pv, ctabs, wpos, clens,
                kernel=kern, mesh=kmesh, kv_sharded=kv_tp,
                method=TransformerLM.prefill_chunk_paged)
            cnxt, _ = pick_next(
                clog, cpos + clens - 1,
                jnp.zeros(clens.shape, jnp.bool_), ctemps, cseeds,
                ctopps, use_sample, use_topp)
            return nxt, pos, done, cnxt, pk, pv

        def fused_paged_dsa_fn(variables, pk, pv, tok, pos, done, tables,
                               temps, seeds, topps, ctoks, cpos, clens,
                               ctabs, ctemps, cseeds, ctopps, with_decode,
                               use_sample, use_topp):
            """``fused_paged_fn`` of a model with an indexer: the same
            tick through the ``*_paged_sparse`` methods, ``pk`` an
            ``IndexedKeys``, and the tick's four counters returned after
            ``cnxt``.  The decode rows that are live and the chunk rows
            that are real (a padding row's table is all sink) are what
            the counters count."""
            ctx = n_read = jnp.zeros((1,), jnp.int32)
            load = 0
            if with_decode:
                live = dsa_live(done, tables)
                logits, pk, pv, n_read, load = model.apply(
                    variables, tok, pk, pv, tables, pos, live,
                    method=TransformerLM.decode_step_paged_sparse)
                ctx = jnp.where(live, pos + 1, 0)
                n_read = jnp.where(live, n_read, 0)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
            else:
                nxt = tok
            wpos = (cpos + jnp.minimum(jnp.min(nxt), 0) if with_decode
                    else cpos)          # orders the writes: fused_paged_fn
            real = jnp.any(ctabs != SINK_BLOCK, axis=1)
            clog, pk, pv, _, cload = model.apply(
                variables, ctoks, pk, pv, ctabs, wpos, clens, real,
                method=TransformerLM.prefill_chunk_paged_sparse)
            cnxt, _ = pick_next(
                clog, cpos + clens - 1,
                jnp.zeros(clens.shape, jnp.bool_), ctemps, cseeds,
                ctopps, use_sample, use_topp)
            return (nxt, pos, done, cnxt,
                    dsa_stats(ctx, n_read, load + cload), pk, pv)

        def fused_paged_ssm_fn(variables, pk, pv, tok, pos, done, tables,
                               temps, seeds, topps, ctoks, cpos, clens,
                               ctabs, ctemps, cseeds, ctopps, cslots,
                               with_decode, use_sample, use_topp):
            """``fused_paged_fn`` of a model with state-space layers: ``pk``
            a ``HybridCache``; the chunk rows name their slots (``cslots``,
            after the llama operands; a padding row's is out of range) so
            that each reads its own state and writes it back; the tick's
            counters come back after ``cnxt``.  A PREFILLING row is frozen
            in the decode half, so its state moves in the chunk half
            alone."""
            stats = jnp.zeros((len(HYBRID_COUNTERS),), jnp.int32)
            if with_decode:
                logits, pk, pv, stats = model.apply(
                    variables, tok, pk, pv, tables, pos,
                    dsa_live(done, tables), kernel=kern,
                    method=HybridLM.decode_step_paged_ssm)
                nxt, done = pick_next(logits, pos, done, temps, seeds,
                                      topps, use_sample, use_topp)
                pos = jnp.minimum(pos + 1, Lmax - 1)
            else:
                nxt = tok
            wpos = (cpos + jnp.minimum(jnp.min(nxt), 0) if with_decode
                    else cpos)          # orders the writes: fused_paged_fn
            clog, pk, pv, cstats = model.apply(
                variables, ctoks, pk, pv, ctabs, wpos, clens, cslots,
                kernel=kern, method=HybridLM.prefill_chunk_paged_ssm)
            cnxt, _ = pick_next(
                clog, cpos + clens - 1,
                jnp.zeros(clens.shape, jnp.bool_), ctemps, cseeds,
                ctopps, use_sample, use_topp)
            stats = jnp.concatenate([stats[:-1] + cstats[:-1],
                                     jnp.maximum(stats[-1:], cstats[-1:])])
            return nxt, pos, done, cnxt, stats, pk, pv

        # one program per (with_decode, sampled, topp, read_len) —
        # read_len only varies on the arena path (O(log L) buckets)
        self._fused_cache: Dict[Tuple[bool, bool, bool, int],
                                Callable] = {}

        def get_fused(with_decode: bool, sampled: bool, use_topp: bool,
                      read_len: int = 0) -> Callable:
            key = (with_decode, sampled, use_topp, read_len)
            if key not in self._fused_cache:
                self.telemetry.jit_build("fused", key)
                if self.paged:
                    fn = partial(fused_paged_dsa_fn if self._dsa
                                 else fused_paged_ssm_fn if self._ssm
                                 else fused_paged_fn,
                                 with_decode=with_decode,
                                 use_sample=sampled, use_topp=use_topp)
                else:
                    fn = partial(fused_fn, with_decode=with_decode,
                                 use_sample=sampled, use_topp=use_topp,
                                 read_len=read_len)
                self._fused_cache[key] = _WeightedJit(
                    fn, (variables,), donate_argnums=(0, 1))
            return self._fused_cache[key]

        self._get_fused = get_fused

        if draft_model is not None:
            self._init_speculative(cdtype)

        # ---- prefix caching (shared system prompts) --------------------
        # register_prefix() prefills a prompt PREFIX once; requests that
        # name it splice the stored K/V and prefill only their suffix —
        # against the spliced cache, via the same block-causal decode_k
        # the speculative verify uses (bitwise = running the full
        # concatenated prompt).
        self._prefixes: Dict[int, tuple] = {}
        self._next_prefix_id = 0

        def _prefix_admit_for(m, v, want_logits):
            def fn(v, ck, cv, pks, pvs, suffixes, suffix_lens, slots):
                """Splice a stored prefix [layers, 1, P, H, D] into kb
                slots and run their suffixes through decode_k against it
                in ONE forward — a burst naming the same system prompt
                (the feature's primary workload) costs one device call,
                like the plain path's bucketed prefill.  The row count
                is padded to a power of two by the caller (bounded
                compile count, like _admit's kb); padding rows carry the
                OUT-OF-RANGE slot index S — their reads clamp and their
                scatter-back is dropped (mode='drop'), so they touch no
                real slot.  Real slots must be distinct (popped from the
                free list)."""
                P = pks.shape[2]
                kb = suffixes.shape[0]
                read_idx = jnp.minimum(slots, ck.shape[1] - 1)
                rows_k = jnp.take(ck, read_idx, axis=1)
                rows_v = jnp.take(cv, read_idx, axis=1)
                pref_k = jnp.broadcast_to(
                    pks, (pks.shape[0], kb) + pks.shape[2:])
                pref_v = jnp.broadcast_to(
                    pvs, (pvs.shape[0], kb) + pvs.shape[2:])
                rows_k = jax.lax.dynamic_update_slice(
                    rows_k, pref_k.astype(rows_k.dtype), (0, 0, 0, 0, 0))
                rows_v = jax.lax.dynamic_update_slice(
                    rows_v, pref_v.astype(rows_v.dtype), (0, 0, 0, 0, 0))
                # the suffix is ONE chunk at offset P: prefill_chunk is
                # the block-causal decode_k forward this path always
                # ran, minus the [kb, sb, V] logits cube (the head only
                # touches each row's last real position)
                last, rows_k, rows_v = m.apply(
                    v, suffixes, rows_k, rows_v,
                    jnp.full((kb,), P, jnp.int32), suffix_lens,
                    method=TransformerLM.prefill_chunk)
                ck = ck.at[:, slots].set(rows_k.astype(ck.dtype),
                                         mode="drop")
                cv = cv.at[:, slots].set(rows_v.astype(cv.dtype),
                                         mode="drop")
                if not want_logits:
                    return None, ck, cv
                return last, ck, cv

            return _WeightedJit(fn, (v,), donate_argnums=(0, 1))

        self._prefix_admit = _prefix_admit_for(model, variables, True)
        if self.draft_model is not None:
            self._draft_prefix_admit = _prefix_admit_for(
                self.draft_model, self._draft_variables, False)

        self._register_engine_gauges()

    def _register_engine_gauges(self) -> None:
        """Scrape-time gauges over engine/pool state: nothing is
        updated per tick — each callback reads the live value when
        /metrics is actually scraped, under the same lock its mutators
        hold (``n_waiting`` -> engine lock, pool fields -> pool lock),
        so a scrape can never see a torn value."""
        m = self.telemetry.metrics
        m.gauge("zoo_engine_queue_depth",
                "requests waiting for a slot", fn=lambda: self.n_waiting)
        # pre-registered (not lazily on first shed) so dashboards see
        # the stable zero whether or not any deadline ever expires
        m.counter("zoo_engine_deadline_admission_sheds_total",
                  "requests shed at admission because their deadline "
                  "had already passed (never reached prefill)")
        m.gauge("zoo_engine_active_slots",
                "resident requests (decode + prefilling)",
                fn=lambda: self.n_active)
        m.gauge("zoo_engine_peak_resident",
                "max co-resident requests observed",
                fn=lambda: self._peak_resident)
        # storage economics: constant per engine config, exported so a
        # scrape can compute tokens/sec/HBM-byte without knowing the
        # model geometry (int8 pools halve this vs bf16)
        m.gauge("zoo_engine_kv_bytes_per_token",
                "HBM bytes one cached token position costs across all "
                "layers and tenants",
                fn=lambda: self._kv_bytes_per_token)
        if self.paged:
            m.gauge("zoo_engine_kv_pool_bytes",
                    "total HBM bytes of the paged KV pools (target + "
                    "draft, all blocks)",
                    fn=lambda: (
                        self._per_block_bytes * self._pool.n_blocks
                        + (self._draft_per_block_bytes
                           * self._dpool.n_blocks
                           if self._dpool is not None else 0)))
        if self.chunked:
            def _budget_util():
                denom = self._budget_ticks * self.tick_token_budget
                return (self._budget_tokens_used / denom) if denom \
                    else 0.0

            m.gauge("zoo_engine_budget_utilization",
                    "mean filled fraction of the tick token budget",
                    fn=_budget_util)
            m.gauge("zoo_engine_prefill_stall_ticks_total",
                    "ticks whose budget left no room for any chunk",
                    fn=lambda: self._prefill_stall_ticks,
                    kind="counter")
        if self.paged:
            def _pool_read(key):
                def read():
                    with self._pool_lock:
                        return self._pool.metrics()[key]
                return read

            for key, name, kind, hlp in (
                    ("free_blocks", "zoo_engine_free_blocks", "gauge",
                     "pool blocks on the free list"),
                    ("cached_blocks", "zoo_engine_cached_blocks",
                     "gauge",
                     "unreferenced blocks parked in the prefix LRU"),
                    ("referenced_blocks", "zoo_engine_referenced_blocks",
                     "gauge", "blocks held by live requests"),
                    ("occupancy", "zoo_engine_pool_occupancy", "gauge",
                     "referenced fraction of non-sink blocks"),
                    ("prefix_hit_rate", "zoo_engine_prefix_hit_rate",
                     "gauge", "prefix-cache block hits / queries"),
                    ("prefix_queries", "zoo_engine_prefix_queries_total",
                     "counter", "prompt blocks offered to lookup()"),
                    ("prefix_hits", "zoo_engine_prefix_hits_total",
                     "counter", "prompt blocks answered from the index"),
                    ("evictions", "zoo_engine_pool_evictions_total",
                     "counter", "LRU evictions of cached blocks"),
                    ("alloc_failures",
                     "zoo_engine_pool_alloc_failures_total", "counter",
                     "allocate() calls the pool could not serve")):
                m.gauge(name, hlp, fn=_pool_read(key), kind=kind)
            # elastic pool + disaggregation surface: registered for
            # EVERY paged engine (zero until the features engage) so
            # dashboards and the doc-drift guard see stable names
            m.gauge("zoo_engine_pool_n_blocks",
                    "current per-tenant pool size in blocks (moves "
                    "only under elastic_pool)",
                    fn=lambda: self._pool.n_blocks)
            m.gauge("zoo_engine_pool_resize_total",
                    "applied elastic pool resizes (grow + shrink)",
                    fn=lambda: self._pool_resizes, kind="counter")
            m.gauge("zoo_engine_pool_resize_clamped_total",
                    "resize requests clamped at the eviction boundary "
                    "or the floor/ceiling",
                    fn=lambda: self._pool_resize_clamps,
                    kind="counter")
            m.gauge("zoo_engine_handoffs_out_total",
                    "prefilled rows exported to a decode replica",
                    fn=lambda: self._handoffs_out, kind="counter")
            m.gauge("zoo_engine_handoffs_in_total",
                    "prefilled rows adopted from a prefill replica",
                    fn=lambda: self._handoffs_in, kind="counter")
            # tiered-KV surface (serving/kv_store.py): same contract —
            # stable names for every paged engine, zero with the host
            # store off
            m.gauge("zoo_engine_kv_spill_chains_total",
                    "evicted blocks accepted by the host KV store",
                    fn=lambda: self._kv_spills, kind="counter")
            m.gauge("zoo_engine_kv_spill_bytes_total",
                    "KV bytes spilled to the host store",
                    fn=lambda: self._kv_spill_bytes, kind="counter")
            m.gauge("zoo_engine_kv_readmit_chains_total",
                    "host-store chains adopted back into the pool at "
                    "admission",
                    fn=lambda: self._kv_readmits, kind="counter")
            m.gauge("zoo_engine_kv_readmit_tokens_saved_total",
                    "prompt tokens served host->HBM instead of "
                    "re-prefilled",
                    fn=lambda: self._kv_readmit_tokens_saved,
                    kind="counter")
            m.gauge("zoo_engine_kv_store_bytes",
                    "host KV store occupancy in bytes",
                    fn=lambda: (self._kv_store.occupancy_bytes
                                if self._kv_store is not None else 0))
            if self._dpool is not None:
                def _dpool_read(key):
                    def read():
                        with self._pool_lock:
                            return self._dpool.metrics()[key]
                    return read

                for key, name, kind, hlp in (
                        ("free_blocks", "zoo_engine_draft_free_blocks",
                         "gauge", "draft-pool blocks on the free list"),
                        ("referenced_blocks",
                         "zoo_engine_draft_referenced_blocks", "gauge",
                         "draft-pool blocks held by live requests"),
                        ("occupancy", "zoo_engine_draft_pool_occupancy",
                         "gauge",
                         "referenced fraction of the draft pool"),
                        ("alloc_failures",
                         "zoo_engine_draft_pool_alloc_failures_total",
                         "counter", "draft-pool allocate() calls it "
                         "could not serve")):
                    m.gauge(name, hlp, fn=_dpool_read(key), kind=kind)

    def _init_speculative(self, cdtype):
        """Draft cache + the jitted spec-round programs.  One round per
        device call: draft proposes k per slot (k+1 cached feeds), the
        target verifies all slots' proposals in ONE decode_k forward,
        each slot advances by its own accepted count (per-row pointers).
        Arena mode gives the draft its own [layers, S, L, DH, DD] strip;
        paged mode addresses draft K/V through the second pool tenant's
        block tables — the SAME round structure, with verify writing its
        k+1 positions through the paged write path and rejection rolling
        the pointers back (``pos + n_emit``, never a block copy: entries
        past the new pointer are dead and the next round overwrites
        them in-place before anything attends that far)."""
        draft, model = self.draft_model, self.model
        both = (self._variables, self._draft_variables)
        S, L, k = self._S, self._L, self._spec_k
        eos_id = self.eos_id
        kern = self.kernel
        kmesh = self._kernel_mesh()
        kv_tp, dkv_tp = self._kv_tp, self._dkv_tp
        self._dpos = np.zeros(S, np.int32)

        if self.paged:
            def spec_step_paged(variables, dvars, pk, pv, dpk, dpv, tok,
                                pos, dpos, done, tables, dtables):
                # draft: k proposals via k+1 greedy cached feeds through
                # the DRAFT tenant's tables (the extra feed writes
                # d_{k-1}'s KV so a full-acceptance round leaves the
                # draft pages complete — models/speculative.py)
                def dstep(c, _):
                    t, dpk, dpv, p = c
                    lg, dpk, dpv = draft.apply(
                        dvars, t, dpk, dpv, dtables, p, kernel=kern,
                        mesh=kmesh, kv_sharded=dkv_tp,
                        method=TransformerLM.decode_step_paged)
                    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                    return (nxt, dpk, dpv, p + 1), nxt

                (_, dpk, dpv, _), d = jax.lax.scan(
                    dstep, (tok, dpk, dpv, dpos), None, length=k + 1)
                d = d.T[:, :k]                          # [S, k]

                # verify: k+1 positions written through the paged path
                # (rows with table rows all SINK — free/frozen — write
                # only sink-block garbage)
                inputs = jnp.concatenate([tok[:, None], d], axis=1)
                logits, pk, pv = model.apply(
                    variables, inputs, pk, pv, tables, pos,
                    kernel=kern, mesh=kmesh, kv_sharded=kv_tp,
                    method=TransformerLM.verify_step_paged)
                t, n_emit, new_tok, done = accept_proposals(
                    logits, d, tok, done, k=k, eos_id=eos_id)
                # pointer rollback IS the advance: rejected positions
                # stay physically written but unreachable (< pos never
                # attends past pos+j), and the next round re-writes them
                pos = jnp.minimum(pos + n_emit, L - 1)
                dpos = jnp.minimum(dpos + n_emit, L - 1)
                # [k+1, S] to match the plain step's emission order
                return (t.T, n_emit, new_tok, pos, dpos, done,
                        pk, pv, dpk, dpv)

            self._spec_step_paged = _WeightedJit(
                spec_step_paged, both, donate_argnums=(0, 1, 2, 3))

            def draft_paged_admit_fn(dvars, dpk, dpv, suffixes, slens,
                                     dtables, pos):
                """Draft-tenant admission prefill: the same grid the
                target's ``_paged_admit`` ran, against the draft pool —
                logits are discarded (only the target picks tokens)."""
                _, dpk, dpv = draft.apply(
                    dvars, suffixes, dpk, dpv, dtables, pos, slens,
                    kernel=kern, mesh=kmesh, kv_sharded=dkv_tp,
                    method=TransformerLM.prefill_chunk_paged)
                return dpk, dpv

            self._draft_paged_admit = _WeightedJit(
                draft_paged_admit_fn, both[1:], donate_argnums=(0, 1))
        else:
            DH = getattr(draft, "kv_heads", draft.num_heads)
            DD = draft.head_size
            dkv_sh = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                dkv_sh = NamedSharding(
                    self.mesh, P(None, None, None, "tp", None)
                    if self._dkv_tp else P())
            self._dck = jnp.zeros((draft.num_layers, S, L, DH, DD),
                                  cdtype, device=dkv_sh)
            self._dcv = jnp.zeros((draft.num_layers, S, L, DH, DD),
                                  cdtype, device=dkv_sh)

            def spec_step(variables, dvars, ck, cv, dck, dcv, tok, pos,
                          dpos, done):
                # draft: k proposals via k+1 greedy cached feeds (the
                # extra feed writes d_{k-1}'s KV so a full-acceptance
                # round leaves the draft cache complete)
                def dstep(c, _):
                    t, dck, dcv, p = c
                    lg, dck, dcv = draft.apply(
                        dvars, t, dck, dcv, p,
                        method=TransformerLM.decode_step)
                    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                    return (nxt, dck, dcv, p + 1), nxt

                (_, dck, dcv, _), d = jax.lax.scan(
                    dstep, (tok, dck, dcv, dpos), None, length=k + 1)
                d = d.T[:, :k]                          # [S, k]

                inputs = jnp.concatenate([tok[:, None], d], axis=1)
                logits, ck, cv = model.apply(
                    variables, inputs, ck, cv, pos,
                    method=TransformerLM.verify_step)
                t, n_emit, new_tok, done = accept_proposals(
                    logits, d, tok, done, k=k, eos_id=eos_id)
                pos = jnp.minimum(pos + n_emit, L - 1)
                dpos = jnp.minimum(dpos + n_emit, L - 1)
                # [k+1, S] to match the plain step's emission order
                return (t.T, n_emit, new_tok, pos, dpos, done,
                        ck, cv, dck, dcv)

            self._spec_step = _WeightedJit(
                spec_step, both, donate_argnums=(0, 1, 2, 3))

            def draft_prefill_fn(dvars, prompts):
                _, ks, vs = draft.apply(dvars, prompts,
                                        method=TransformerLM.prefill)
                return ks, vs

            self._draft_prefill = _WeightedJit(draft_prefill_fn,
                                               both[1:])

        if not self.chunked:
            return

        # ---- spec chunk program (greedy-only, both tenants) -----------
        # A spec tick with PREFILLING rows runs TWO device calls under
        # one token budget: the spec round above for decode rows, then
        # this chunk program, which lands prompt chunks in BOTH models'
        # caches (the draft must have the prompt's K/V before it can
        # propose) and picks each completing prompt's first token from
        # the TARGET logits.  Fusing the two would square the compile
        # grid (verify shapes x chunk shapes) to save zero host syncs —
        # both results are consumed by the same host step.
        if self.paged:
            def spec_chunk_paged_fn(variables, dvars, pk, pv, dpk, dpv,
                                    ctoks, cpos, clens, ctabs, dctabs):
                clog, pk, pv = model.apply(
                    variables, ctoks, pk, pv, ctabs, cpos, clens,
                    kernel=kern, mesh=kmesh, kv_sharded=kv_tp,
                    method=TransformerLM.prefill_chunk_paged)
                _, dpk, dpv = draft.apply(
                    dvars, ctoks, dpk, dpv, dctabs, cpos, clens,
                    kernel=kern, mesh=kmesh, kv_sharded=dkv_tp,
                    method=TransformerLM.prefill_chunk_paged)
                # greedy-only by the submit() contract, so the first
                # pick is plain argmax (pick_next minus sampling/eos —
                # _record_token handles an eos first token host-side)
                cnxt = jnp.argmax(clog, -1).astype(jnp.int32)
                return cnxt, pk, pv, dpk, dpv

            self._spec_chunk_paged = _WeightedJit(
                spec_chunk_paged_fn, both, donate_argnums=(0, 1, 2, 3))
        else:
            def spec_chunk_fn(variables, dvars, ck, cv, dck, dcv, ctoks,
                              cpos, clens, cslots, read_len):
                read_idx = jnp.minimum(cslots, S - 1)
                rows_k = jnp.take(ck, read_idx, axis=1)[:, :, :read_len]
                rows_v = jnp.take(cv, read_idx, axis=1)[:, :, :read_len]
                clog, rows_k, rows_v = model.apply(
                    variables, ctoks, rows_k, rows_v, cpos, clens,
                    method=TransformerLM.prefill_chunk)
                ck = ck.at[:, cslots, :read_len].set(
                    rows_k.astype(ck.dtype), mode="drop")
                cv = cv.at[:, cslots, :read_len].set(
                    rows_v.astype(cv.dtype), mode="drop")
                drows_k = jnp.take(dck, read_idx,
                                   axis=1)[:, :, :read_len]
                drows_v = jnp.take(dcv, read_idx,
                                   axis=1)[:, :, :read_len]
                _, drows_k, drows_v = draft.apply(
                    dvars, ctoks, drows_k, drows_v, cpos, clens,
                    method=TransformerLM.prefill_chunk)
                dck = dck.at[:, cslots, :read_len].set(
                    drows_k.astype(dck.dtype), mode="drop")
                dcv = dcv.at[:, cslots, :read_len].set(
                    drows_v.astype(dcv.dtype), mode="drop")
                cnxt = jnp.argmax(clog, -1).astype(jnp.int32)
                return cnxt, ck, cv, dck, dcv

            self._spec_chunk = _WeightedJit(
                spec_chunk_fn, both, static_argnames=("read_len",),
                donate_argnums=(0, 1, 2, 3))

    def _kernel_mesh(self):
        """The mesh the fused kernel runs under ``shard_map`` on: only a
        mesh of several devices needs the per-chip wrapper — on one
        device the kernel is called directly."""
        if self.kernel == "fused" and self.mesh is not None \
                and self.mesh.size > 1:
            return self.mesh
        return None

    def _hbm_stats(self) -> Optional[Dict[str, int]]:
        """``bytes_limit`` / ``bytes_in_use`` of the engine's OWN
        devices, reduced to the tightest chip (least limit, most in
        use).  ``None`` on the CPU backend, which has no device memory
        to report; on any other platform a device that reports nothing
        is an error — sizing a pool "as if" a fraction had been
        honoured would hide that the chip was never asked."""
        if self._devices[0].platform == "cpu":
            return None
        stats = [d.memory_stats() or {} for d in self._devices]
        if not all(st.get("bytes_limit") for st in stats):
            raise RuntimeError(
                f"{self._devices[0].platform} device(s) "
                f"{[d.id for d in self._devices]} report no "
                f"memory_stats()['bytes_limit']; hbm_fraction / "
                f"elastic_pool need it — pass n_blocks explicitly")
        return {"bytes_limit": min(int(st["bytes_limit"])
                                   for st in stats),
                "bytes_in_use": max(int(st.get("bytes_in_use", 0))
                                    for st in stats)}

    @staticmethod
    def _kv_kernels_tp_sharded(shardings) -> bool:
        """Do the chosen rules put 'tp' on the k/v projection outputs?
        Inspected from the sharding tree itself so the arena layout can
        never drift from what the kernels actually emit."""
        import jax as _jax

        for path, sh in _jax.tree_util.tree_flatten_with_path(
                shardings)[0]:
            keys = [str(getattr(p, "key", "")) for p in path]
            if "kernel" in keys and any(k in ("key", "value")
                                        for k in keys):
                spec = getattr(sh, "spec", ())
                if any(ax == "tp" or (isinstance(ax, tuple)
                                      and "tp" in ax) for ax in spec):
                    return True
        return False

    # ---- submission ---------------------------------------------------

    def capacity_report(self) -> dict:
        """Concrete arena economics (what GQA/cache_dtype actually buy):
        bytes per slot, total arena bytes, and the multiplier vs a
        full-head model-dtype arena of the same geometry."""
        m = self.model
        if self.paged:
            # pool layout is [layers, N, KH, bs, D] (head-major for
            # the fused kernel); int8 pools are QuantKV, so bill from
            # the init-time ledger rather than re-deriving off dtypes
            H = getattr(self.model, "kv_heads", self.model.num_heads)
            per_block = self._per_block_bytes
            per_slot_max = per_block * self._M
            arena_equiv = (per_block // self._bs) * self._L * self._S
            return {
                "mode": "paged",
                "slots": self._S,
                "cache_len": self._L,
                "kv_heads": H,
                "cache_dtype": str(
                    jax.tree_util.tree_leaves(self._pk)[0].dtype),
                "kv_dtype": self.kv_dtype,
                "kernel": self.kernel,
                "kv_bytes_per_token": self._kv_bytes_per_token,
                "block_size": self._bs,
                "n_blocks": self._pool.n_blocks,
                "blocks_per_row_max": self._M,
                "bytes_per_block": per_block,
                "bytes_per_slot": per_slot_max,   # worst case; actual
                # residency is pay-as-you-grow + shared prefixes
                "arena_bytes": per_block * self._pool.n_blocks,
                "arena_equivalent_bytes": arena_equiv,
                # per-chip pressure follows the pool's ACTUAL sharding:
                # tp shards it over the kv-heads dim, a narrow-KV
                # (MQA/GQA) override replicates it
                "tp": (int(self.mesh.shape.get("tp", 1))
                       if self.mesh is not None else 1),
                "arena_bytes_per_chip":
                    per_block * self._pool.n_blocks
                    // (self._tp if self._kv_tp else 1),
                # the draft tenant's pool (0 without a draft model);
                # pinned prefixes live IN the pools for both tenants
                "draft_arena_bytes": (
                    self._draft_per_block_bytes * self._dpool.n_blocks
                    if self._dpool is not None else 0),
                "draft_n_blocks": (self._dpool.n_blocks
                                   if self._dpool is not None else 0),
                "prefix_bytes": 0,
            }
        H_full = m.num_heads
        H = self._ck.shape[3]
        D = self._ck.shape[4]
        per_slot = 2 * m.num_layers * self._L * H * D * \
            self._ck.dtype.itemsize
        full = 2 * m.num_layers * self._L * H_full * D * \
            jnp.dtype(m.dtype).itemsize
        tp = int(self.mesh.shape.get("tp", 1)) if self.mesh is not None \
            else 1
        # per-chip pressure follows the arena's ACTUAL sharding — a
        # narrow-KV override replicates it, so /tp would overstate
        spec = getattr(self._ck.sharding, "spec", None)
        arena_tp = tp if spec is not None and len(spec) > 3 \
            and spec[3] == "tp" else 1
        return {
            "slots": self._S,
            "cache_len": self._L,
            "kv_heads": H,
            "cache_dtype": str(self._ck.dtype),
            "bytes_per_slot": per_slot,
            "arena_bytes": per_slot * self._S,
            # tp shards the arena over chips: HBM pressure per chip is
            # arena/tp, so tp slots multiply like a narrower dtype does
            "tp": tp,
            "arena_bytes_per_chip": per_slot * self._S // arena_tp,
            "capacity_multiplier_vs_mha_model_dtype":
                round(full / per_slot, 2),
            # HBM the speculative/prefix features pin beyond the arena
            "draft_arena_bytes": (
                2 * int(np.prod(self._dck.shape))
                * self._dck.dtype.itemsize
                if self.draft_model is not None else 0),
            "prefix_bytes": sum(
                int(np.prod(e.shape)) * e.dtype.itemsize
                for entry in self._prefix_snapshot()
                for e in (entry[0], entry[1], entry[3], entry[4])
                if e is not None),
        }

    def _prefix_snapshot(self):
        # register/unregister mutate the dict from client threads;
        # iterate a locked copy
        with self._lock:
            return list(self._prefixes.values())

    @property
    def n_active(self) -> int:
        return self._S - len(self._free)

    @property
    def n_waiting(self) -> int:
        with self._lock:
            return len(self._waiting)

    def register_prefix(self, tokens: np.ndarray) -> int:
        """Prefill a shared prompt PREFIX (system prompt) once; returns
        an id for ``submit(..., prefix=id)``.  Requests then ship only
        their suffix: admission splices the stored K/V and runs the
        suffix against it (block-causal decode_k — bitwise what the
        full concatenated prompt would have produced)."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 1 or len(tokens) < 1:
            raise ValueError("prefix must be a non-empty 1-D int32 array")
        P = len(tokens)
        if P >= self.max_prompt_width:
            raise ValueError(
                f"prefix length {P} leaves no room for a suffix inside "
                f"max prompt width {self.max_prompt_width}")
        if self._ssm:
            raise ValueError(
                "register_prefix: a model with state-space layers shares "
                "no prefix (its state is a slot's, not a block's); "
                "docs/serving.md")
        if self.paged:
            return self._register_prefix_paged(tokens)
        _, ks, vs = self.model.apply(self._variables,
                                     jnp.asarray(tokens[None], jnp.int32),
                                     method=TransformerLM.prefill)
        entry = [jax.device_put(ks), jax.device_put(vs), P, None, None]
        if self.draft_model is not None:
            _, dks, dvs = self.draft_model.apply(
                self._draft_variables,
                jnp.asarray(tokens[None], jnp.int32),
                method=TransformerLM.prefill)
            entry[3], entry[4] = jax.device_put(dks), jax.device_put(dvs)
        with self._lock:
            pid = self._next_prefix_id
            self._next_prefix_id += 1
            self._prefixes[pid] = tuple(entry)
        return pid

    def unregister_prefix(self, pid: int) -> None:
        """Release a prefix's pinned device K/V (both models').  A
        long-running server registering per-tenant prefixes must be able
        to evict them or HBM ratchets up forever.  In-flight requests
        already admitted keep their spliced copy; queued requests naming
        the id will fail admission loudly.

        Paged mode: releases the pin on the prefix's blocks — they park
        in the pool's LRU (still shareable by chain-hash lookups) until
        allocation pressure actually evicts them."""
        if self.paged:
            with self._lock:
                if pid not in self._paged_prefixes:
                    raise ValueError(f"unknown prefix id {pid}")
                _, blocks, dblocks = self._paged_prefixes.pop(pid)
            with self._pool_lock:
                for b in blocks:
                    self._pool.release(b)
                for b in dblocks:
                    self._dpool.release(b)
            return
        with self._lock:
            if pid not in self._prefixes:
                raise ValueError(f"unknown prefix id {pid}")
            del self._prefixes[pid]

    def abort(self, uri: str) -> bool:
        """Drop a request nobody will collect (an abandoned client):
        remove it from the waiting queue, or free its resident slot —
        including BOTH pool tenants' blocks for a speculative paged row
        (``_release_slot_blocks``), so an abandoned row can never strand
        draft pages.  Call from the pump thread (the serving loop's
        prune pass runs there); resident-slot teardown touches the same
        per-slot state the tick mutates.  Returns True if the uri was
        found.  No callback fires — the caller already decided nobody
        is listening."""
        with self._lock:
            for req in self._waiting:
                if req.uri == uri:
                    self._waiting.remove(req)
                    self.telemetry.req_errored(uri, "aborted")
                    return True
        for slot, st in enumerate(self._slots):
            if st is not None and st.uri == uri:
                self._slots[slot] = None
                self._done[slot] = True     # frozen until readmission
                self._free.append(slot)
                if self.paged:
                    self._release_slot_blocks(slot)
                self.telemetry.req_errored(uri, "aborted")
                return True
        return False

    def submit(self, uri: str, prompt: np.ndarray,
               on_done: Optional[Callable] = None, *,
               on_error: Optional[Callable] = None,
               temperature: float = 0.0,
               rng_seed: Optional[int] = None,
               max_new: Optional[int] = None,
               prefix: Optional[int] = None,
               top_p: float = 0.0,
               on_token: Optional[Callable] = None,
               priority: str = "standard",
               tenant: str = "",
               handoff_cb: Optional[Callable] = None,
               deadline_t: float = 0.0) -> None:
        """Queue one request.  ``prompt``: 1-D int32 token array.
        ``on_done(uri, tokens)`` fires from the pump thread when the
        request finishes (tokens: ``[max_new]`` int32, eos-padded frozen
        tail); ``on_error(uri, exc)`` fires if admission (prefill/
        splice) fails after the request left the waiting queue — without
        it a device error there would silently swallow the request.  ``max_new`` (default: the engine budget) caps THIS
        request's tokens — slot-level budgets are a capability the
        whole-batch path structurally lacks (its one scan runs every
        row to the same length).  Raises on bounds violations — the
        serving layer error-publishes per request before calling this.

        Front-door fields (serving/frontdoor.py): ``on_token(uri,
        token, index)`` streams every generated token from the pump
        thread (the index dedups re-emissions after preemption);
        ``priority`` / ``tenant`` feed the QoS scheduler when the
        engine was built with a ``qos`` policy (recorded but inert
        otherwise).

        ``handoff_cb(state)`` marks THIS engine as the request's
        prefill side of a disaggregated fleet: the tick the prompt's
        first token lands, the row's KV block chain is exported
        (host table snapshot + materialized device pool slices), the
        row is freed here, and the callback receives the
        self-contained state dict to route to a decode replica's
        ``submit_handoff``.  Paged + greedy only (docs/serving_memory.md
        'Disaggregation & elastic pools')."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D, got {prompt.shape}")
        n = len(prompt)
        if prefix is not None:
            with self._lock:
                if self.paged:
                    if prefix not in self._paged_prefixes:
                        raise ValueError(f"unknown prefix id {prefix}")
                    plen_pref = len(self._paged_prefixes[prefix][0])
                else:
                    if prefix not in self._prefixes:
                        raise ValueError(f"unknown prefix id {prefix}")
                    plen_pref = self._prefixes[prefix][2]
            # the TRUE prompt (prefix + suffix) must fit the prompt
            # budget; the padded suffix only needs to fit the cache
            # (_suffix_width handles that), so no bucket term here
            if n < 1 or plen_pref + n > self.max_prompt_width:
                raise ValueError(
                    f"prefix({plen_pref}) + suffix({n}) exceeds max "
                    f"prompt width {self.max_prompt_width}")
        elif n < 1 or n > self.max_prompt_width:
            raise ValueError(
                f"prompt length {n} outside [1, {self.max_prompt_width}]")
        if temperature > 0.0 and rng_seed is None:
            raise ValueError("temperature > 0 needs rng_seed")
        if temperature > 0.0 and self.draft_model is not None:
            raise ValueError(
                "speculative continuous batching is greedy-only (the "
                "sampled contract needs rejection sampling); submit "
                "with temperature=0 or build the engine without a draft")
        if rng_seed is not None:
            # mask into uint32 range: an out-of-range client seed must
            # not crash the pump thread at the np.uint32 staging array
            rng_seed = int(rng_seed) & 0xFFFFFFFF
        mn = self.max_new_tokens if max_new is None else int(max_new)
        if not 1 <= mn <= self.max_new_tokens:
            raise ValueError(
                f"max_new {mn} outside [1, {self.max_new_tokens}]")
        if priority not in PRIORITIES:
            raise ValueError(
                f"priority must be one of {PRIORITIES}, got {priority!r}")
        if handoff_cb is not None:
            if self._ssm:
                raise ValueError(
                    "prefill/decode handoff ships a KV block chain; a "
                    "model with state-space layers also carries a state "
                    "a slot, which the wire format has no place for "
                    "(docs/serving.md)")
            if not self.paged:
                raise ValueError(
                    "handoff_cb requires paged=True: a prefill/decode "
                    "handoff exports a KV BLOCK chain; the arena engine "
                    "has no block tables to rewrite")
            if temperature > 0.0:
                raise ValueError(
                    "prefill/decode handoff is greedy-only: a sampled "
                    "row's RNG stream cannot be split across replicas "
                    "bitwise; submit with temperature=0 or without "
                    "handoff_cb")
            if self.draft_model is not None:
                raise ValueError(
                    "prefill/decode handoff does not compose with "
                    "speculative decoding yet: the draft tenant's block "
                    "chain would have to ship alongside the target's "
                    "(the ROADMAP follow-on 'spec-aware KV handoff' "
                    "lifts this); serve the disaggregated fleet without "
                    "a draft model")
        deadline_t = float(deadline_t or 0.0)
        if deadline_t > 0.0:
            # deadline-aware admission sweeps cost a queue scan per
            # tick — armed only once the FIRST deadline ever arrives,
            # so deadline-free deployments pay nothing
            self._deadline_seen = True
        # stamp AFTER validation: a rejected submit never existed as
        # far as queue-wait/TTFT accounting is concerned
        self.telemetry.req_enqueued(uri)
        with self._lock:
            self._waiting.append(_Req(
                uri, prompt, on_done, on_error, float(temperature),
                rng_seed, mn, prefix, float(top_p), on_token,
                priority, str(tenant), time.monotonic(), handoff_cb,
                deadline_t=deadline_t))

    def submit_handoff(self, state: dict) -> None:
        """Adopt a prefilled request exported by another engine's
        ``handoff_cb``: queue it for admission as a DECODE row whose KV
        block chain is copied from the shipped pool slices instead of
        recomputed.  ``state`` is the self-contained dict
        ``_handoff_slot`` built on the source (prompt, emitted tokens,
        chain hashes, materialized K/V slices, completion callbacks).
        Thread-safe like ``submit`` — the source pump may call straight
        into the destination engine; all device writes happen later on
        THIS engine's pump thread at admission."""
        if not self.paged or self._ssm:
            raise ValueError(
                "submit_handoff requires a paged engine of a model whose "
                "whole cache rides the block table: the handoff wire "
                "format is a KV block chain")
        if self.draft_model is not None:
            raise ValueError(
                "prefill/decode handoff does not compose with "
                "speculative decoding yet (ROADMAP follow-on "
                "'spec-aware KV handoff'); the decode replica must "
                "serve without a draft model")
        chain = state["chain"]
        if int(chain["block_size"]) != self._bs:
            raise ValueError(
                f"handoff block_size {chain['block_size']} != this "
                f"engine's block_size {self._bs}")
        if chain["kv_dtype"] != self.kv_dtype:
            raise ValueError(
                f"handoff kv_dtype {chain['kv_dtype']!r} != this "
                f"engine's kv_dtype {self.kv_dtype!r}")
        plen = int(state["plen"])
        mn = int(state["max_new"])
        if plen > self.max_prompt_width:
            raise ValueError(
                f"handoff prompt length {plen} exceeds max prompt "
                f"width {self.max_prompt_width}")
        if mn > self.max_new_tokens:
            raise ValueError(
                f"handoff max_new {mn} exceeds engine budget "
                f"{self.max_new_tokens}")
        self.telemetry.req_enqueued(state["uri"])
        with self._lock:
            self._waiting.append(_Req(
                state["uri"], np.asarray(state["prompt"], np.int32),
                state.get("on_done"), state.get("on_error"),
                0.0, None, mn, None, 0.0, state.get("on_token"),
                state.get("priority", "standard"),
                state.get("tenant", ""), time.monotonic(),
                None, state))

    # ---- pump ---------------------------------------------------------

    def _admit(self) -> int:
        """Move waiting requests into free slots.  Joiners sharing a
        prompt bucket prefill TOGETHER in one forward (row count padded
        to a power of two so a burst costs a handful of compiles, not
        one per burst size); their K/V splice into slots one
        dynamic_update_slice each.  Returns the number admitted."""
        # a slot that holds nothing rests at position 0: every step
        # program advances ``pos`` for ALL rows and the host reads it
        # back whole, so a freed slot's would creep to Lmax - 1 while
        # its table is all sink, and the fused kernel walks a row's
        # table up to ``pos``.  Re-pinned here because every tick path
        # starts and ends in ``_admit``, as ``_reanchor_prefill`` re-pins
        # a PREFILLING row's.
        if self._free:
            self._pos[list(self._free)] = 0
        if self._deadline_seen:
            self._shed_expired_waiting()
        deferred = (self._brownout_defer_extract()
                    if self._brownout_level >= 1 else None)
        try:
            admitted = self._admit_pass()
            if deferred and admitted == 0 and self._free \
                    and not len(self._waiting):
                # work-conserving brownout: the ladder gates NEW
                # arrivals (front door 429s), but work already accepted
                # must not strand — with zero admissible demand and
                # slots free, idling while holding a backlog wastes the
                # very capacity the ladder protects AND latches the
                # controller (the held queue keeps the depth signal
                # above the exit threshold forever).  Serve the held
                # classes opportunistically; under real pressure the
                # first pass admits or leaves admissible waiting, so
                # this pass never runs and the shed holds.
                with self._lock:
                    for req in reversed(deferred):
                        self._waiting.appendleft(req)
                deferred = None
                admitted = self._admit_pass()
            return admitted
        finally:
            if deferred:
                # deferred classes return to the FRONT of their own
                # subqueues in original order — held, not reordered, so
                # they admit untouched the moment the ladder descends
                with self._lock:
                    for req in reversed(deferred):
                        self._waiting.appendleft(req)

    def _admit_pass(self) -> int:
        if self.chunked:
            return self._admit_chunked()
        if self.paged:
            return self._admit_paged()
        return self._admit_arena()

    def _shed_expired_waiting(self) -> None:
        """Admission-time deadline shed: every waiting request whose
        ``deadline_t`` already passed terminates NOW with a
        ``deadline_exceeded`` error — before any prefill work, before
        claiming a slot, before touching either KV pool.  An overloaded
        engine must not burn its scarcest resource (tick budget) on
        work nobody is waiting for anymore."""
        now = time.monotonic()
        with self._lock:
            expired = [r for r in self._waiting
                       if getattr(r, "deadline_t", 0.0) > 0.0
                       and now > r.deadline_t]
            for r in expired:
                self._waiting.remove(r)
        for r in expired:
            self._deadline_sheds += 1
            self.telemetry.deadline_shed(r.uri)
            late_ms = (now - r.deadline_t) * 1e3
            self._req_error(r.uri, r.on_error, DeadlineExceeded(
                f"deadline_exceeded: deadline passed {late_ms:.0f}ms "
                f"before admission"))

    def _brownout_defer_extract(self) -> list:
        """Pull every waiting request whose class the current brownout
        level sheds OUT of the queue for this admission pass (the
        caller reinserts them at the front afterwards).  Held requests
        keep aging — their enq_t is untouched — so a descending ladder
        admits them with their full waited-time priority."""
        lvl = self._brownout_level
        with self._lock:
            deferred = [r for r in self._waiting
                        if not scheduler_policy.brownout_admit(
                            lvl, getattr(r, "priority", "standard"))]
            for r in deferred:
                self._waiting.remove(r)
        return deferred

    def _admit_arena(self) -> int:
        admitted = 0
        while self._free:
            with self._lock:
                grab = min(len(self._free), len(self._waiting))
                batch = [self._waiting.popleft() for _ in range(grab)]
            if not batch:
                break
            by_bucket: Dict[int, list] = {}
            by_prefix: Dict[Tuple[int, int], list] = {}
            for req in batch:
                if req.prefix is not None:  # prefix-cached request
                    with self._lock:
                        P = self._prefixes.get(req.prefix,
                                               (None, None, 0))[2]
                    sb = self._suffix_width(len(req.prompt), P)
                    by_prefix.setdefault((req.prefix, sb),
                                         []).append(req)
                    continue
                pb = _next_bucket(len(req.prompt), self.prompt_buckets)
                by_bucket.setdefault(pb, []).append(req)
            for (pid, sb), reqs in by_prefix.items():
                try:
                    admitted += self._admit_prefix_group(pid, sb, reqs)
                except Exception as e:
                    logger.exception(
                        "prefix admission failed for %d request(s), "
                        "prefix %s", len(reqs), pid)
                    for req in reqs:
                        self._req_error(req.uri, req.on_error, e)
            for pb, reqs in by_bucket.items():
                # a failed prefill/splice must not swallow requests that
                # already left the waiting queue: surface each one to
                # its error callback and keep admitting other groups
                try:
                    k = len(reqs)
                    kb = 1 << (k - 1).bit_length()  # pad rows to pow2
                    padded = np.full((kb, pb), self.pad_id, np.int32)
                    plens = np.ones(kb, np.int32)   # dummy rows: len 1
                    for i, req in enumerate(reqs):
                        padded[i, :len(req.prompt)] = req.prompt
                        plens[i] = len(req.prompt)
                    self._lap("admit")
                    pre = self._prefill(jnp.asarray(padded, jnp.int32),
                                        jnp.asarray(plens, jnp.int32))
                    if self.draft_model is not None:
                        pre = pre + self._draft_prefill(
                            jnp.asarray(padded, jnp.int32))
                    self._dispatched()
                    # ONE host fetch of the bucket's first-token logits;
                    # per-request picks below then stay on numpy
                    pre = (np.asarray(pre[0]),) + tuple(pre[1:])
                    self._lap("device_wait")
                except Exception as e:
                    logger.exception(
                        "prefill failed for %d request(s), bucket %d",
                        len(reqs), pb)
                    for req in reqs:
                        self._req_error(req.uri, req.on_error, e)
                    continue
                for i, req in enumerate(reqs):
                    try:
                        self._splice_one(pre, i, req)
                        admitted += 1
                    except Exception as e:
                        logger.exception("splice failed for %r", req.uri)
                        self._req_error(req.uri, req.on_error, e)
        return admitted

    def _dispatched(self) -> None:
        """A device call has just been enqueued: close the ``dispatch``
        lap and run ``after_dispatch``.  Every tick path and admission
        goes through here between its jitted call and the fetch of its
        result.  The pools are already donated to the call, so nothing
        the hook does may raise into the step."""
        self._lap("dispatch")
        if self.after_dispatch is not None:
            try:
                self.after_dispatch()
            except Exception:
                logger.exception("after_dispatch hook failed")

    def _req_error(self, uri, on_error, exc, phase: str = "admit"):
        """``phase`` is the cycle phase the caller is in (all but the
        handoff's are admission paths): the callback's own time is
        taken out of it and booked to ``publish``."""
        self.telemetry.req_errored(uri, f"{type(exc).__name__}: {exc}")
        if on_error is None:
            return
        self._lap(phase)
        try:
            on_error(uri, exc)
        except Exception:
            logger.exception("on_error callback failed for %r", uri)
        self._lap("publish")

    def _suffix_width(self, n: int, P: int) -> int:
        """Padded width for a prefix request's suffix: a shared prompt
        bucket when one fits after the prefix (bounded compile count),
        else the exact remaining cache room (one compile per prefix
        length — still bounded by registered prefixes).  Suffix padding
        writes dead K/V past the true prompt; they are never attended
        and later rounds overwrite them, so only the CACHE bound (L)
        applies, not the prompt budget."""
        for b in self.prompt_buckets:
            if n <= b and P + b <= self._L - 1:
                return b
        return self._L - 1 - P

    def _admit_prefix_group(self, pid: int, sb: int, reqs) -> int:
        """Admission for prefix-cached requests sharing (prefix, suffix
        width): splice the stored K/V into each group member's slot and
        run ALL their suffixes against it in one decode_k forward — the
        semantics of prefilling each concatenated prompt, at one device
        call per burst.  Returns the number admitted."""
        with self._lock:
            if pid not in self._prefixes:
                raise ValueError(f"prefix id {pid} was unregistered "
                                 f"while queued")
            pks, pvs, P, dks, dvs = self._prefixes[pid]
        n = min(len(reqs), len(self._free))
        if n < len(reqs):
            # free slots ran out mid-batch: requeue the rest in order
            with self._lock:
                for req in reversed(reqs[n:]):
                    self._waiting.appendleft(req)
            reqs = reqs[:n]
        if not reqs:
            return 0
        # pad rows to a power of two (bounded compile count, like the
        # bucketed prefill); padding rows target the out-of-range slot
        # index S — reads clamp, writes drop
        kb = 1 << (n - 1).bit_length()
        padded = np.full((kb, sb), self.pad_id, np.int32)
        lens = np.ones(kb, np.int32)
        for i, req in enumerate(reqs):
            padded[i, :len(req.prompt)] = req.prompt
            lens[i] = len(req.prompt)
        real = [self._free.popleft() for _ in range(n)]
        slots = real + [self._S] * (kb - n)
        self._lap("admit")
        try:
            last, self._ck, self._cv = self._prefix_admit(
                self._ck, self._cv, pks, pvs,
                jnp.asarray(padded, jnp.int32),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray(slots, jnp.int32))
            if self.draft_model is not None:
                _, self._dck, self._dcv = self._draft_prefix_admit(
                    self._dck, self._dcv, dks, dvs,
                    jnp.asarray(padded, jnp.int32),
                    jnp.asarray(lens, jnp.int32),
                    jnp.asarray(slots, jnp.int32))
        except Exception:
            self._free.extend(real)
            raise
        self._dispatched()
        last = np.asarray(last)     # one D2H for the whole group
        self._lap("device_wait")
        admitted = 0
        for i, req in enumerate(reqs):
            try:
                plen = P + int(lens[i])
                first = self._pick_first(last[i], plen,
                                         req.temperature, req.rng_seed,
                                         req.top_p)
                self._install_slot(real[i], req.uri, plen, req.max_new,
                                   req.on_done, req.on_error,
                                   req.temperature, req.rng_seed,
                                   first, req.top_p,
                                   on_token=req.on_token,
                                   priority=req.priority)
                admitted += 1
            except Exception as e:
                self._free.append(real[i])
                self._req_error(req.uri, req.on_error, e)
        return admitted

    # ---- chunked admission (PREFILLING slots, no device call) ---------

    def _admit_chunked(self) -> int:
        """Chunked admission runs NO prefill: it only claims a slot,
        installs it in the ``PREFILLING`` state, and (paged) attaches
        any prefix-matched blocks — the prompt feeds the cache chunk by
        chunk inside the fused tick, interleaved with decodes under
        the token budget.  A paged request the pool can't start yet
        requeues at the front and admission stops (order preserved);
        mid-prompt growth handles the rest per chunk."""
        admitted = 0
        while self._free:
            with self._lock:
                req = self._waiting.popleft() if self._waiting else None
            if req is None:
                break
            res = (self._admit_one_chunked_paged(req) if self.paged
                   else self._admit_one_chunked(req))
            if res == "admitted":
                admitted += 1
            elif res == "blocked":
                with self._lock:
                    self._waiting.appendleft(req)
                break
        return admitted

    def _admit_one_chunked(self, req: _Req) -> str:
        """Arena chunked admission: splice a named prefix's stored K/V
        (chunks then run against it block-causally, like the monolithic
        prefix path) and install the slot PREFILLING at the prefix
        boundary."""
        base = 0
        pks = pvs = dks = dvs = None
        if req.prefix is not None:
            with self._lock:
                entry = self._prefixes.get(req.prefix)
            if entry is None:
                self._req_error(req.uri, req.on_error, ValueError(
                    f"prefix id {req.prefix} was unregistered while "
                    f"queued"))
                return "error"
            pks, pvs, base = entry[0], entry[1], entry[2]
            dks, dvs = entry[3], entry[4]
        slot = self._free.popleft()
        if pks is not None:
            try:
                self._ck, self._cv = self._insert(
                    self._ck, self._cv, pks, pvs, jnp.int32(slot))
                if self.draft_model is not None:
                    # the draft's chunks run against the SAME spliced
                    # prefix boundary, so its cache needs the prefix too
                    self._dck, self._dcv = self._insert(
                        self._dck, self._dcv, dks, dvs,
                        jnp.int32(slot))
            except Exception as e:
                self._free.append(slot)
                logger.exception("chunked prefix splice failed for %r",
                                 req.uri)
                self._req_error(req.uri, req.on_error, e)
                return "error"
        self._install_prefill(slot, req, base + len(req.prompt),
                              base=base, full=req.prompt)
        return "admitted"

    def _admit_one_chunked_paged(self, req: _Req) -> str:
        """Paged chunked admission: match + acquire leading full prompt
        blocks (copy-free sharing, capped at ``(plen-1)//bs`` so the
        last token always recomputes for its first-token logits) and
        install PREFILLING at the matched boundary.  Blocks for the
        unmatched tail are allocated PER CHUNK by the tick scheduler —
        a mid-prompt dry pool preempts this prefilling row back to the
        queue, never a decoder."""
        if req.handoff_state is not None:
            return self._admit_handoff(req)
        try:
            full = self._full_prompt(req)
        except Exception as e:
            self._req_error(req.uri, req.on_error, e)
            return "error"
        plen = len(full)
        hashes = self._pool.block_hashes(full)
        total = -(-plen // self._bs)
        # errors surface AFTER the lock: on_error is arbitrary user
        # code and must never run under _pool_lock
        err: Optional[Exception] = None
        with self._pool_lock:
            matched = self._pool.lookup(
                hashes[:(plen - 1) // self._bs])
            dmatch = None
            if self._dpool is not None:
                # the fill frontier is one number for both tenants, so
                # the usable prefix match is the shorter of the two
                dmatch = self._dpool.lookup(
                    hashes[:(plen - 1) // self._bs])
                m = min(len(matched), len(dmatch))
                matched, dmatch = matched[:m], dmatch[:m]
            need = total - len(matched)
            cap = self._pool.n_blocks - 1
            if self._dpool is not None:
                cap = min(cap, self._dpool.n_blocks - 1)
            # per-chunk allocation only needs room to START (first
            # chunk block + decode headroom); monolithic admission's
            # need+1 gate would block exactly the long prompts
            # chunking exists to stream in
            dry = self._pool.allocatable() < 2 or (
                self._dpool is not None
                and self._dpool.allocatable() < 2)
            if need + 1 > cap:
                err = ValueError(
                    f"prompt needs {need} private blocks + headroom "
                    f"but the pool holds {cap}")
            elif dry:
                if self.n_active == 0:
                    err = RuntimeError(
                        f"pool dry with no residents: "
                        f"{self._pool.num_referenced()} of "
                        f"{self._pool.n_blocks} blocks are pinned "
                        f"(unregister a prefix or raise n_blocks)")
                else:
                    return "blocked"
            else:
                for b in matched:
                    self._pool.acquire(b)
                if dmatch is not None:
                    for b in dmatch:
                        self._dpool.acquire(b)
                if self._kv_store is not None:
                    # tiered KV: extend the pinned device match from
                    # the host store.  The probe window is capped so
                    # adoption leaves the >= 2 allocatable blocks the
                    # chunked dry gate just guaranteed — the first
                    # chunk must still be able to start.  (No draft
                    # tenant here: the store refuses speculative
                    # engines at construction.)
                    limit = min((plen - 1) // self._bs,
                                len(matched)
                                + max(0, self._pool.allocatable() - 2))
                    matched = matched + self._store_readmit(
                        hashes, len(matched), limit)
        if err is not None:
            self._req_error(req.uri, req.on_error, err)
            return "error"
        # adoption may have evicted (spill pending) and recorded host
        # payloads; flush both before the tick's device work
        self._drain_spills()
        self._apply_readmits()
        slot = self._free.popleft()
        self._row_blocks[slot] = list(matched)
        self._tables[slot, :] = SINK_BLOCK
        self._tables[slot, :len(matched)] = matched
        if dmatch is not None:
            self._drow_blocks[slot] = list(dmatch)
            self._dtables[slot, :] = SINK_BLOCK
            self._dtables[slot, :len(dmatch)] = dmatch
        self._install_prefill(slot, req, plen, base=0, full=full,
                              hashes=list(hashes),
                              fill=len(matched) * self._bs,
                              n_pub=len(matched))
        return "admitted"

    def _install_prefill(self, slot: int, req: _Req, plen: int, *,
                         base: int, full, hashes=None, fill=None,
                         n_pub: int = 0) -> None:
        """Install a slot in the PREFILLING state: the decode side sees
        a frozen row (done=True, fed pad) anchored at the fill frontier
        until its last chunk lands.  ``fill`` (paged) starts past
        prefix-matched blocks; arena rows start past the spliced
        prefix (``base``)."""
        self._slots[slot] = _Slot(
            uri=req.uri, plen=plen,
            max_new=self._brownout_mn(req.priority, req.max_new),
            on_done=req.on_done, on_error=req.on_error,
            temperature=req.temperature, rng_seed=req.rng_seed,
            top_p=req.top_p, req=req, admit_seq=self._admit_seq,
            on_token=req.on_token,
            state="PREFILLING",
            fill_pos=base if fill is None else fill,
            base=base, full=np.asarray(full, np.int32),
            hashes=hashes, n_pub=n_pub)
        self._admit_seq += 1
        self._tok[slot] = self.pad_id
        self._pos[slot] = self._slots[slot].fill_pos
        if self.draft_model is not None:
            self._dpos[slot] = self._slots[slot].fill_pos
        self._done[slot] = True
        self.telemetry.req_admitted(req.uri, slot, prefilling=True,
                                    priority=req.priority)

    # ---- paged mode (block-pool cache) --------------------------------

    def _full_prompt(self, req: _Req) -> np.ndarray:
        """The TRUE token sequence a paged request decodes: a
        ``prefix=`` id expands to its registered tokens + the suffix —
        the chain-hash index then shares the pinned blocks
        automatically, subsuming the arena's device-side splice."""
        if req.prefix is None:
            return req.prompt
        with self._lock:
            if req.prefix not in self._paged_prefixes:
                raise ValueError(f"prefix id {req.prefix} was "
                                 f"unregistered while queued")
            ptoks = self._paged_prefixes[req.prefix][0]
        return np.concatenate([ptoks, req.prompt])

    def _register_prefix_paged(self, tokens: np.ndarray) -> int:
        """Pin a shared prefix's FULL blocks in the pool (ref held until
        ``unregister_prefix``): prefill them once through the paged
        path, publish their chain hashes, and store the tokens so
        ``submit(prefix=id)`` requests concatenate host-side and match
        the pinned blocks at admission.  The partial tail beyond the
        last full block recomputes per request inside its suffix (a
        partial block can never be shared — it would keep growing)."""
        P = len(tokens)
        bs = self._bs
        nfull = P // bs
        hashes = self._pool.block_hashes(tokens[:nfull * bs])

        def pin(pool, admit, pk, pv):
            """Pin one tenant's full prefix blocks: match, allocate the
            rest, prefill the unmatched span through the tenant's paged
            path, publish.  Returns (blocks, pk, pv) — the buffers come
            back because ``admit`` donates its inputs."""
            with self._pool_lock:
                matched = pool.lookup(hashes)
                for b in matched:
                    pool.acquire(b)
                blocks = list(matched)
                for _ in range(nfull - len(matched)):
                    b = pool.allocate()
                    if b is None:
                        for bb in blocks:
                            pool.release(bb)
                        raise RuntimeError(
                            f"{pool.name} block pool has no room to pin "
                            f"a {nfull}-block prefix "
                            f"({pool.num_referenced()} of "
                            f"{pool.n_blocks} blocks referenced)")
                    blocks.append(b)
            # allocation may have evicted indexed blocks: gather their
            # old bytes before the admit below rewrites the ids (the
            # buffers are still self._pk/_pv here — admit's donation
            # hasn't happened yet; the draft tenant never spills)
            self._drain_spills()
            if len(matched) < nfull:
                span = tokens[len(matched) * bs:nfull * bs]
                sb = _next_bucket(len(span), self.prompt_buckets)
                padded = np.full((1, sb), self.pad_id, np.int32)
                padded[0, :len(span)] = span
                tabs = np.full((1, self._M), SINK_BLOCK, np.int32)
                tabs[0, :len(blocks)] = blocks
                # target admit returns (logits, pk, pv); draft (pk, pv)
                out = admit(pk, pv, jnp.asarray(padded, jnp.int32),
                            jnp.asarray([len(span)], jnp.int32),
                            jnp.asarray(tabs, jnp.int32),
                            jnp.asarray([len(matched) * bs], jnp.int32))
                pk, pv = out[-2:]
                with self._pool_lock:
                    for j in range(len(matched), nfull):
                        pool.insert(hashes[j], blocks[j])
            return blocks, pk, pv

        blocks, self._pk, self._pv = pin(
            self._pool, self._paged_admit, self._pk, self._pv)
        dblocks: tuple = ()
        if self._dpool is not None:
            try:
                dblocks, self._dpk, self._dpv = pin(
                    self._dpool, self._draft_paged_admit,
                    self._dpk, self._dpv)
            except Exception:
                # a half-pinned prefix would leak target blocks forever
                with self._pool_lock:
                    for b in blocks:
                        self._pool.release(b)
                raise
        with self._lock:
            pid = self._next_prefix_id
            self._next_prefix_id += 1
            self._paged_prefixes[pid] = (tokens, blocks, dblocks)
        return pid

    def _admit_handoff(self, req: _Req) -> str:
        """Adopt a prefill exported by another engine (the decode half
        of a prefill/decode handoff): allocate a same-length block
        chain via ``adopt_chain`` (carried prefix hashes republished,
        first writer wins, so the decode side keeps sharing the
        prefix), SCATTER the shipped pool slices into this engine's
        arena at the new block ids, and install the slot directly in
        DECODE at the donor's position — no prefill forward runs here.
        A pool that can't hold the chain yet blocks (requeue at the
        front), and a preemption later requeues the same request with
        its immutable ``handoff_state``, so re-adoption regenerates
        the identical row."""
        state = req.handoff_state
        chain = state["chain"]
        n = int(chain["n"])
        # errors surface AFTER the lock: on_error is arbitrary user
        # code and must never run under _pool_lock
        err: Optional[Exception] = None
        with self._pool_lock:
            # +1 headroom mirrors monolithic admission: the first
            # decode tokens must not instantly preempt the adoption
            cap = self._pool.n_blocks - 1
            if n + 1 > cap:
                err = ValueError(
                    f"handoff chain needs {n} blocks + headroom but "
                    f"the pool holds {cap}")
            elif self._pool.allocatable() < n + 1:
                if self.n_active == 0:
                    err = RuntimeError(
                        f"pool dry with no residents: "
                        f"{self._pool.num_referenced()} of "
                        f"{self._pool.n_blocks} blocks are pinned "
                        f"(unregister a prefix or raise n_blocks)")
                else:
                    return "blocked"
            else:
                blocks = self._pool.adopt_chain(chain)
                if blocks is None:
                    return "blocked"
        if err is not None:
            self._req_error(req.uri, req.on_error, err)
            return "error"
        # adoption may have evicted indexed blocks (spill pending) and
        # an adopted id may BE one — gather before the scatter below
        self._drain_spills()
        idx = jnp.asarray(blocks, jnp.int32)

        def scatter(d, s):
            # the chain arrives on the SOURCE replica's chip(s): move
            # it onto this engine's own before the scatter (the
            # gathered [layers, n, KH, bs, D] rows take the pool's spec)
            s = jax.device_put(jnp.asarray(s, d.dtype), d.sharding)
            out = d.at[:, idx].set(s)
            return jax.device_put(out, d.sharding)

        self._pk = jax.tree_util.tree_map(scatter, self._pk,
                                          state["k"])
        self._pv = jax.tree_util.tree_map(scatter, self._pv,
                                          state["v"])
        slot = self._free.popleft()
        self._row_blocks[slot] = list(blocks)
        self._tables[slot, :] = SINK_BLOCK
        self._tables[slot, :len(blocks)] = blocks
        self._slots[slot] = _Slot(
            uri=req.uri, plen=int(state["plen"]), max_new=req.max_new,
            tokens=list(state["tokens"]), on_done=req.on_done,
            on_error=req.on_error, temperature=0.0, rng_seed=None,
            top_p=0.0, on_token=req.on_token, req=req,
            admit_seq=self._admit_seq)
        self._admit_seq += 1
        # the donor already emitted token[0]; decode resumes from it
        self._tok[slot] = int(state["last_token"])
        self._pos[slot] = int(state["pos"])
        self._done[slot] = False
        self._handoffs_in += 1
        self.telemetry.req_admitted(req.uri, slot,
                                    priority=req.priority)
        # two-phase handoff ack: adoption is now durable on THIS
        # engine, so the source may release its retained state.  The
        # callback is record-only by contract (the broker pops a
        # pending-handoff entry and bumps a counter) and must never
        # re-enter this engine.
        ack = state.get("on_adopt")
        if ack is not None:
            try:
                ack(req.uri, self._replica_id)
            except Exception:
                logger.exception("handoff adoption ack failed for %r",
                                 req.uri)
        return "admitted"

    # ---- tiered KV memory (serving/kv_store.py) -----------------------

    def _store_evicted(self, hash_: int) -> None:
        """HostKVStore capacity-eviction callback: the host copy is
        gone, retract the host-tier directory claim (device-tier
        claims are untouched — the block may still be indexed)."""
        if self._prefix_directory is not None:
            self._prefix_directory.unpublish(self._replica_id, hash_,
                                             TIER_HOST)

    def _pool_index_event(self, kind: str, *, hash_: int,
                          block: int) -> None:
        """BlockPool index_cb: mirror device-index membership into the
        fleet PrefixDirectory (fires under ``_pool_lock``; the
        directory has its own lock and never re-enters the pool)."""
        if kind == "publish":
            self._prefix_directory.publish(self._replica_id, hash_,
                                           TIER_HBM)
        else:
            self._prefix_directory.unpublish(self._replica_id, hash_,
                                             TIER_HBM)

    def _spill_block(self, block: int, hash_: int) -> None:
        """BlockPool spill_cb: an indexed CACHED block is being
        evicted — record it so the pump thread copies its K/V to the
        host tier before the block id is rewritten.  Fires under
        ``_pool_lock``, so per the record-only contract
        (``paged_cache.CALLBACK_CONTRACT``) it must not touch the
        device: the D2H gather happens in ``_drain_spills``, which
        every evicting path runs before its next device write.  Until
        then ``self._pk``/``self._pv`` still hold exactly the bytes
        the hash describes — the pump thread is the only arena
        writer, and it drains before it scatters."""
        self._pending_spills.append((int(block), hash_))

    def _drain_spills(self) -> None:
        """Flush pool-eviction spills recorded by ``_spill_block``:
        ONE batched D2H gather for the whole wave (vs the per-block
        fetch the under-lock path used to make), then host-store puts
        and directory publishes — all outside ``_pool_lock``.  Must
        run before any device write that could touch an evicted block
        id (a just-allocated or adopted id may BE one): admission,
        growth, handoff scatter, and pool-shrink slicing all drain
        first.  Pump thread only, like every arena access."""
        with self._pool_lock:
            pending, self._pending_spills = self._pending_spills, []
        if not pending:
            return
        idx = jnp.asarray([b for b, _ in pending], jnp.int32)

        def gather(x):
            return jnp.take(x, idx, axis=1)

        fetched = jax.device_get({
            "k": jax.tree_util.tree_map(gather, self._pk),
            "v": jax.tree_util.tree_map(gather, self._pv),
        })      # one D2H for the whole spill wave
        for i, (_, hash_) in enumerate(pending):
            payload = jax.tree_util.tree_map(
                lambda x: x[:, i:i + 1], fetched)
            if self._kv_store.put(hash_, payload, self._per_block_bytes):
                self._kv_spills += 1
                self._kv_spill_bytes += self._per_block_bytes
                if self._prefix_directory is not None:
                    self._prefix_directory.publish(
                        self._replica_id, hash_, TIER_HOST)

    def _store_readmit(self, hashes, n_matched: int,
                       max_blocks: int) -> List[int]:
        """Extend a device-index prefix match from the host tier:
        probe the store for the hashes PAST the device match, adopt
        the hit chain back into the pool (all-or-nothing with
        rollback, carried hashes republished first-writer-wins — the
        PR 15 contract), and RECORD the host payloads for
        ``_apply_readmits`` to scatter after the lock is released
        (tpulint TZ102: no H2D under the pool lock).  Admission
        applies every recorded scatter before its prefill device call
        — and before releasing blocks on a failure — so a republished
        block is never read, shared, or recycled holding garbage.
        Returns the adopted block ids (ref=1 each, [] on miss or dry
        pool — the store entries survive either way).  Caller holds
        ``_pool_lock``; the caller already holds a reference on every
        device-matched block (adoption's allocate may evict CACHED
        blocks, and a pinned match cannot be among them)."""
        run = self._kv_store.probe(hashes[n_matched:max_blocks])
        if not run:
            return []
        chain = {"block_size": self._bs, "kv_dtype": self.kv_dtype,
                 "n": len(run), "hashes": [h for h, _ in run]}
        blocks = self._pool.adopt_chain(chain)
        if blocks is None:
            return []

        def cat(*leaves):
            return np.concatenate(leaves, axis=1)

        kcat = jax.tree_util.tree_map(cat, *[p["k"] for _, p in run])
        vcat = jax.tree_util.tree_map(cat, *[p["v"] for _, p in run])
        self._pending_readmits.append((list(blocks), kcat, vcat))
        self._kv_readmits += 1
        self._kv_readmit_tokens_saved += len(blocks) * self._bs
        return blocks

    def _apply_readmits(self) -> None:
        """Scatter host-tier payloads recorded by ``_store_readmit``
        into the device pool.  Runs outside ``_pool_lock``, AFTER
        ``_drain_spills`` (an adopted id may be a just-evicted id
        whose old content the spill must gather first) and before the
        admission's prefill call reads the blocks."""
        pending, self._pending_readmits = self._pending_readmits, []
        for blocks, kcat, vcat in pending:
            idx = jnp.asarray(blocks, jnp.int32)

            def scatter(d, s):
                out = d.at[:, idx].set(jnp.asarray(s, d.dtype))
                return jax.device_put(out, d.sharding)

            self._pk = jax.tree_util.tree_map(scatter, self._pk, kcat)
            self._pv = jax.tree_util.tree_map(scatter, self._pv, vcat)

    def _admit_paged(self) -> int:
        """Paged admission: per request, match leading FULL prompt
        blocks in the chain-hash index (copy-free sharing), allocate
        private blocks for the rest, and prefill only the unshared
        suffix — grouped by suffix bucket so a burst costs one device
        call per bucket.  A request the pool can't hold yet requeues at
        the FRONT (order preserved) and admission stops — residents
        finishing or preemption will free blocks.  The match length is
        capped at ``(plen-1)//bs`` blocks so the LAST prompt token
        always recomputes: its forward yields the first-token logits
        (a 100% cache hit would leave nothing to run)."""
        admitted = 0
        while self._free:
            with self._lock:
                grab = min(len(self._free), len(self._waiting))
                batch = [self._waiting.popleft() for _ in range(grab)]
            if not batch:
                break
            plans, blocked = [], []
            for req in batch:
                if blocked:         # keep queue order behind the block
                    blocked.append(req)
                    continue
                if req.handoff_state is not None:
                    # adopted chains never prefill — no plan, no group
                    res = self._admit_handoff(req)
                    if res == "admitted":
                        admitted += 1
                    elif res == "blocked":
                        blocked.append(req)
                    continue
                try:
                    full = self._full_prompt(req)
                except Exception as e:
                    self._req_error(req.uri, req.on_error, e)
                    continue
                plen = len(full)
                hashes = self._pool.block_hashes(full)
                total = -(-plen // self._bs)
                # errors surface AFTER the lock: on_error is arbitrary
                # user code and must never run under _pool_lock
                err: Optional[Exception] = None
                planned = False
                with self._pool_lock:
                    matched = self._pool.lookup(
                        hashes[:(plen - 1) // self._bs])
                    if self._dpool is not None:
                        # both tenants must prefill the SAME suffix, so
                        # the usable match is the shorter of the two
                        # (identical op sequences keep the pools mirror
                        # images; the min is a safety net, not a tax)
                        dmatch = self._dpool.lookup(
                            hashes[:(plen - 1) // self._bs])
                        m = min(len(matched), len(dmatch))
                        matched, dmatch = matched[:m], dmatch[:m]
                    need = total - len(matched)
                    # +1 headroom: the first decode tokens must not
                    # instantly preempt what admission just built
                    cap = self._pool.n_blocks - 1
                    if self._dpool is not None:
                        cap = min(cap, self._dpool.n_blocks - 1)
                    dry = self._pool.allocatable() < need + 1 or (
                        self._dpool is not None
                        and self._dpool.allocatable() < need + 1)
                    if need + 1 > cap:
                        err = ValueError(
                            f"prompt needs {need} private blocks + "
                            f"headroom but the pool holds {cap}")
                    elif dry:
                        if (self.n_active == 0 and not plans
                                and admitted == 0):
                            # nothing in flight will ever free blocks:
                            # only prefix pins hold the pool
                            err = RuntimeError(
                                f"pool dry with no residents: "
                                f"{self._pool.num_referenced()} of "
                                f"{self._pool.n_blocks} blocks are "
                                f"pinned (unregister a prefix or "
                                f"raise n_blocks)")
                        else:
                            blocked.append(req)
                    else:
                        for b in matched:
                            self._pool.acquire(b)
                        if self._kv_store is not None:
                            # tiered KV: extend the (now pinned — the
                            # adoption below allocates, and allocation
                            # may evict CACHED blocks, never a pinned
                            # match) device match from the host store.
                            # Adoption consumes exactly the allocatable
                            # blocks the shrunken ``need`` no longer
                            # asks for, so the dry gate above still
                            # guarantees the allocate loop below.  No
                            # draft tenant here: the store refuses
                            # speculative engines at construction.
                            matched = matched + self._store_readmit(
                                hashes, len(matched),
                                (plen - 1) // self._bs)
                            need = total - len(matched)
                        blocks = list(matched)
                        for _ in range(need):
                            blocks.append(self._pool.allocate())
                        dblocks = None
                        if self._dpool is not None:
                            for b in dmatch:
                                self._dpool.acquire(b)
                            dblocks = list(dmatch)
                            for _ in range(need):
                                dblocks.append(self._dpool.allocate())
                        planned = True
                if err is not None:
                    self._req_error(req.uri, req.on_error, err)
                    continue
                if not planned:
                    continue
                plans.append((req, full, hashes, len(matched), blocks,
                              dblocks))
            if blocked:
                with self._lock:
                    for req in reversed(blocked):
                        self._waiting.appendleft(req)
            # deferred pool-callback device work, in dependency order:
            # spills gather an evicted id's OLD bytes before the
            # readmit scatter (or the group prefill below) rewrites it
            self._drain_spills()
            self._apply_readmits()
            groups: Dict[int, list] = {}
            for plan in plans:
                slen = len(plan[1]) - plan[3] * self._bs
                sb = _next_bucket(slen, self.prompt_buckets)
                groups.setdefault(sb, []).append(plan)
            for sb, plist in groups.items():
                try:
                    admitted += self._admit_paged_group(sb, plist)
                except Exception as e:
                    logger.exception("paged admission failed for %d "
                                     "request(s)", len(plist))
                    with self._pool_lock:
                        for req, _, _, _, blocks, dblocks in plist:
                            for b in blocks:
                                self._pool.release(b)
                            for b in dblocks or ():
                                self._dpool.release(b)
                    for req, _, _, _, _, _ in plist:
                        self._req_error(req.uri, req.on_error, e)
            if blocked:
                break
        return admitted

    def _admit_paged_group(self, sb: int, plans) -> int:
        """One paged-prefill device call for every planned request
        sharing a suffix bucket (rows padded to a power of two;
        padding rows carry all-sink tables and touch nothing real).
        After the call each row's full private prompt blocks are
        published in the hash index, so the NEXT identical prompt
        shares them."""
        n = len(plans)
        kb = 1 << (n - 1).bit_length()
        padded = np.full((kb, sb), self.pad_id, np.int32)
        lens = np.ones(kb, np.int32)
        pos = np.zeros(kb, np.int32)
        tabs = np.full((kb, self._M), SINK_BLOCK, np.int32)
        dtabs = np.full((kb, self._M), SINK_BLOCK, np.int32)
        for i, (req, full, hashes, n_match, blocks,
                dblocks) in enumerate(plans):
            sfx = full[n_match * self._bs:]
            padded[i, :len(sfx)] = sfx
            lens[i] = len(sfx)
            pos[i] = n_match * self._bs
            tabs[i, :len(blocks)] = blocks
            if dblocks is not None:
                dtabs[i, :len(dblocks)] = dblocks
        self._lap("admit")
        last, self._pk, self._pv = self._paged_admit(
            self._pk, self._pv, jnp.asarray(padded, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(tabs, jnp.int32),
            jnp.asarray(pos, jnp.int32))
        if self._dpool is not None:
            # the SAME suffix grid against the draft tenant (min-match
            # keeps the two prefills byte-aligned); draft logits are
            # discarded — only the target picks tokens
            self._dpk, self._dpv = self._draft_paged_admit(
                self._dpk, self._dpv, jnp.asarray(padded, jnp.int32),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray(dtabs, jnp.int32),
                jnp.asarray(pos, jnp.int32))
        self._dispatched()
        last = np.asarray(last)     # one D2H for the whole group
        self._lap("device_wait")
        admitted = 0
        for i, (req, full, hashes, n_match, blocks,
                dblocks) in enumerate(plans):
            plen = len(full)
            slot = self._free.popleft()
            self._row_blocks[slot] = blocks
            self._tables[slot, :] = SINK_BLOCK
            self._tables[slot, :len(blocks)] = blocks
            if dblocks is not None:
                self._drow_blocks[slot] = dblocks
                self._dtables[slot, :] = SINK_BLOCK
                self._dtables[slot, :len(dblocks)] = dblocks
            # publish BEFORE install: the prefill succeeded, so the
            # blocks' content is valid for sharing even if this
            # particular install fails below
            with self._pool_lock:
                for j in range(n_match, plen // self._bs):
                    self._pool.insert(hashes[j], blocks[j])
                if dblocks is not None:
                    for j in range(n_match, plen // self._bs):
                        self._dpool.insert(hashes[j], dblocks[j])
            try:
                first = self._pick_first(last[i], plen,
                                         req.temperature, req.rng_seed,
                                         req.top_p)
                self._install_slot(slot, req.uri, plen, req.max_new,
                                   req.on_done, req.on_error,
                                   req.temperature, req.rng_seed,
                                   first, req.top_p, req=req,
                                   on_token=req.on_token,
                                   priority=req.priority)
                admitted += 1
            except Exception as e:
                self._free.append(slot)
                self._release_slot_blocks(slot)
                self._req_error(req.uri, req.on_error, e)
        return admitted

    def _ensure_blocks(self, active) -> list:
        """Grow each resident's block table to cover the positions the
        coming chunk will write.  When the pool is dry, PREEMPT the
        latest admission (never the oldest — earliest requests keep
        strict forward progress, so this terminates): its blocks free
        up, its request requeues at the queue front, and its tokens
        regenerate deterministically on readmission.  Returns the
        still-active subset."""
        for i in list(active):
            st = self._slots[i]
            if st is None:
                continue
            if self.draft_model is not None:
                # a spec round writes k+1 verify positions pos..pos+k
                # (both tenants — dpos == pos)
                last_write = min(int(self._pos[i]) + self._spec_k,
                                 self._L - 1)
            else:
                ticks = max(1, min(self.ticks_per_step,
                                   st.max_new - len(st.tokens)))
                last_write = min(int(self._pos[i]) + ticks - 1,
                                 self._L - 1)
            self._grow_row(i, last_write // self._bs + 1)
        # growth allocations may have evicted indexed blocks: gather
        # their bytes before the coming step writes the reused ids
        self._drain_spills()
        return [i for i in active if self._slots[i] is not None]

    def _grow_row(self, i: int, need: int) -> None:
        """Grow row ``i``'s block table(s) to ``need`` blocks,
        preempting (latest admission, prefilling rows first) whenever a
        pool is dry — including row ``i`` itself, which ends the loop.
        With a draft model the two tenants grow in LOCKSTEP to the same
        block count: either pool running dry preempts the victim from
        BOTH (``_release_slot_blocks``), so a row's verify pointer can
        never outrun its draft pages."""
        self._grow_tenant(i, need, self._pool, self._row_blocks,
                          self._tables)
        if self._dpool is not None:
            self._grow_tenant(i, need, self._dpool, self._drow_blocks,
                              self._dtables)

    def _grow_tenant(self, i: int, need: int, pool, row_blocks,
                     tables) -> None:
        while (self._slots[i] is not None
               and len(row_blocks[i]) < need):
            with self._pool_lock:
                b = pool.allocate()
            if b is None:
                self._preempt(self._pick_victim())
                continue
            j = len(row_blocks[i])
            row_blocks[i].append(b)
            tables[i, j] = b

    def _grow_chunk_blocks(self, decode_rows, chunks) -> None:
        """Per-tick paged growth for the fused step: decode rows need
        their one write position covered; each chunk row needs blocks
        through its chunk's last write.  Pool-dry preemption targets
        the LATEST PREFILLING row first (``_pick_victim``) — decoders
        that already emitted tokens are never evicted to feed a
        joiner's prompt."""
        for i in decode_rows:
            if self._slots[i] is None:
                continue
            # spec decode rows write k+1 verify positions (spec_k is 0
            # without a draft, reducing to the single decode write)
            last_write = min(int(self._pos[i]) + self._spec_k,
                             self._L - 1)
            self._grow_row(i, last_write // self._bs + 1)
        for i, clen in chunks:
            st = self._slots[i]
            if st is None:
                continue
            self._grow_row(i, (st.fill_pos + clen - 1) // self._bs + 1)
        # growth allocations may have evicted indexed blocks: gather
        # their bytes before the fused step writes the reused ids
        self._drain_spills()

    def _publish_chunk_blocks(self, i: int, st: _Slot) -> None:
        """Hash-publish the prompt blocks a landed chunk fully covered
        (never the frontier block — a partially written block must not
        be shared), so the NEXT identical prompt attaches copy-free,
        exactly like monolithic admission's post-prefill publish."""
        if st.hashes is None:
            return
        hi = min(st.fill_pos // self._bs, st.plen // self._bs)
        if hi <= st.n_pub:
            return
        blocks = self._row_blocks[i]
        with self._pool_lock:
            for j in range(st.n_pub, hi):
                self._pool.insert(st.hashes[j], blocks[j])
            if self._dpool is not None:
                # same hashes (keys are token chains, not tenant-
                # specific); lockstep growth keeps the lists aligned
                dblocks = self._drow_blocks[i]
                for j in range(st.n_pub, hi):
                    self._dpool.insert(st.hashes[j], dblocks[j])
        st.n_pub = hi

    def _table_width(self, need: int) -> int:
        """Pow2-bucketed narrow table width for a chunk grid: wide
        enough for every position the chunks write/attend, capped at
        the full table width M."""
        v = 1
        while v < need:
            v *= 2
        return min(v, self._M)

    def _pick_victim(self) -> int:
        # the choice itself is pure policy (serving/policy.py): the
        # simulator makes the identical decision from modelled state
        return scheduler_policy.pick_victim(
            (i, s.state, s.admit_seq)
            for i, s in enumerate(self._slots) if s is not None)

    def _preempt(self, slot: int) -> None:
        """Evict a resident back to the WAITING queue (front, original
        request intact, partial tokens discarded) and free its blocks.
        Readmission recomputes the prompt — recompute-not-swap, the
        vLLM default — and regenerates the same tokens (greedy argmax;
        sampled rows fold the rng by absolute position)."""
        st = self._slots[slot]
        self._slots[slot] = None
        self._done[slot] = True
        self._free.append(slot)
        self._release_slot_blocks(slot)
        self._preemptions += 1
        if st.state == "PREFILLING":
            self._prefill_preemptions += 1
        logger.warning("block pool dry: preempted %r (recompute on "
                       "readmission)", st.uri)
        with self._lock:
            self._waiting.appendleft(st.req)
        # TTFT keeps the original arrival; partial tokens are
        # discarded, so their stamps go too (telemetry mirrors both)
        self.telemetry.req_preempted(
            st.uri, slot, prefilling=st.state == "PREFILLING")

    def _release_slot_blocks(self, slot: int) -> None:
        """Drop a finished/preempted row's block references and point
        its whole table row at the sink, so the frozen row's future
        writes can NEVER touch a block the pool hands to someone else
        — the paged form of the arena's recycled-slot isolation.  Both
        tenants release together: a row never holds draft pages after
        its target pages are gone (or vice versa)."""
        blocks = self._row_blocks[slot]
        self._row_blocks[slot] = []
        self._tables[slot, :] = SINK_BLOCK
        dblocks = []
        if self._dpool is not None:
            dblocks = self._drow_blocks[slot]
            self._drow_blocks[slot] = []
            self._dtables[slot, :] = SINK_BLOCK
        with self._pool_lock:
            for b in blocks:
                self._pool.release(b)
            for b in dblocks:
                self._dpool.release(b)

    def resize_pool(self, target: int) -> int:
        """Grow or shrink BOTH tenants' block pools toward ``target``
        blocks (clamped to [floor, ceiling]) and pad/slice the device
        arenas to match.  Shrink only sheds the contiguous
        unreferenced TAIL of the id space — the arena is dense in
        block id, so the eviction boundary (``BlockPool.shrink``)
        stops at the first referenced block: cached tail blocks are
        evicted, a referenced block NEVER is, and a deeper request is
        clamped and counted rather than raised.  Both tenants move in
        lockstep (the min of their shrinkable tails) so the mirror-
        image invariant the speculative path relies on survives.
        Pump thread only: the arenas are donated through the step
        programs, so no device call may be in flight.  Returns the
        signed block delta actually applied."""
        if not self.paged:
            raise ValueError("resize_pool requires paged=True")
        want = int(target)
        target = max(self._pool_floor,
                     min(want, self._pool_ceiling or want))
        clamped = target != want
        with self._pool_lock:
            n = self._pool.n_blocks
            if target > n:
                applied = self._pool.grow(target - n)
                if self._dpool is not None:
                    self._dpool.grow(target - n)
            elif target < n:
                m = min(n - target, self._pool.shrinkable())
                if self._dpool is not None:
                    m = min(m, self._dpool.shrinkable())
                if m < n - target:
                    clamped = True
                applied = -self._pool.shrink(m) if m else 0
                if m and self._dpool is not None:
                    self._dpool.shrink(m)
            else:
                applied = 0
        # shrink evicts the cached tail: gather those blocks' bytes
        # into the host tier BEFORE fit() slices them off the arena
        self._drain_spills()
        if clamped:
            self._pool_resize_clamps += 1
        if applied == 0:
            return 0
        new_n = n + applied

        def fit(x):
            if applied > 0:
                pad = [(0, 0)] * x.ndim
                pad[1] = (0, applied)
                out = jnp.pad(x, pad)
            else:
                out = x[:, :new_n]
            # keep the mesh layout: a resized pool must land exactly
            # where the step programs expect their donated operands
            return jax.device_put(out, x.sharding)

        self._pk = jax.tree_util.tree_map(fit, self._pk)
        self._pv = jax.tree_util.tree_map(fit, self._pv)
        if self._dpool is not None:
            self._dpk = jax.tree_util.tree_map(fit, self._dpk)
            self._dpv = jax.tree_util.tree_map(fit, self._dpv)
        self._pool_resizes += 1
        logger.info("elastic pool resized %d -> %d blocks (%+d)",
                    n, new_n, applied)
        return applied

    def maybe_autoresize(self,
                         goodput: Optional[Dict[str, float]] = None
                         ) -> int:
        """One elastic-pool control step (pump thread): feed the
        current pool pressure — allocatable blocks and fresh
        allocation failures since the last call — plus the caller's
        per-class goodput map into the pure ``plan_pool_resize``
        policy, and execute any non-zero delta via ``resize_pool``.
        No-op (returns 0) unless built with ``elastic_pool=True``."""
        if not (self.paged and self.elastic_pool):
            return 0
        with self._pool_lock:
            n = self._pool.n_blocks
            alloc = self._pool.allocatable()
            fails = self._pool.alloc_failures
            if self._dpool is not None:
                alloc = min(alloc, self._dpool.allocatable())
                fails += self._dpool.alloc_failures
        streak = fails - self._autoresize_last_fails
        self._autoresize_last_fails = fails
        delta = scheduler_policy.plan_pool_resize(
            n_blocks=n, allocatable=alloc, alloc_fail_streak=streak,
            step=self._resize_step, floor=self._pool_floor,
            ceiling=self._pool_ceiling, goodput=goodput)
        if delta == 0:
            return 0
        return self.resize_pool(n + delta)

    def cache_metrics(self) -> dict:
        """Serving-visible cache counters.

        The snapshot is taken under the ENGINE lock (and, for the pool
        merge, the pool lock), so a caller on another thread can never
        see torn state — e.g. a queue depth from before a preemption
        merged with pool occupancy from after it.  Field semantics:

        - **cumulative** (monotonic since construction): ``preemptions``,
          ``prefill_stall_ticks``, ``prefill_preemptions``, and the
          pool's ``prefix_queries`` / ``prefix_hits`` / ``evictions`` /
          ``alloc_failures``.  ``peak_resident`` and
          ``budget_utilization`` are cumulative aggregates (running max
          / running mean), not resettable rates.
        - **instantaneous** (value at snapshot time):
          ``prefill_queue_depth``, ``chunks_in_flight``, and the pool's
          ``free_blocks`` / ``cached_blocks`` / ``referenced_blocks`` /
          ``occupancy`` (plus the static ``mode`` / ``chunked`` /
          ``tick_token_budget`` / ``n_blocks`` / ``block_size``).

        The same values are exported continuously (and individually
        documented) by the telemetry registry — this dict remains for
        callers that want one coherent point-in-time snapshot."""
        with self._lock:
            out = {
                "mode": "paged" if self.paged else "arena",
                "preemptions": self._preemptions,
                "peak_resident": self._peak_resident,
                "qos": self._qos is not None,
            }
            if self._qos is not None:
                out["qos_waiting"] = {
                    f"{cls}/{tenant}": d for (cls, tenant), d in
                    self._waiting.depths().items()}
            if self.chunked:
                denom = self._budget_ticks * self.tick_token_budget
                out.update({
                    "chunked": True,
                    "tick_token_budget": self.tick_token_budget,
                    # mean fraction of each fused tick's budget
                    # actually filled with decode rows + chunk tokens
                    "budget_utilization": (
                        self._budget_tokens_used / denom
                        if denom else 0.0),
                    # len() directly: self.n_waiting re-acquires the
                    # non-reentrant engine lock we already hold
                    "prefill_queue_depth": len(self._waiting),
                    "chunks_in_flight": sum(
                        1 for s in self._slots
                        if s is not None and s.state == "PREFILLING"),
                    "prefill_stall_ticks": self._prefill_stall_ticks,
                    "prefill_preemptions": self._prefill_preemptions,
                })
            if self.draft_model is not None:
                out.update({
                    "speculation_k": self._spec_k,
                    "spec_rounds": getattr(self, "_spec_rounds", 0),
                    "spec_emitted": getattr(self, "_spec_emitted", 0),
                    # cumulative draft proposals / acceptances (same
                    # counters /metrics exports)
                    "spec_proposed": self.telemetry.c_spec_proposed.value,
                    "spec_accepted": self.telemetry.c_spec_accepted.value,
                })
        if self.paged:
            with self._pool_lock:
                out.update(self._pool.metrics())
                if self._dpool is not None:
                    # draft tenant, prefixed — one snapshot shows both
                    # pools' pressure side by side
                    out.update({"draft_" + kk: vv for kk, vv in
                                self._dpool.metrics().items()})
            out.update({
                "pool_resizes": self._pool_resizes,
                "pool_resize_clamps": self._pool_resize_clamps,
                "pool_floor": self._pool_floor,
                "pool_ceiling": self._pool_ceiling,
                "handoffs_out": self._handoffs_out,
                "handoffs_in": self._handoffs_in,
                "kv_spills": self._kv_spills,
                "kv_spill_bytes": self._kv_spill_bytes,
                "kv_readmits": self._kv_readmits,
                "kv_readmit_tokens_saved":
                    self._kv_readmit_tokens_saved,
                "kv_store_bytes": (self._kv_store.occupancy_bytes
                                   if self._kv_store is not None
                                   else 0),
            })
        return out

    def _install_slot(self, slot, uri, plen, mn, on_done, on_error,
                      temp, seed, first, top_p=0.0, req=None,
                      on_token=None, priority=None):
        """Shared slot-state installation for every admission path —
        plain bucket splice and prefix admission must never drift."""
        self._slots[slot] = _Slot(
            uri=uri, plen=plen, max_new=self._brownout_mn(priority, mn),
            on_done=on_done,
            on_error=on_error, temperature=temp, rng_seed=seed,
            top_p=top_p, req=req, admit_seq=self._admit_seq,
            on_token=on_token)
        self._admit_seq += 1
        self._tok[slot] = first
        self._pos[slot] = plen
        if self.draft_model is not None:
            self._dpos[slot] = plen
        self._done[slot] = False
        self.telemetry.req_admitted(uri, slot, priority=priority)
        self._record_token(slot, int(first), "admit")

    def _splice_one(self, pre, i: int, req) -> None:
        """Insert one prefetched joiner into a free slot; the slot goes
        back to the free list if the splice fails."""
        last_logits, ks, vs = pre[0], pre[1], pre[2]
        uri, prompt = req.uri, req.prompt
        temp, seed, tp = req.temperature, req.rng_seed, req.top_p
        mn, on_done, on_error = req.max_new, req.on_done, req.on_error
        slot = self._free.popleft()
        try:
            self._ck, self._cv = self._insert(
                self._ck, self._cv, ks[:, i:i + 1], vs[:, i:i + 1],
                jnp.int32(slot))
            if self.draft_model is not None:
                dks, dvs = pre[3], pre[4]
                self._dck, self._dcv = self._insert(
                    self._dck, self._dcv, dks[:, i:i + 1],
                    dvs[:, i:i + 1], jnp.int32(slot))
            plen = len(prompt)
            first = self._pick_first(last_logits[i], plen, temp, seed,
                                     tp)
        except Exception:
            self._free.append(slot)
            raise
        self._install_slot(slot, uri, plen, mn, on_done, on_error,
                           temp, seed, first, tp,
                           on_token=req.on_token, priority=req.priority)

    def _pick_first(self, last_logits, plen: int, temp: float,
                    seed, top_p: float = 0.0) -> int:
        """The prefill's last-position logits produce the request's first
        token — same pick semantics (and rng position-fold) as
        ``generate``'s step at t = plen-1.  ``last_logits`` arrives as
        host numpy: every admission path fetches its whole group's
        logits in ONE transfer, so the common greedy pick costs zero
        device round-trips per request."""
        if temp <= 0.0:
            return int(np.argmax(last_logits))
        key = jax.random.fold_in(jax.random.key(int(seed)), plen - 1)
        scaled = jnp.asarray(last_logits, jnp.float32) / temp
        if top_p > 0.0:
            scaled = top_p_filter(scaled, jnp.float32(top_p))
        # sampled admission must reproduce pick_next's categorical
        # bitwise (a preempted-and-readmitted row regenerates the same
        # token), so the draw stays on device: one sync per SAMPLED
        # admission only (baselined).
        return int(jax.random.categorical(key, scaled))

    def _handoff_slot(self, slot: int, st: _Slot, phase: str) -> None:
        """Export a just-prefilled row for adoption by another engine
        (the prefill half of a prefill/decode handoff).  Runs on the
        pump thread at first-token time: snapshot the block chain +
        published hashes (``export_chain``), GATHER the row's pool
        slices into fresh device buffers (the live pool is DONATED
        through later step programs, so the copy must materialize
        now), then free the slot exactly like a completion.  The
        state dict is self-contained — the destination engine needs
        nothing further from this one."""
        blocks = list(self._row_blocks[slot])
        with self._pool_lock:
            chain = self._pool.export_chain(blocks)
        idx = jnp.asarray(blocks, jnp.int32)

        def gather(x):
            return jnp.take(x, idx, axis=1)

        state = {
            "uri": st.uri,
            "prompt": np.asarray(self._full_prompt(st.req), np.int32),
            "plen": st.plen,
            "pos": int(self._pos[slot]),
            "tokens": list(st.tokens),
            "last_token": int(st.tokens[-1]),
            "max_new": st.max_new,
            "priority": st.req.priority,
            "tenant": st.req.tenant,
            "chain": chain,
            "k": jax.tree_util.tree_map(gather, self._pk),
            "v": jax.tree_util.tree_map(gather, self._pv),
            "on_done": st.on_done,
            "on_error": st.on_error,
            "on_token": st.on_token,
        }
        self._slots[slot] = None
        self._done[slot] = True
        self._free.append(slot)
        self._release_slot_blocks(slot)
        self._handoffs_out += 1
        # this engine's part of the request is over — the destination
        # runs its own full enqueue->admit->finish telemetry lifecycle
        self.telemetry.req_finished(st.uri, slot, len(st.tokens))
        self._lap(phase)
        try:
            st.req.handoff_cb(state)
        except Exception as e:
            logger.exception("handoff callback failed for %r", st.uri)
            self._req_error(st.uri, st.on_error, e, "publish")
        self._lap("publish")

    def _record_token(self, slot: int, token: int, phase: str = "book"):
        """Append one generated token; finish + free the slot when done.
        ``phase`` is the cycle phase the caller is in (``admit`` for a
        monolithic admission's first token): the time inside a fired
        ``on_done`` / handoff callback is taken out of it and booked
        to ``publish``."""
        st = self._slots[slot]
        st.tokens.append(token)
        self.telemetry.req_token(st.uri, slot)
        if st.on_token is not None:
            # host-side emission hook (streaming): two list appends in
            # the serving emitter — no Redis I/O, no device sync here
            try:
                st.on_token(st.uri, token, len(st.tokens) - 1)
            except Exception:
                logger.exception("continuous-batching on_token callback "
                                 "failed for %r", st.uri)
        done = len(st.tokens) >= st.max_new or \
            (self.eos_id is not None and token == self.eos_id)
        if not done:
            if (len(st.tokens) == 1 and st.req is not None
                    and st.req.handoff_cb is not None):
                # prefill role: the first token is this engine's LAST —
                # export the row instead of decoding it here
                self._handoff_slot(slot, st, phase)
            return
        out = np.full(st.max_new,
                      self.eos_id if self.eos_id is not None else 0,
                      np.int32)
        out[:len(st.tokens)] = st.tokens      # frozen tail: eos padding
        self._slots[slot] = None
        self._done[slot] = True     # terminal state until readmission
        self._free.append(slot)
        if self.paged:
            # refcounts drop + table row -> sink BEFORE the next device
            # step, so a recycled block can never see this row's writes
            self._release_slot_blocks(slot)
        self.telemetry.req_finished(st.uri, slot, len(st.tokens))
        if st.on_done is not None:
            self._lap(phase)
            try:
                st.on_done(st.uri, out)
            except Exception:
                logger.exception("continuous-batching on_done callback "
                                 "failed for %r", st.uri)
            self._lap("publish")

    def step(self) -> int:
        """One engine iteration: admit joiners, then advance every
        resident by up to ``ticks_per_step`` tokens in one device call
        (capped by the largest remaining token budget among residents —
        a nearly-finished slot must not throttle the arena to 1-tick
        device calls; its surplus tokens are dropped host-side in
        ``_record_token``, and EOS mid-chunk freezes on-device like
        generate()'s frozen tail).  Returns the number of active
        slots afterwards (0 = idle; the caller decides how to wait).
        Higher ``ticks_per_step`` trades admission latency granularity
        for fewer host round-trips."""
        clock = self.telemetry.clock
        if self.n_active == 0 and not self._waiting:
            # idle poll (the serving pump spins on step()): no work to
            # do or measure, and no tick event to spam the ring with;
            # the pass's laps are summed into the open cycle
            clock.lap(clock.rest)
            clock.fold()
            return 0
        if self._fault is not None:
            self._fault_tick()
        # the laps at the step's two ends are the very clock readings
        # that give ts and dur: a cycle's laps sum to the time from the
        # end of the last step to the end of this one, with no remainder
        t0 = clock.lap(clock.rest)
        n = self._step_impl()
        dur = clock.lap("admit") - t0   # every tick path ends in _admit()
        phases, folded = clock.take()
        samples = self._tick_samples(n)
        self.telemetry.tick(t0, dur, samples, phases, folded)
        if self.flight is not None:
            self._flight_record(t0, dur, samples, phases)
        return n

    def _fault_tick(self) -> None:
        """Apply the due engine-side fault actions for this BUSY tick
        (serving/fault.py): a ``freeze_tick`` sleeps here (a wedged
        device — the pump misses heartbeats), an ``alloc_storm`` tick
        records a pool allocation failure (driving the alloc-fail
        streak, anomaly trigger, and router pressure without draining
        the pool), and a ``raise_step`` escapes as
        :class:`~analytics_zoo_tpu.serving.fault.InjectedFault` out of
        ``step()`` — the pump's crash handler path."""
        acts = self._fault.tick_actions(self._replica_id)
        if not acts:
            return
        freeze = acts.get("freeze_s", 0.0)
        if freeze > 0:
            time.sleep(freeze)
        if acts.get("alloc_fail") and self._pool is not None:
            with self._pool_lock:
                self._pool.alloc_failures += 1
        msg = acts.get("raise_step")
        if msg:
            from .fault import InjectedFault
            raise InjectedFault(msg)

    def _note_counters(self, counts, passes: int = 1) -> None:
        """Book the counters one device call of a model with an indexer
        or with state-space layers returned (``_counter_names``: the last
        a maximum, the others sums) to the tick's flight record, and the
        passes over the model the call made."""
        c, names = self._counts, self._counter_names
        for name, v in zip(names[:-1], counts[:-1]):
            c[name] += int(v)
        c[names[-1]] = max(c[names[-1]], int(counts[-1]))
        self._passes += passes

    def _tick_samples(self, n_active: int) -> dict:
        """Post-tick residency mix + queue/pool pressure, as plain host
        ints — the per-tick sample row of the ISSUE's event-log spec."""
        decode = prefill = 0
        for s in self._slots:
            if s is not None:
                if s.state == "DECODE":
                    decode += 1
                else:
                    prefill += 1
        samples = {"active": n_active, "decode_rows": decode,
                   "prefill_rows": prefill,
                   "queue_depth": len(self._waiting)}
        if self._pool is not None:
            with self._pool_lock:
                samples["free_blocks"] = self._pool.allocatable()
                if self._dpool is not None:
                    samples["draft_free_blocks"] = \
                        self._dpool.allocatable()
        return samples

    def _flight_record(self, ts: float, dur: float,
                       samples: dict, phases) -> None:
        """Append one tick snapshot to the flight ring: the telemetry
        samples plus resident row sets, tick kind, and the per-tick
        DELTAS of every cumulative counter an incident reader wants on
        a timeline (preemptions, compiles, chunk/budget consumption,
        spec acceptance, pool allocation failures).  All host ints
        already in hand — O(slots) work, no locks beyond one pool
        read, no device interaction."""
        last = self._flight_last

        def delta(key: str, cur: int) -> int:
            d = cur - last[key]
            last[key] = cur
            return d

        rec = dict(samples)
        rec["seq"] = self.flight.next_seq()
        rec["ts"] = round(ts, 6)
        rec["dur_ms"] = round(dur * 1e3, 3)
        # the cycle this tick closes, [phase, wall_ms, cpu_ms] in the
        # order the laps were taken: the wall_ms sum to (ts + dur_ms)
        # minus the previous record's (ts + dur_ms)
        rec["phases"] = [[name, round(wall * 1e3, 4), round(cpu * 1e3, 4)]
                         for name, wall, cpu in phases]
        rec["kind"] = self._tick_kind
        # which read path / storage mode this tick ran on — a bundle
        # reader's first question when a regression bisects to config
        rec["kernel"] = self.kernel if self.paged else "dense"
        rec["kv_dtype"] = self.kv_dtype
        rec["kv_bytes_per_token"] = self._kv_bytes_per_token
        decoding = [i for i, s in enumerate(self._slots)
                    if s is not None and s.state == "DECODE"]
        rec["decode_uris"] = [self._slots[i].uri for i in decoding]
        rec["prefill_uris"] = [s.uri for s in self._slots
                               if s is not None and s.state != "DECODE"]
        rec["preempted"] = delta("preempt", self._preemptions)
        rec["compiles"] = delta(
            "compiles", self.telemetry.c_jit_builds.value
            + self.telemetry.c_retraces.value)
        if self.chunked:
            rec["budget"] = self.tick_token_budget
            rec["budget_used"] = delta("budget_tokens",
                                       self._budget_tokens_used)
            rec["chunks"] = delta("chunks",
                                  self.telemetry.c_chunks.value)
        if self.draft_model is not None:
            rec["spec_proposed"] = delta(
                "spec_proposed", self.telemetry.c_spec_proposed.value)
            rec["spec_accepted"] = delta(
                "spec_accepted", self.telemetry.c_spec_accepted.value)
        if self._pool is not None:
            with self._pool_lock:
                af = self._pool.alloc_failures
                rec["used_blocks"] = self._pool.num_referenced()
                # schema v2: per-tenant pool SIZE per tick, so elastic
                # resizes are visible on the flight timeline
                rec["n_blocks"] = self._pool.n_blocks
                daf = (self._dpool.alloc_failures
                       if self._dpool is not None else 0)
                if self._dpool is not None:
                    rec["draft_used_blocks"] = \
                        self._dpool.num_referenced()
                    rec["draft_n_blocks"] = self._dpool.n_blocks
            rec["pool_resizes"] = delta("pool_resizes",
                                        self._pool_resizes)
            rec["handoffs_out"] = delta("handoffs_out",
                                        self._handoffs_out)
            rec["handoffs_in"] = delta("handoffs_in",
                                       self._handoffs_in)
            # schema v3: host-tier traffic per tick (tiered KV memory)
            rec["kv_spills"] = delta("kv_spills", self._kv_spills)
            rec["kv_readmits"] = delta("kv_readmits",
                                       self._kv_readmits)
            fails = delta("alloc_fail", af) \
                + delta("draft_alloc_fail", daf)
            rec["alloc_failures"] = fails
            # consecutive ticks with at least one failed allocation —
            # the anomaly monitor's "pool is dry and STAYING dry"
            self._alloc_fail_streak = \
                self._alloc_fail_streak + 1 if fails else 0
            rec["alloc_fail_streak"] = self._alloc_fail_streak
        if self.paged:
            # what the fused kernel's frontier stop has to skip: the
            # blocks the decode rows' next step reads, of the slots x
            # table-width blocks a grid over the whole table would visit
            rec["attn_live_blocks"] = int(
                (self._pos[decoding] // self._bs + 1).sum())
            rec["attn_table_blocks"] = self._tables.size
        if self.after_dispatch is not None:
            # a pump drives this engine: the token-stream events it sent
            # in this cycle, and those of them sent under a device call
            rec["flush_events"] = delta(
                "flush_events", self.telemetry.c_flush_events.value)
            rec["flush_events_overlapped"] = delta(
                "flush_overlapped",
                self.telemetry.c_flush_overlapped.value)
        if self._counter_names:
            # from the step programs themselves (docs/observability.md):
            # what the selection read and how the experts were loaded (a
            # model with an indexer), or whose recurrent state the tick
            # advanced and what the experts held here were sent (one with
            # state-space layers); the record of any other model has none
            rec.update(self._counts)
            if self._ssm:
                # a row's state is read once and written once a pass, and
                # a step of ``ticks_per_step`` tokens passes the model
                # that many times
                rec["ssm_state_bytes"] = 2 * self._ssm_row_bytes \
                    * self._counts["ssm_rows"]
                rec["ssm_passes"] = self._passes
            self._counts = dict.fromkeys(self._counter_names, 0)
            self._passes = 0
        if self._qos is not None:
            rec["qos_depths"] = {f"{c}/{t}" if t else c: n
                                 for (c, t), n in
                                 self._waiting.depths().items()}
        # schema v3 pure additions: brownout/deadline fields appear
        # only once the feature is live, so records from untouched
        # engines stay byte-identical to the pre-brownout build
        if self._brownout_enabled:
            rec["brownout_level"] = self._brownout_level
        if self._deadline_seen:
            rec["deadline_sheds"] = delta("deadline_sheds",
                                          self._deadline_sheds)
        self.flight.record(rec)

    @property
    def alloc_fail_streak(self) -> int:
        """Consecutive ticks whose flight record saw >= 1 block-pool
        allocation failure (0 when not paged or currently healthy)."""
        return self._alloc_fail_streak

    # ---- overload brownout (docs/serving_qos.md) ----------------------

    @property
    def brownout_level(self) -> int:
        return self._brownout_level

    @property
    def deadline_sheds(self) -> int:
        """Requests shed at admission because their deadline already
        passed (separate from the supervisor's in-flight give-ups)."""
        return self._deadline_sheds

    def set_brownout(self, level: int,
                     standard_max_new: int = 0) -> None:
        """Push the broker controller's ladder level into per-tick
        engine state (thread-safe: plain int stores the pump reads at
        tick boundaries).  Level >= 1 defers batch-class admission,
        >= 2 clamps standard-class ``max_new`` to ``standard_max_new``,
        >= 3 drops speculative rounds (the target decodes alone — the
        draft cache goes cold for in-flight rows, which costs
        acceptance after recovery, never correctness: the verify step
        is what picks tokens), >= 4 admits interactive only.  Never
        calling this keeps every gate at 0 and the engine bit-identical
        to the pre-brownout build."""
        self._brownout_enabled = True
        self._brownout_level = max(
            0, min(int(level), scheduler_policy.BROWNOUT_MAX_LEVEL))
        self._brownout_clamp = max(0, int(standard_max_new))

    def _brownout_mn(self, priority, mn: int) -> int:
        """Level-2 token clamp at slot install (one choke point per
        admission family; handoff adoption is exempt — its token count
        is already mid-flight)."""
        if self._brownout_level < 2:
            return mn
        return scheduler_policy.brownout_max_new(
            self._brownout_level, priority, mn, self._brownout_clamp)

    def spec_acceptance(self) -> Optional[dict]:
        """The recorded speculative-acceptance distribution (exact
        counts of accepted draft tokens per row per verify round,
        0..k), or None when the engine has no draft model.  This is
        the calibration section ``dump_bundle`` ships so the
        discrete-event simulator (docs/simulation.md) models
        acceptance from RECORDED data instead of re-deriving it from
        raw ticks."""
        if self.draft_model is None:
            return None
        section = self.telemetry.spec_acceptance()
        section["k"] = self._spec_k
        return section

    def _step_impl(self) -> int:
        self._tick_kind = "decode"
        lap = self._lap
        self._admit()
        lap("admit")
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return 0
        # brownout level >= 3: speculative rounds are dropped — the
        # dispatch below falls through to the target-only tick paths.
        # Mechanically safe: _ensure_blocks/_grow_chunk_blocks still
        # cover pos + spec_k writes, draft tables grow in lockstep, and
        # _dpos merely goes stale (proposals degrade after recovery;
        # the target verify alone picks tokens, so outputs stay exact).
        spec_on = (self.draft_model is not None
                   and scheduler_policy.brownout_spec_enabled(
                       self._brownout_level))
        if spec_on:
            if self.chunked and any(
                    self._slots[i].state == "PREFILLING"
                    for i in active):
                self._tick_kind = "spec_chunked"
                return self._spec_chunked_tick(active)
            self._tick_kind = "spec"
            if self.paged:
                # grow BOTH tenants' tables to cover the round's k+1
                # verify writes; may preempt
                active = self._ensure_blocks(active)
                if not active:
                    lap("plan")
                    self._admit()   # preemptions freed blocks
                    return self.n_active
            return self._spec_tick(active)
        if self.chunked and any(self._slots[i].state == "PREFILLING"
                                for i in active):
            self._tick_kind = "chunked"
            return self._chunked_tick(active)
        # a chunked engine with NO prefill in flight decodes on the
        # ORIGINAL (multi-tick, scan-amortised) path below — chunking
        # costs nothing in steady state
        if self.paged:
            # grow block tables for the coming chunk; may preempt
            active = self._ensure_blocks(active)
            if not active:
                lap("plan")
                self._admit()   # preemptions freed blocks: retry now
                return self.n_active
        self._peak_resident = max(self._peak_resident, len(active))
        sampled = any(self._slots[i].temperature > 0.0 for i in active)
        use_topp = any(self._slots[i].top_p > 0.0 for i in active)
        temps = np.zeros(self._S, np.float32)
        seeds = np.zeros(self._S, np.uint32)
        topps = np.zeros(self._S, np.float32)
        for i in active:
            temps[i] = self._slots[i].temperature
            seeds[i] = self._slots[i].rng_seed or 0
            topps[i] = self._slots[i].top_p
        n_eff = max(1, min(
            self.ticks_per_step,
            max(self._slots[i].max_new - len(self._slots[i].tokens)
                for i in active)))
        if self.draft_model is not None:
            # only reachable with spec browned out (level >= 3):
            # single-tick steps keep the write frontier inside the
            # pos + spec_k coverage _ensure_blocks grants this engine
            n_eff = 1
        elif self._ssm and n_eff < self.ticks_per_step:
            # two step programs and no more (precompile_chunked warms
            # both): the last tokens of a draining engine go one a call
            n_eff = 1
        step = self._get_step(n_eff, sampled, use_topp)
        lap("plan")
        if self.paged:
            # (*dsa: the four counters of a model with an indexer, and
            # nothing for any other model)
            toks, tok, pos, done, *dsa, self._pk, self._pv = step(
                self._pk, self._pv, jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(self._tables, jnp.int32),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(topps, jnp.float32))
        else:
            toks, tok, pos, done, self._ck, self._cv = step(
                self._ck, self._cv, jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(topps, jnp.float32))
        self._dispatched()
        toks = np.asarray(toks)                     # [n_eff, S]
        # np.asarray of a jax array is a read-only view; _admit writes
        # per-slot entries, so take mutable copies
        self._tok = np.array(tok)
        self._pos = np.array(pos)
        self._done = np.array(done)
        if self._counter_names:
            self._note_counters(np.asarray(dsa[0]), n_eff)
        lap("device_wait")
        for i in active:
            for j in range(n_eff):
                if self._slots[i] is None:
                    break       # finished mid-chunk; the rest is frozen
                self._record_token(i, int(toks[j, i]))
        lap("book")
        self._admit()       # freed slots recycle on the SAME iteration
        return self.n_active

    def _sampling_vectors(self, rows):
        """[S]-wide temperature/seed/top_p staging vectors with entries
        only at ``rows`` (other rows are frozen or empty — their picks
        are discarded, so zeros are fine)."""
        temps = np.zeros(self._S, np.float32)
        seeds = np.zeros(self._S, np.uint32)
        topps = np.zeros(self._S, np.float32)
        for i in rows:
            temps[i] = self._slots[i].temperature
            seeds[i] = self._slots[i].rng_seed or 0
            topps[i] = self._slots[i].top_p
        return temps, seeds, topps

    def _reanchor_prefill(self) -> None:
        """Re-pin every still-PREFILLING row's decode-side state after
        a device step: frozen (done=True), fed pad, positioned at the
        fill frontier — the decode part of the next fused tick then
        writes its one dead K/V entry exactly where the row's own next
        chunk will overwrite it."""
        for i, st in enumerate(self._slots):
            if st is not None and st.state == "PREFILLING":
                self._done[i] = True
                self._pos[i] = st.fill_pos
                if self.draft_model is not None:
                    self._dpos[i] = st.fill_pos
                self._tok[i] = self.pad_id

    def _grant_rank(self, slot: int):
        """Prefill-grant sort key for the chunked ticks.  QoS off: the
        admission sequence number — bit-identical FIFO to the
        pre-front-door engine (the parity guarantee).  QoS on: aged
        priority class first, FIFO within a class, so an interactive
        prompt's chunks land ahead of a batch prompt admitted earlier
        while aging still bounds how long batch can be outranked.
        Delegates to the pure ``serving/policy.py`` key — the
        simulator sorts with the same function on virtual time."""
        st = self._slots[slot]
        req = st.req
        if req is None:
            return scheduler_policy.grant_rank(
                self._qos, None, 0.0, st.admit_seq)
        return scheduler_policy.grant_rank(
            self._qos, req.priority, time.monotonic() - req.enq_t,
            st.admit_seq)

    def _chunked_tick(self, active) -> int:
        """One budget-bounded fused iteration (the tentpole): every
        DECODE row advances one token AND up to ``tick_token_budget -
        n_decode`` tokens of PREFILLING prompts land, in ONE device
        call.  Chunks are granted FIFO by admission order (aged
        priority class first under a QoS policy — ``_grant_rank``); a
        prompt's final chunk also picks its first token inside the same
        program (no extra admission forward, no decode stall)."""
        decode_rows = [i for i in active
                       if self._slots[i].state == "DECODE"]
        prefill_rows = sorted(
            (i for i in active
             if self._slots[i].state == "PREFILLING"),
            key=self._grant_rank)
        # budget billing is pure policy (serving/policy.py): decode
        # rows cost 1 position each, the remainder grants chunks in
        # grant order
        chunks, stalled = scheduler_policy.plan_chunks(
            self.tick_token_budget, 1, len(decode_rows),
            [(i, self._slots[i].plen - self._slots[i].fill_pos)
             for i in prefill_rows],
            self._chunk_buckets[-1])
        if stalled:
            # budget fully consumed by decode rows: prefill waits
            self._prefill_stall_ticks += 1
        if self.paged:
            self._grow_chunk_blocks(decode_rows, chunks)  # may preempt
            decode_rows = [i for i in decode_rows
                           if self._slots[i] is not None]
            chunks = [(i, c) for i, c in chunks
                      if self._slots[i] is not None]
        lap = self._lap
        if not decode_rows and not chunks:
            lap("plan")
            self._admit()       # preemptions may have freed blocks
            return self.n_active
        self._peak_resident = max(self._peak_resident, len(active))
        self._budget_ticks += 1
        self._budget_tokens_used += len(decode_rows) \
            + sum(c for _, c in chunks)
        if not chunks:
            return self._decode_only_tick(decode_rows)
        with_decode = bool(decode_rows)
        crows = [i for i, _ in chunks]
        sampled = any(self._slots[i].temperature > 0.0
                      for i in decode_rows + crows)
        use_topp = any(self._slots[i].top_p > 0.0
                       for i in decode_rows + crows)
        temps, seeds, topps = self._sampling_vectors(decode_rows)
        # ---- chunk grid: pow2 rows x bucketed width ----
        k = len(chunks)
        kb = 1 << (k - 1).bit_length()
        Cb = _next_bucket(max(c for _, c in chunks),
                          self._chunk_buckets)
        ctoks = np.full((kb, Cb), self.pad_id, np.int32)
        cpos = np.zeros(kb, np.int32)
        clens = np.ones(kb, np.int32)
        cslots = np.full(kb, self._S, np.int32)     # pad rows: drop
        ctemps = np.zeros(kb, np.float32)
        cseeds = np.zeros(kb, np.uint32)
        ctopps = np.zeros(kb, np.float32)
        for j, (i, clen) in enumerate(chunks):
            st = self._slots[i]
            off = st.fill_pos - st.base
            ctoks[j, :clen] = st.full[off:off + clen]
            cpos[j] = st.fill_pos
            clens[j] = clen
            cslots[j] = i
            ctemps[j] = st.temperature
            cseeds[j] = st.rng_seed or 0
            ctopps[j] = st.top_p
        need = int((cpos + clens).max())
        if self.paged:
            Mb = self._table_width(-(-need // self._bs))
            ctabs = np.full((kb, Mb), SINK_BLOCK, np.int32)
            for j, (i, _) in enumerate(chunks):
                ctabs[j] = self._tables[i, :Mb]
            fused = self._get_fused(with_decode, sampled, use_topp)
            t_fused = lap("plan")
            nxt, pos2, done2, cnxt, *dsa, self._pk, self._pv = fused(
                self._pk, self._pv,
                jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(self._tables, jnp.int32),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(topps, jnp.float32),
                jnp.asarray(ctoks, jnp.int32),
                jnp.asarray(cpos, jnp.int32),
                jnp.asarray(clens, jnp.int32),
                jnp.asarray(ctabs, jnp.int32),
                jnp.asarray(ctemps, jnp.float32),
                jnp.asarray(cseeds, jnp.uint32),
                jnp.asarray(ctopps, jnp.float32),
                # (a model with state-space layers: whose state each
                # chunk row carries)
                *((jnp.asarray(cslots, jnp.int32),) if self._ssm else ()))
        else:
            read_len = next(b for b in self._read_buckets
                            if b >= need)
            fused = self._get_fused(with_decode, sampled, use_topp,
                                    read_len)
            t_fused = lap("plan")
            nxt, pos2, done2, cnxt, self._ck, self._cv = fused(
                self._ck, self._cv,
                jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(topps, jnp.float32),
                jnp.asarray(ctoks, jnp.int32),
                jnp.asarray(cpos, jnp.int32),
                jnp.asarray(clens, jnp.int32),
                jnp.asarray(cslots, jnp.int32),
                jnp.asarray(ctemps, jnp.float32),
                jnp.asarray(cseeds, jnp.uint32),
                jnp.asarray(ctopps, jnp.float32))
        self._dispatched()
        # one host sync for decode picks + chunk first-token picks
        if self._counter_names:
            nxt, pos2, done2, cnxt, counts = jax.device_get(
                (nxt, pos2, done2, cnxt, dsa[0]))
            self._note_counters(counts, 1 + with_decode)
        else:
            nxt, pos2, done2, cnxt = jax.device_get(
                (nxt, pos2, done2, cnxt))
        # all of a tick's chunks land in the one fused call above, so
        # they share its span (per-chunk device timing doesn't exist)
        dur_fused = lap("device_wait") - t_fused
        for i, clen in chunks:
            self.telemetry.events.span(
                "prefill_chunk", t_fused, dur_fused, i,
                {"uri": self._slots[i].uri, "tokens": int(clen),
                 "fill_pos": int(self._slots[i].fill_pos)})
        self.telemetry.c_chunks.inc(len(chunks))
        if with_decode:
            self._tok = np.array(nxt)
            self._pos = np.array(pos2)
            self._done = np.array(done2)
        completed: List[Tuple[int, int]] = []
        for j, (i, clen) in enumerate(chunks):
            st = self._slots[i]
            st.fill_pos += clen
            if self.paged:
                self._publish_chunk_blocks(i, st)
            if st.fill_pos >= st.plen:
                completed.append((i, int(cnxt[j])))
        for i, first in completed:
            st = self._slots[i]
            st.state = "DECODE"
            st.full = st.hashes = None
            self._tok[i] = first
            self._pos[i] = st.plen
            self._done[i] = False
            self._record_token(i, first)    # the request's FIRST token
        self._reanchor_prefill()
        for i in decode_rows:
            if self._slots[i] is not None:
                self._record_token(i, int(nxt[i]))
        lap("book")
        self._admit()       # freed slots recycle on the SAME iteration
        return self.n_active

    def _decode_only_tick(self, decode_rows) -> int:
        """Budget tick with no chunk grants (budget exhausted by decode
        rows, or every prefill row preempted): one unfused 1-tick step
        — the SAME compiled program as the non-chunked path, so no
        extra compile — then re-anchor the frozen PREFILLING rows."""
        sampled = any(self._slots[i].temperature > 0.0
                      for i in decode_rows)
        use_topp = any(self._slots[i].top_p > 0.0 for i in decode_rows)
        temps, seeds, topps = self._sampling_vectors(decode_rows)
        step = self._get_step(1, sampled, use_topp)
        lap = self._lap
        lap("plan")
        if self.paged:
            # (*dsa: the four counters of a model with an indexer, and
            # nothing for any other model)
            toks, tok, pos, done, *dsa, self._pk, self._pv = step(
                self._pk, self._pv, jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(self._tables, jnp.int32),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(topps, jnp.float32))
        else:
            toks, tok, pos, done, self._ck, self._cv = step(
                self._ck, self._cv, jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(topps, jnp.float32))
        self._dispatched()
        toks = np.asarray(toks)
        self._tok = np.array(tok)
        self._pos = np.array(pos)
        self._done = np.array(done)
        if self._counter_names:
            self._note_counters(np.asarray(dsa[0]))
        lap("device_wait")
        self._reanchor_prefill()
        for i in decode_rows:
            if self._slots[i] is not None:
                self._record_token(i, int(toks[0, i]))
        lap("book")
        self._admit()
        return self.n_active

    def precompile_chunked(self, sampled: bool = False,
                           use_topp: bool = False,
                           max_chunk_rows: Optional[int] = None) -> int:
        """Eagerly compile the chunked scheduler's whole fused-program
        shape grid, so steady-state serving compiles NOTHING regardless
        of arrival timing — a cold-start aid for latency-sensitive
        deployments (and for benchmarks, where a first-encounter
        compile inside a percentile would be measured as a stall).

        The grid is exactly the bounded space ``_chunked_tick`` can
        reach: chunk-row counts (pow2 up to ``max_chunk_rows``, default
        ``max_slots``), chunk widths (the prompt buckets that fit the
        budget), with/without live decode rows, and per shape the arena
        read window (pow2 buckets, capped at the largest prompt bucket)
        or the paged narrow-table width (pow2, same cap).  Unreachable
        combinations are pruned: a chunk width bucket ``Cb`` implies
        some chunk longer than the previous bucket, so windows that
        cannot contain such a chunk are skipped.  Returns the number of
        (program, shape) variants visited.  Dummy buffers are used
        throughout — engine state is untouched."""
        if not self.chunked:
            raise ValueError("precompile_chunked requires chunked=True")
        if self._ssm and self.n_active:
            # a second, zeroed state arena beside the first would not fit
            # where the first was sized to fill the chip
            raise RuntimeError(
                "precompile_chunked of a model with state-space layers "
                "runs on the engine's own caches: call it before serving")
        S = self._S
        kmax = min(max_chunk_rows or S, S)
        kbs, kb = [], 1
        while kb < kmax:
            kbs.append(kb)
            kb *= 2
        kbs.append(kb)
        max_prompt = self.prompt_buckets[-1]
        tok = jnp.zeros(S, jnp.int32)
        pos = jnp.zeros(S, jnp.int32)
        done = jnp.ones(S, jnp.bool_)
        temps = jnp.zeros(S, jnp.float32)
        seeds = jnp.zeros(S, jnp.uint32)
        topps = jnp.zeros(S, jnp.float32)
        count = 0
        for ci, Cb in enumerate(self._chunk_buckets):
            prev = self._chunk_buckets[ci - 1] if ci else 0
            # the need (max fill frontier) that selects this Cb spans
            # (prev, max_prompt]: every window bucket covering part of
            # that range is reachable, nothing else is
            if self.paged:
                lo = self._table_width(-(-(prev + 1) // self._bs))
                hi = self._table_width(-(-max_prompt // self._bs))
                widths = []
                v = lo
                while v <= hi:
                    widths.append(v)
                    if v >= self._M:
                        break
                    v *= 2
            else:
                # window b serves need in (previous bucket, b]; keep it
                # iff that range overlaps the reachable (prev,
                # max_prompt]
                widths = [b for bi, b in enumerate(self._read_buckets)
                          if b > prev
                          and (self._read_buckets[bi - 1] if bi else 0)
                          < max_prompt]
            for kb in kbs:
                ctoks = jnp.full((kb, Cb), self.pad_id, jnp.int32)
                cpos = jnp.zeros(kb, jnp.int32)
                clens = jnp.ones(kb, jnp.int32)
                cslots = jnp.full(kb, S, jnp.int32)
                czeros = (jnp.zeros(kb, jnp.float32),
                          jnp.zeros(kb, jnp.uint32),
                          jnp.zeros(kb, jnp.float32))
                for width in widths:
                    if self.draft_model is not None:
                        # spec engines never run the fused program —
                        # their chunk half is the two-tenant spec chunk
                        # program (one variant per grid shape, no
                        # with_decode/sampled axes: greedy-only, and
                        # the decode half is the separate spec round)
                        if self.paged:
                            self._spec_chunk_paged(
                                _zeros_like(self._pk),
                                _zeros_like(self._pv),
                                _zeros_like(self._dpk),
                                _zeros_like(self._dpv),
                                ctoks, cpos, clens,
                                jnp.full((kb, width), SINK_BLOCK,
                                         jnp.int32),
                                jnp.full((kb, width), SINK_BLOCK,
                                         jnp.int32))
                        else:
                            self._spec_chunk(
                                jnp.zeros_like(self._ck),
                                jnp.zeros_like(self._cv),
                                jnp.zeros_like(self._dck),
                                jnp.zeros_like(self._dcv),
                                ctoks, cpos, clens, cslots,
                                read_len=width)
                        count += 1
                        continue
                    for wd in (False, True):
                        if self._ssm:
                            # on the engine's OWN caches (idle: checked
                            # above).  Every decode row is frozen and
                            # every chunk row padding (sink tables,
                            # out-of-range slots), so the call hands them
                            # back as it got them but for the sink block
                            fn = self._get_fused(wd, sampled, use_topp)
                            *_, self._pk, self._pv = fn(
                                self._pk, self._pv, tok, pos, done,
                                jnp.full((S, self._M), SINK_BLOCK,
                                         jnp.int32),
                                temps, seeds, topps, ctoks, cpos, clens,
                                jnp.full((kb, width), SINK_BLOCK,
                                         jnp.int32),
                                *czeros, cslots)
                            # tpulint: disable-next-line=TZ001
                            jax.block_until_ready(self._pv)
                        elif self.paged:
                            fn = self._get_fused(wd, sampled, use_topp)
                            # wait for the call: the next one allocates
                            # its zeroed pools when it is dispatched,
                            # and two calls in flight hold THREE pools.
                            # Warm-up, not a serving loop: the sync is
                            # the point
                            # tpulint: disable-next-line=TZ001
                            jax.block_until_ready(fn(
                                _zeros_like(self._pk),
                                _zeros_like(self._pv),
                                tok, pos, done,
                                jnp.full((S, self._M), SINK_BLOCK,
                                         jnp.int32),
                                temps, seeds, topps, ctoks, cpos,
                                clens,
                                jnp.full((kb, width), SINK_BLOCK,
                                         jnp.int32),
                                *czeros))
                        else:
                            fn = self._get_fused(wd, sampled,
                                                 use_topp, width)
                            fn(jnp.zeros_like(self._ck),
                               jnp.zeros_like(self._cv),
                               tok, pos, done, temps, seeds, topps,
                               ctoks, cpos, clens, cslots, *czeros)
                        count += 1
        if self.draft_model is not None:
            # the decode half of a spec chunk tick: one shape-stable
            # spec-round program
            if self.paged:
                self._spec_step_paged(
                    _zeros_like(self._pk), _zeros_like(self._pv),
                    _zeros_like(self._dpk),
                    _zeros_like(self._dpv),
                    tok, pos, pos, done,
                    jnp.full((S, self._M), SINK_BLOCK, jnp.int32),
                    jnp.full((S, self._M), SINK_BLOCK, jnp.int32))
            else:
                self._spec_step(
                    jnp.zeros_like(self._ck), jnp.zeros_like(self._cv),
                    jnp.zeros_like(self._dck),
                    jnp.zeros_like(self._dcv),
                    tok, pos, pos, done)
            count += 1
        if self._ssm and self.ticks_per_step > 1:
            # the decode step of ``ticks_per_step`` tokens: the first
            # request's last tokens go one a call (``_step_impl``), so no
            # warm-up request would reach it
            *_, self._pk, self._pv = self._get_step(
                self.ticks_per_step, sampled, use_topp)(
                self._pk, self._pv, tok, pos, done,
                jnp.full((S, self._M), SINK_BLOCK, jnp.int32),
                temps, seeds, topps)
            # tpulint: disable-next-line=TZ001
            jax.block_until_ready(self._pv)
            count += 1
        return count

    def paged_step_memory(self, program: str = "decode"
                          ) -> Dict[str, int]:
        """What the compiler reserves for one paged step program,
        beside the pool, per device: ``pool_bytes`` (ONE of the two
        pools; under a mesh one device's shard of it), ``temp_bytes``
        and ``alias_bytes`` from ``memory_analysis()`` of the program
        compiled for this engine's devices on abstract operands
        (nothing runs, engine state is untouched).

        ``program``: ``"decode"`` (the one-tick step), ``"chunk"`` (a
        chunk tick without decode rows) or ``"fused"`` (decode + chunk),
        the chunk half at one row of the widest chunk bucket and the
        full table width.  The in-place contract of the
        paged step (docs/serving_memory.md) reads here: both donated
        pools aliased to the outputs (``alias_bytes >= 2 *
        pool_bytes``) and temporaries far under one LAYER's slice of a
        pool — a slice or a stack of layers inside the program, or a
        write the compiler cannot do in place, shows as pool-sized
        temporaries.  ``tests/test_paged_inplace.py`` and
        ``chip_smoke.py`` hold it."""
        if not self.paged or self.draft_model is not None:
            raise ValueError("paged_step_memory reads the plain paged "
                             "step programs (paged=True, no draft)")
        if program not in ("decode", "chunk", "fused"):
            raise ValueError(f"program must be 'decode', 'chunk' or "
                             f"'fused', got {program!r}")
        if program != "decode" and not self.chunked:
            raise ValueError(f"{program!r} needs chunked=True")

        spec = jax.ShapeDtypeStruct

        def like(a):
            return spec(a.shape, a.dtype, sharding=a.sharding)

        S, kb = self._S, 1
        pk = jax.tree_util.tree_map(like, self._pk)
        pv = jax.tree_util.tree_map(like, self._pv)
        rows = (spec((S,), jnp.int32), spec((S,), jnp.int32),
                spec((S,), jnp.bool_), spec((S, self._M), jnp.int32),
                spec((S,), jnp.float32), spec((S,), jnp.uint32),
                spec((S,), jnp.float32))
        if program == "decode":
            lowered = self._get_step(1, False).lower(pk, pv, *rows)
        else:
            Cb = self._chunk_buckets[-1]
            lowered = self._get_fused(
                program == "fused", False, False).lower(
                pk, pv, *rows, spec((kb, Cb), jnp.int32),
                spec((kb,), jnp.int32), spec((kb,), jnp.int32),
                spec((kb, self._M), jnp.int32),
                spec((kb,), jnp.float32), spec((kb,), jnp.uint32),
                spec((kb,), jnp.float32),
                *((spec((kb,), jnp.int32),) if self._ssm else ()))
        mem = lowered.compile().memory_analysis()
        return {
            "pool_bytes": sum(a.addressable_shards[0].data.nbytes
                              for a in
                              jax.tree_util.tree_leaves(self._pk)),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}

    def _spec_tick(self, active) -> int:
        """One speculative round for the whole batch: every resident
        advances by its own accepted count (1..k+1 tokens) in one device
        call.  Paged dispatch already grew both tenants' block tables
        (``_ensure_blocks``)."""
        self._peak_resident = max(self._peak_resident, len(active))
        self._spec_round(active)
        self._admit()       # freed slots recycle on the SAME iteration
        return self.n_active

    def _spec_round(self, rows) -> None:
        """Run the spec-round program (arena or paged) and record each
        row's emitted tokens.  Emission recording mirrors the plain
        path: per slot, in order, stopping when the slot finishes
        (budget surplus dropped host-side).  PREFILLING rows ride along
        frozen (done=True -> n_emit=0); their k+1 garbage writes land at
        or past the fill frontier, where their own chunks (and, after
        the flip, their first verify) overwrite them before anything
        attends that far."""
        lap = self._lap
        lap("plan")
        if self.paged:
            (toks, n_emit, tok, pos, dpos, done, self._pk, self._pv,
             self._dpk, self._dpv) = self._spec_step_paged(
                self._pk, self._pv, self._dpk, self._dpv,
                jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._dpos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_),
                jnp.asarray(self._tables, jnp.int32),
                jnp.asarray(self._dtables, jnp.int32))
        else:
            (toks, n_emit, tok, pos, dpos, done, self._ck, self._cv,
             self._dck, self._dcv) = self._spec_step(
                self._ck, self._cv, self._dck, self._dcv,
                jnp.asarray(self._tok, jnp.int32),
                jnp.asarray(self._pos, jnp.int32),
                jnp.asarray(self._dpos, jnp.int32),
                jnp.asarray(self._done, jnp.bool_))
        self._dispatched()
        toks = np.asarray(toks)                 # [k+1, S]
        n_emit = np.asarray(n_emit)
        self._tok = np.array(tok)
        self._pos = np.array(pos)
        self._dpos = np.array(dpos)
        self._done = np.array(done)
        lap("device_wait")
        self._spec_rounds = getattr(self, "_spec_rounds", 0) + 1
        self._spec_emitted = getattr(self, "_spec_emitted", 0) + int(
            n_emit[rows].sum())
        # acceptance accounting: every live row consumed k proposals;
        # n_emit-1 of them matched (eos clipping only shortens usage)
        emitting = [i for i in rows if int(n_emit[i]) > 0]
        lens = [int(n_emit[i]) - 1 for i in emitting]
        self.telemetry.spec_round(self._spec_k * len(emitting),
                                  sum(lens), lens)
        for i in rows:
            for j in range(int(n_emit[i])):
                if self._slots[i] is None:
                    break       # finished mid-round; the rest is frozen
                self._record_token(i, int(toks[j, i]))
        lap("book")

    def _spec_chunked_tick(self, active) -> int:
        """Chunked tick with a draft model: ONE token budget covers
        both work-item kinds — each DECODE row costs ``k+1`` verify
        positions, the remainder grants prefill chunks FIFO by
        admission order, exactly like ``_chunked_tick``.  Two device
        calls (spec round + spec chunk program, see
        ``_init_speculative``); with no PREFILLING rows in flight the
        dispatcher never enters here, so steady-state decoding pays
        the plain one-call spec tick."""
        decode_rows = [i for i in active
                       if self._slots[i].state == "DECODE"]
        prefill_rows = sorted(
            (i for i in active
             if self._slots[i].state == "PREFILLING"),
            key=self._grant_rank)
        per_row = self._spec_k + 1
        # same pure billing as _chunked_tick, with every decode row
        # costing its k+1 verify positions
        chunks, stalled = scheduler_policy.plan_chunks(
            self.tick_token_budget, per_row, len(decode_rows),
            [(i, self._slots[i].plen - self._slots[i].fill_pos)
             for i in prefill_rows],
            self._chunk_buckets[-1])
        if stalled:
            # budget fully consumed by verify rows: prefill waits
            self._prefill_stall_ticks += 1
        if self.paged:
            self._grow_chunk_blocks(decode_rows, chunks)  # may preempt
            decode_rows = [i for i in decode_rows
                           if self._slots[i] is not None]
            chunks = [(i, c) for i, c in chunks
                      if self._slots[i] is not None]
        if not decode_rows and not chunks:
            self._lap("plan")
            self._admit()       # preemptions may have freed blocks
            return self.n_active
        self._peak_resident = max(self._peak_resident, len(active))
        self._budget_ticks += 1
        self._budget_tokens_used += per_row * len(decode_rows) \
            + sum(c for _, c in chunks)
        if decode_rows:
            self._spec_round(decode_rows)
        # a round can finish rows but never kills chunk rows (they are
        # PREFILLING — frozen in the round); re-filter for safety
        chunks = [(i, c) for i, c in chunks
                  if self._slots[i] is not None]
        if chunks:
            self._spec_chunks(chunks)
        self._reanchor_prefill()
        self._lap("book")
        self._admit()       # freed slots recycle on the SAME iteration
        return self.n_active

    def _spec_chunks(self, chunks) -> None:
        """Land this tick's prefill chunks in BOTH models' caches (one
        device call) and flip prompts whose last chunk landed into
        DECODE with their first token — the spec twin of
        ``_chunked_tick``'s chunk half, greedy-only."""
        k = len(chunks)
        kb = 1 << (k - 1).bit_length()
        Cb = _next_bucket(max(c for _, c in chunks),
                          self._chunk_buckets)
        ctoks = np.full((kb, Cb), self.pad_id, np.int32)
        cpos = np.zeros(kb, np.int32)
        clens = np.ones(kb, np.int32)
        cslots = np.full(kb, self._S, np.int32)     # pad rows: drop
        for j, (i, clen) in enumerate(chunks):
            st = self._slots[i]
            off = st.fill_pos - st.base
            ctoks[j, :clen] = st.full[off:off + clen]
            cpos[j] = st.fill_pos
            clens[j] = clen
            cslots[j] = i
        need = int((cpos + clens).max())
        lap = self._lap
        if self.paged:
            Mb = self._table_width(-(-need // self._bs))
            ctabs = np.full((kb, Mb), SINK_BLOCK, np.int32)
            dctabs = np.full((kb, Mb), SINK_BLOCK, np.int32)
            for j, (i, _) in enumerate(chunks):
                ctabs[j] = self._tables[i, :Mb]
                dctabs[j] = self._dtables[i, :Mb]
            t_chunk = lap("plan")
            (cnxt, self._pk, self._pv, self._dpk,
             self._dpv) = self._spec_chunk_paged(
                self._pk, self._pv, self._dpk, self._dpv,
                jnp.asarray(ctoks, jnp.int32),
                jnp.asarray(cpos, jnp.int32),
                jnp.asarray(clens, jnp.int32),
                jnp.asarray(ctabs, jnp.int32),
                jnp.asarray(dctabs, jnp.int32))
        else:
            read_len = next(b for b in self._read_buckets
                            if b >= need)
            t_chunk = lap("plan")
            (cnxt, self._ck, self._cv, self._dck,
             self._dcv) = self._spec_chunk(
                self._ck, self._cv, self._dck, self._dcv,
                jnp.asarray(ctoks, jnp.int32),
                jnp.asarray(cpos, jnp.int32),
                jnp.asarray(clens, jnp.int32),
                jnp.asarray(cslots, jnp.int32),
                read_len=read_len)
        self._dispatched()
        cnxt = np.asarray(cnxt)     # one host sync for first-token picks
        dur_chunk = lap("device_wait") - t_chunk
        for i, clen in chunks:
            self.telemetry.events.span(
                "prefill_chunk", t_chunk, dur_chunk, i,
                {"uri": self._slots[i].uri, "tokens": int(clen),
                 "fill_pos": int(self._slots[i].fill_pos)})
        self.telemetry.c_chunks.inc(len(chunks))
        completed: List[Tuple[int, int]] = []
        for j, (i, clen) in enumerate(chunks):
            st = self._slots[i]
            st.fill_pos += clen
            if self.paged:
                self._publish_chunk_blocks(i, st)
            if st.fill_pos >= st.plen:
                completed.append((i, int(cnxt[j])))
        for i, first in completed:
            st = self._slots[i]
            st.state = "DECODE"
            st.full = st.hashes = None
            self._tok[i] = first
            self._pos[i] = st.plen
            self._dpos[i] = st.plen
            self._done[i] = False
            self._record_token(i, first)    # the request's FIRST token

    def drain(self, max_ticks: int = 100_000) -> None:
        """Run ticks until every submitted request has finished (tests /
        batch use)."""
        for _ in range(max_ticks):
            if self.step() == 0 and self.n_waiting == 0:
                return
        raise RuntimeError("drain did not converge")
