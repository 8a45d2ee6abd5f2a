"""Cluster Serving — continuous-batching TPU inference service.

Reference surface (SURVEY.md §2.6, §3.5; ref: serving/ClusterServing.scala,
serving/engine/ClusterServingInference.scala, ClusterServingHelper.scala):
a Flink job XREADGROUPs the Redis input stream, micro-batches by size/
timeout, runs InferenceModel, XADDs results; config.yaml drives model path,
batch size, redis address.

TPU re-design: no Flink — ONE host thread owns the serving loop (queue →
micro-batcher → bucketed-pad → jitted forward → result hashes). The TPU's
own pipelining replaces Flink operator parallelism: while step N computes
on device, step N+1 is being batched on host. Backpressure = stream length
(the reference's de-facto backlog metric, SURVEY §5); fixed jit shapes come
from InferenceModel's bucket cache.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from analytics_zoo_tpu.common.compile_cache import enable_compile_cache
from analytics_zoo_tpu.common.log import logger
from analytics_zoo_tpu.learn.inference_model import InferenceModel
from analytics_zoo_tpu.serving.flight import (SLO_METRICS, AnomalyMonitor,
                                              FlightRecorder, SloPolicy,
                                              SloWatchdog, dump_bundle,
                                              install_flight_logging,
                                              prune_bundles)
from analytics_zoo_tpu.serving.frontdoor import (PRIORITIES, QosPolicy,
                                                 TokenEmitter,
                                                 decode_deadline,
                                                 decode_priority,
                                                 decode_str_field)
from analytics_zoo_tpu.serving.fault import FaultInjector, InjectedFault
from analytics_zoo_tpu.serving.kv_store import PrefixDirectory
from analytics_zoo_tpu.serving.paged_cache import chain_hashes
from analytics_zoo_tpu.serving.policy import (REPLICA_ROLES,
                                                BrownoutPolicy,
                                                BrownoutState,
                                                ReplicaSignals,
                                                pick_retry_target,
                                                plan_brownout,
                                                plan_handoff_recovery,
                                                plan_redispatch,
                                                replica_dead,
                                                route_request)
from analytics_zoo_tpu.serving.queues import (
    CANCEL_STREAM, IMG_MAGIC, INPUT_STREAM, RESULT_PREFIX, SIGNAL_PREFIX,
    TOKEN_PREFIX, OutputQueue, decode_ndarray, encode_ndarray)
from analytics_zoo_tpu.serving.resp import RespClient, RespServer
from analytics_zoo_tpu.serving.telemetry import Telemetry


@dataclasses.dataclass
class ServingConfig:
    """config.yaml parity (ref: ClusterServingHelper field names)."""

    model_path: str = ""
    redis_host: str = "127.0.0.1"
    redis_port: int = 6379
    batch_size: int = 32            # micro-batch cap
    batch_timeout_ms: float = 5.0   # flush partial batch after this wait
    workers: int = 1                # parallel serving-loop consumers in
    #                                 one shared consumer group (ref: Flink
    #                                 source parallelism; >1 overlaps host
    #                                 decode/batching across workers, and N
    #                                 ClusterServing PROCESSES on one broker
    #                                 scale out the same way)
    input_cols: Optional[List[str]] = None  # None: infer from request
    image_shape: Optional[List[int]] = None  # (H, W): resize decoded
    #                                          image payloads to the model
    #                                          input (ref: serving image
    #                                          resize per model config)
    result_ttl_s: float = 300.0     # abandoned results pruned after this
    core_number: Optional[int] = None   # ref: host CPU cores per serving
    #                                     task — here it caps concurrent
    #                                     host staging (InferenceModel
    #                                     semaphore), NOT batch; None keeps
    #                                     the model's own concurrent_num
    # Generative serving (LM generate): requests in `prompt_col` are
    # RAGGED 1-D token arrays; the batcher right-pads them to a common
    # width with `prompt_pad_id` and appends each request's true length
    # as an extra model input (InferenceModel.load_flax_generator's
    # (prompts, lengths) contract).  None = ordinary fixed-shape serving.
    prompt_col: Optional[str] = None
    prompt_pad_id: int = 0
    # Continuous batching (generative only): in-flight joining over a
    # fixed-slot KV arena (serving/continuous.py) instead of convoying
    # whole generations per micro-batch.  engine_slots co-resident
    # requests; eos_id frees a slot early when the model emits it.
    continuous_batching: bool = False
    engine_slots: int = 8
    # engine replicas (continuous mode): N engines, each owning its own
    # pump thread, telemetry registry and flight ring, behind ONE
    # broker/front door — a router thread (serving/policy.py
    # route_request) places each request on live per-replica signals
    # (pool pressure, queue depth, per-class SLO goodput), falling back
    # to least-loaded round-robin.  1 keeps the single-pump layout
    # bit-identical to previous releases.
    n_replicas: int = 1
    # Prefill/decode disaggregation (docs/serving_memory.md
    # "Disaggregation & elastic pools"): one role string per replica,
    # "prefill" or "decode".  Prefill-heavy replicas run prompts to
    # their first token, export the KV block chain, and a decode-heavy
    # replica adopts it (route_request ranks role match FIRST, so
    # either side still absorbs the other's overflow).  Requires
    # n_replicas > 1, continuous_batching, engine_paged, and no draft
    # model.  None keeps every replica symmetric — bit-identical to
    # role-less routing.
    replica_roles: Optional[List[str]] = None
    # Elastic per-replica block pools: after weights load, each paged
    # engine probes free HBM for a grow ceiling and resizes n_blocks
    # in block-granular steps at the eviction boundary, driven by pool
    # pressure and per-class goodput (policy.plan_pool_resize).  Off =
    # static pools, bit-identical to previous releases.
    engine_elastic_pool: bool = False
    # Tiered KV memory (serving/kv_store.py, docs/serving_memory.md
    # "Tiered KV memory"): a host-RAM second tier per paged engine —
    # evicted prefix chains spill there and re-admit at admission as a
    # host->HBM copy instead of a re-prefill.  0 = tier off,
    # bit-identical to single-tier serving.
    engine_kv_host_store_bytes: int = 0
    # Fleet-wide prefix index: every replica publishes which chain
    # hashes it holds (HBM index or host store) into one shared
    # PrefixDirectory, and the router ranks candidates by estimated
    # reuse depth (the prefix-locality term of route_request, between
    # role match and pool pressure).  Off = locality-blind routing,
    # bit-identical ranks.
    prefix_directory: bool = False
    eos_id: Optional[int] = None
    # tokens decoded per device call: >1 trades admission-latency
    # granularity for fewer host round-trips
    engine_ticks: int = 1
    # narrow the KV arena ("bfloat16" under an f32 model = 2x slots)
    engine_cache_dtype: Optional[str] = None
    # Paged KV cache (serving/paged_cache.py): block-pool memory
    # instead of a per-slot arena — residents hold only the blocks
    # they've filled, shared prompt prefixes attach to the same blocks
    # copy-free, and a dry pool preempts-to-queue instead of OOMing.
    engine_paged: bool = False
    engine_block_size: int = 16
    # Paged-attention read kernel: "gather" (materialising jnp.take
    # reference — the CPU/interpret-safe default) or "fused" (Pallas
    # kernel streaming KV blocks HBM->VMEM).  Paged-only.
    engine_kernel: str = "gather"
    # Paged KV block storage: None follows engine_cache_dtype, "bf16"
    # forces a bfloat16 pool, "int8" stores quantized blocks with
    # per-row scales (~1.9x n_blocks at equal HBM).  Paged-only.
    engine_kv_dtype: Optional[str] = None
    # pool size: engine_blocks wins when set; else engine_hbm_fraction
    # of device HBM (where the backend reports it); else arena-
    # equivalent (every slot can run full-length)
    engine_blocks: Optional[int] = None
    engine_hbm_fraction: Optional[float] = None
    engine_prefix_cache: bool = True
    # Chunked prefill (serving/continuous.py token-budget scheduler):
    # joiners' prompts stream into the cache in chunks fused with
    # active decodes under engine_tick_token_budget tokens per tick —
    # long prompts stop spiking residents' inter-token latency.  None
    # budget = engine default (about one decode bucket of work).
    engine_chunked: bool = False
    engine_tick_token_budget: Optional[int] = None
    # Speculative decoding depth override (proposals per round).  Only
    # meaningful when the model was loaded with a draft
    # (load_flax_generator(draft_model=...)); composes with paged and
    # chunked.  None keeps the depth stored at model load.
    engine_speculation_k: Optional[int] = None
    # QoS front door (serving/frontdoor.py; default OFF for parity):
    # admission + prefill-grant order become a weighted fair share over
    # (priority class, tenant) with aging as the starvation bound.
    qos_enabled: bool = False
    qos_weight_interactive: float = 8.0
    qos_weight_standard: float = 4.0
    qos_weight_batch: float = 1.0
    qos_aging_s: float = 30.0
    # bounded admission: the HTTP frontend's InputQueues reject past
    # this backlog with 429 + Retry-After (0 disables the cap)
    max_backlog: int = 10000
    # SLO watchdog (serving/flight.py): per-priority-class latency
    # targets, seconds.  A finished request is GOOD when none of its
    # queue-wait / TTFT / mean-TPOT exceeded its class target;
    # zoo_slo_* gauges and breach counters keep the score.  A target
    # of 0 disables that dimension for that class.
    slo_ttft_s_interactive: float = 1.0
    slo_ttft_s_standard: float = 2.5
    slo_ttft_s_batch: float = 10.0
    slo_tpot_s_interactive: float = 0.25
    slo_tpot_s_standard: float = 0.5
    slo_tpot_s_batch: float = 2.0
    slo_queue_wait_s_interactive: float = 0.5
    slo_queue_wait_s_standard: float = 2.0
    slo_queue_wait_s_batch: float = 30.0
    # flight recorder: per-tick snapshots retained for diagnostic
    # bundles and GET /debug/flight (0 disables the recorder)
    flight_capacity: int = 2048
    # anomaly-triggered diagnostic bundles (docs/debugging.md): where
    # they land, how often at most, how many survive pruning
    diag_dir: str = "diagnostics"
    diag_min_interval_s: float = 30.0
    diag_max_bundles: int = 8
    # triggers: >= anomaly_breach_burst SLO breaches inside
    # anomaly_breach_window_s; >= anomaly_alloc_streak consecutive
    # ticks with a block-pool allocation failure; any compile after
    # the first anomaly_steady_ticks ticks (0 disables a trigger)
    anomaly_breach_burst: int = 8
    anomaly_breach_window_s: float = 10.0
    anomaly_alloc_streak: int = 8
    anomaly_steady_ticks: int = 500
    # Fleet crash-tolerance (serving/fault.py + the broker supervisor;
    # docs/debugging.md "Crash recovery runbook").  fault_injection is
    # a deterministic chaos schedule — a list of fault-spec dicts
    # (fault.FaultSpec fields: kind / replica / at_tick / at_handoff /
    # count / duration_s).  None = injection OFF, every serving path
    # bit-identical to previous releases.
    fault_injection: Optional[List[dict]] = None
    fault_seed: int = 0
    # Supervisor: a pump silent for supervisor_miss_s seconds is
    # declared dead (policy.replica_dead) and its lost in-flight
    # requests re-dispatch to survivors; 0 disables heartbeat-based
    # death (an exception ESCAPING a pump thread always declares it).
    supervisor_miss_s: float = 0.0
    # At-least-once recovery: max total placements one request may
    # consume (first submit counts as attempt 1); past the budget the
    # supervisor publishes a terminal error instead of re-dispatching.
    retry_budget: int = 2
    # Two-phase handoff: the prefill source retains the exported state
    # until the decode side acks adoption; un-acked entries this old
    # re-dispatch to an alternate decode replica (0 = fire-and-forget,
    # the pre-supervisor behavior).
    handoff_ack_timeout_s: float = 5.0
    # A request the router cannot place (zero live replicas) parks for
    # at most this long before a terminal error — bounded wait, never
    # forever.
    unrouted_ttl_s: float = 5.0
    # Optional end-to-end deadline: a lost request older than this is
    # errored instead of re-dispatched (0 = no deadline; the
    # result_ttl_s prune remains the backstop).
    request_deadline_s: float = 0.0
    # Brownout ladder (docs/serving_qos.md "Overload & brownout"): a
    # broker-level controller walks policy.plan_brownout over the
    # fleet's aggregated signals (min per-class windowed goodput, max
    # queue depth, max alloc-fail streak, recent tick trend) and
    # pushes the resulting level into every engine — level 1 stops
    # admitting batch, 2 clamps standard max_new, 3 disables
    # speculative rounds, 4 serves interactive only.  Off (the
    # default) = controller never runs, every decision bit-identical
    # to previous releases.
    brownout: bool = False
    brownout_goodput_floor: float = 0.9
    brownout_queue_high: int = 64
    brownout_enter_ticks: int = 3
    brownout_exit_ticks: int = 6
    brownout_standard_max_new: int = 16
    # tick-duration breach threshold, seconds (0 disables that signal)
    brownout_tick_s_high: float = 0.0
    brownout_interval_s: float = 0.25

    @staticmethod
    def from_yaml(path: str) -> "ServingConfig":
        import yaml

        with open(path) as f:
            raw = yaml.safe_load(f) or {}
        params = raw.get("params") or {}
        redis_raw = raw.get("redis") or {}
        redis = redis_raw.get("src", redis_raw.get("url", ""))
        cfg = ServingConfig()
        model = raw.get("model", {})
        if isinstance(model, dict):
            cfg.model_path = model.get("path", "")
        if isinstance(redis, str) and ":" in redis:
            host, port = redis.rsplit(":", 1)
            cfg.redis_host, cfg.redis_port = host, int(port)
        # reference config.yaml semantics: core_number is CPU cores (a
        # resource knob), batch_size is the micro-batch — never conflate
        cfg.batch_size = int(params.get("batch_size", 32))
        if "core_number" in params:
            cfg.core_number = int(params["core_number"])
        if "image_shape" in params:
            cfg.image_shape = [int(v) for v in params["image_shape"]]
        if "workers" in params:
            cfg.workers = int(params["workers"])
        if "prompt_col" in params:
            cfg.prompt_col = str(params["prompt_col"])
        if "prompt_pad_id" in params:
            cfg.prompt_pad_id = int(params["prompt_pad_id"])
        if "continuous_batching" in params:
            cfg.continuous_batching = bool(params["continuous_batching"])
        if "engine_slots" in params:
            cfg.engine_slots = int(params["engine_slots"])
        if "n_replicas" in params:
            cfg.n_replicas = int(params["n_replicas"])
        if "replica_roles" in params:
            v = params["replica_roles"]
            cfg.replica_roles = (None if v is None
                                 else [str(x) for x in v])
        if "engine_elastic_pool" in params:
            cfg.engine_elastic_pool = bool(
                params["engine_elastic_pool"])
        if "engine_kv_host_store_bytes" in params:
            cfg.engine_kv_host_store_bytes = int(
                params["engine_kv_host_store_bytes"])
        if "prefix_directory" in params:
            cfg.prefix_directory = bool(params["prefix_directory"])
        if "eos_id" in params:
            cfg.eos_id = int(params["eos_id"])
        if "engine_ticks" in params:
            cfg.engine_ticks = int(params["engine_ticks"])
        if "engine_cache_dtype" in params:
            cfg.engine_cache_dtype = str(params["engine_cache_dtype"])
        if "engine_paged" in params:
            cfg.engine_paged = bool(params["engine_paged"])
        if "engine_block_size" in params:
            cfg.engine_block_size = int(params["engine_block_size"])
        if "engine_kernel" in params:
            cfg.engine_kernel = str(params["engine_kernel"])
        if "engine_kv_dtype" in params:
            v = params["engine_kv_dtype"]
            cfg.engine_kv_dtype = None if v is None else str(v)
        if "engine_blocks" in params:
            cfg.engine_blocks = int(params["engine_blocks"])
        if "engine_hbm_fraction" in params:
            cfg.engine_hbm_fraction = float(params["engine_hbm_fraction"])
        if "engine_prefix_cache" in params:
            cfg.engine_prefix_cache = bool(params["engine_prefix_cache"])
        if "engine_chunked" in params:
            cfg.engine_chunked = bool(params["engine_chunked"])
        if "engine_tick_token_budget" in params:
            cfg.engine_tick_token_budget = int(
                params["engine_tick_token_budget"])
        if "engine_speculation_k" in params:
            cfg.engine_speculation_k = int(
                params["engine_speculation_k"])
        if "qos_enabled" in params:
            cfg.qos_enabled = bool(params["qos_enabled"])
        if "qos_weight_interactive" in params:
            cfg.qos_weight_interactive = float(
                params["qos_weight_interactive"])
        if "qos_weight_standard" in params:
            cfg.qos_weight_standard = float(
                params["qos_weight_standard"])
        if "qos_weight_batch" in params:
            cfg.qos_weight_batch = float(params["qos_weight_batch"])
        if "qos_aging_s" in params:
            cfg.qos_aging_s = float(params["qos_aging_s"])
        if "max_backlog" in params:
            cfg.max_backlog = int(params["max_backlog"])
        for cls in PRIORITIES:
            for dim in SLO_METRICS:
                key = f"slo_{dim}_s_{cls}"
                if key in params:
                    setattr(cfg, key, float(params[key]))
        for key, cast in (("flight_capacity", int), ("diag_dir", str),
                          ("diag_min_interval_s", float),
                          ("diag_max_bundles", int),
                          ("anomaly_breach_burst", int),
                          ("anomaly_breach_window_s", float),
                          ("anomaly_alloc_streak", int),
                          ("anomaly_steady_ticks", int),
                          ("fault_seed", int),
                          ("supervisor_miss_s", float),
                          ("retry_budget", int),
                          ("handoff_ack_timeout_s", float),
                          ("unrouted_ttl_s", float),
                          ("request_deadline_s", float),
                          ("brownout", bool),
                          ("brownout_goodput_floor", float),
                          ("brownout_queue_high", int),
                          ("brownout_enter_ticks", int),
                          ("brownout_exit_ticks", int),
                          ("brownout_standard_max_new", int),
                          ("brownout_tick_s_high", float),
                          ("brownout_interval_s", float)):
            if key in params:
                setattr(cfg, key, cast(params[key]))
        if "fault_injection" in params:
            v = params["fault_injection"]
            cfg.fault_injection = (None if v is None
                                   else [dict(d) for d in v])
        return cfg

    def slo_policy(self) -> SloPolicy:
        """The per-class target table the ``slo_*`` knobs resolve to."""
        return SloPolicy(targets={
            cls: {dim: float(getattr(self, f"slo_{dim}_s_{cls}"))
                  for dim in SLO_METRICS}
            for cls in PRIORITIES})


class ClusterServing:
    """The serving job. Optionally owns an embedded RESP broker.

    Usage:
      serving = ClusterServing(model, config, embedded_broker=True).start()
      InputQueue(port=serving.port).enqueue(...)
    """

    def __init__(self, inference_model: InferenceModel,
                 config: Optional[ServingConfig] = None,
                 embedded_broker: bool = False,
                 engine_mesh=None, engine_partition_rules=None):
        self.model = inference_model
        self.config = config or ServingConfig()
        # continuous batching on a tp mesh (models beyond one chip's
        # HBM); Python-API only — a mesh is not a config.yaml value
        self.engine_mesh = engine_mesh
        self.engine_partition_rules = engine_partition_rules
        self._check_pad_agreement(inference_model)
        if self.config.core_number is not None:
            inference_model.set_concurrency(self.config.core_number)
        self.engine = None      # continuous-batching engine (start())
        # job-level telemetry; continuous mode hands this same facade
        # to the engine, so one registry carries zoo_serving_* AND
        # zoo_engine_* metrics and the event ring interleaves engine
        # spans with serving-side terminal events (abandonment).  Its
        # fan-out clock is the process's: the embedded broker's
        # connection threads and the front door's stream hub book
        # there, and every engine reads it with a cursor of its own
        self.telemetry = Telemetry()
        self.broker: Optional[RespServer] = None
        if embedded_broker:
            self.broker = RespServer(
                port=0, fanout=self.telemetry.fanout).start()
            self.config.redis_host = "127.0.0.1"
            self.config.redis_port = self.broker.port
        self.port = self.config.redis_port
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stats_lock = threading.Lock()
        # (uri, written_at) of results not yet known consumed — abandoned
        # ones (client timed out / died) are pruned after result_ttl_s so
        # broker memory stays bounded in long-lived deployments
        self._written: collections.deque = collections.deque()
        # continuous mode: uri -> (submit_time, stream entry id) of
        # requests still inside the engine.  A row older than the ttl
        # has no client left to collect it — _prune_abandoned aborts it
        # so its KV blocks (both pool tenants under speculation) free
        # instead of finishing dead work
        self._inflight: "collections.OrderedDict[str, tuple]" = \
            collections.OrderedDict()
        self.stats = {"requests": 0, "batches": 0, "batch_fill": 0.0,
                      "predict_ms": 0.0}
        self._register_serving_gauges()
        # ---- incident pillar (serving/flight.py) -----------------------
        # SLO watchdog fed from the shared telemetry's request hooks;
        # its zoo_slo_* families land in the same registry a /metrics
        # scrape merges.  The flight recorder is created HERE (not by
        # the engine) so a crash bundle can still ship the ring after
        # the engine is gone; start() hands it to the engine.
        self.watchdog = SloWatchdog(self.config.slo_policy(),
                                    registry=self.telemetry.metrics)
        self.telemetry.watchdog = self.watchdog
        self.flight = (FlightRecorder(self.config.flight_capacity)
                       if self.config.flight_capacity > 0 else None)
        self.log_ring = install_flight_logging()
        self.anomalies = AnomalyMonitor(
            self._dump_bundle,
            min_interval_s=self.config.diag_min_interval_s,
            breach_burst=self.config.anomaly_breach_burst,
            breach_window_s=self.config.anomaly_breach_window_s,
            alloc_streak=self.config.anomaly_alloc_streak,
            steady_after_ticks=self.config.anomaly_steady_ticks)
        # ---- replica set (continuous mode scale-out) -------------------
        # replica 0 owns the job-level telemetry/watchdog/flight above
        # (single-replica deployments stay bit-identical); each further
        # replica gets its OWN registry, watchdog, flight ring and
        # anomaly monitor, so one replica's incident never muddies a
        # neighbour's trace and the router can read per-replica SLO
        # goodput.  /metrics merges every registry (distinct engines
        # share metric names, so multi-replica scrapes read replica 0's
        # registry plus the zoo_router_* families for the fleet view;
        # per-replica registries feed bundles and the router).
        self.n_replicas = max(1, int(getattr(self.config,
                                             "n_replicas", 1)))
        if self.n_replicas > 1 and not self.config.continuous_batching:
            raise ValueError(
                "n_replicas > 1 needs continuous_batching: true — the "
                "micro-batch path already scales with `workers` "
                "consumers on the shared group; replicas exist to "
                "multiply continuous engines")
        # replica roles (prefill/decode disaggregation): validated
        # eagerly so a bad fleet layout fails at assembly, not from a
        # pump thread mid-request
        roles = getattr(self.config, "replica_roles", None)
        self.replica_roles: Optional[List[str]] = None
        if roles is not None:
            roles = [str(x) for x in roles]
            if len(roles) != self.n_replicas:
                raise ValueError(
                    f"replica_roles needs one role per replica: got "
                    f"{len(roles)} roles for n_replicas="
                    f"{self.n_replicas}")
            bad = [x for x in roles if x not in REPLICA_ROLES]
            if bad:
                raise ValueError(
                    f"replica_roles entries must be one of "
                    f"{REPLICA_ROLES}, got {bad}")
            if self.n_replicas < 2:
                raise ValueError(
                    "replica_roles needs n_replicas > 1: a sole "
                    "replica must both prefill and decode")
            if not self.config.engine_paged:
                raise ValueError(
                    "replica_roles requires engine_paged: true — the "
                    "handoff wire format is a KV block chain")
            self.replica_roles = roles
        if self.config.engine_elastic_pool and \
                not self.config.engine_paged:
            raise ValueError(
                "engine_elastic_pool requires engine_paged: true — "
                "the arena has no block pool to resize")
        # tiered KV memory (serving/kv_store.py): validated eagerly
        # like the knobs above
        if getattr(self.config, "engine_kv_host_store_bytes", 0) > 0 \
                and not self.config.engine_paged:
            raise ValueError(
                "engine_kv_host_store_bytes requires engine_paged: "
                "true — the host tier spills and re-admits KV block "
                "chains")
        if getattr(self.config, "prefix_directory", False):
            if not self.config.engine_paged:
                raise ValueError(
                    "prefix_directory requires engine_paged: true — "
                    "the directory indexes KV block chain hashes")
            if not self.config.continuous_batching:
                raise ValueError(
                    "prefix_directory requires continuous_batching: "
                    "true — only continuous engines publish prefix "
                    "residency")
        self._prefix_directory = (
            PrefixDirectory()
            if getattr(self.config, "prefix_directory", False)
            else None)
        # disaggregation counters (under _rq_cond like the router's
        # other placement state)
        self._role_handoffs = 0
        self._role_prefill_routed = 0
        self._role_decode_routed = 0
        self._h_handoff = None      # set by _register_router_gauges
        self.engines: list = []
        self.telemetries = [self.telemetry]
        self.watchdogs = [self.watchdog]
        self.flights = [self.flight]
        self.anomaly_monitors = [self.anomalies]
        for r in range(1, self.n_replicas):
            # the fan-out clock is the process's: every replica's engine
            # reads the one the broker and the front door book on
            tm = Telemetry(fanout=self.telemetry.fanout)
            wd = SloWatchdog(self.config.slo_policy(),
                             registry=tm.metrics)
            tm.watchdog = wd
            fl = (FlightRecorder(self.config.flight_capacity)
                  if self.config.flight_capacity > 0 else None)
            mon = AnomalyMonitor(
                (lambda reason, detail, _r=r:
                 self._dump_bundle(reason, dict(detail, replica=_r))),
                min_interval_s=self.config.diag_min_interval_s,
                breach_burst=self.config.anomaly_breach_burst,
                breach_window_s=self.config.anomaly_breach_window_s,
                alloc_streak=self.config.anomaly_alloc_streak,
                steady_after_ticks=self.config.anomaly_steady_ticks)
            self.telemetries.append(tm)
            self.watchdogs.append(wd)
            self.flights.append(fl)
            self.anomaly_monitors.append(mon)
        # router state: per-replica routed-entry queues + cancel sets
        # under ONE condition (the router appends, pumps pop, kills
        # notify), round-robin cursor, uri->replica map for cancel
        # fan-out, per-replica routed counters
        self._rq_cond = threading.Condition()
        self._rqueues: List[collections.deque] = [
            collections.deque() for _ in range(self.n_replicas)]
        self._rcancels: List[set] = [set()
                                     for _ in range(self.n_replicas)]
        self._pump_live = [False] * self.n_replicas
        self._pump_stops = [threading.Event()
                            for _ in range(self.n_replicas)]
        self._rr_cursor = 0
        self._uri_replica: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self._router_cancelled: set = set()
        self._routed_counts = [0] * self.n_replicas
        self._rerouted_count = 0
        # ---- supervisor state (fleet crash-tolerance) ------------------
        # heartbeats: each pump stamps its slot once per loop pass;
        # the router's liveness sweep reads them through
        # replica_signals -> policy.replica_dead.  Death bookkeeping,
        # per-request attempt counters, parked-unrouted entries and
        # pending (un-acked) two-phase handoffs all live under
        # _rq_cond with the rest of the placement state.
        self._beats = [0.0] * self.n_replicas
        self._death_reasons: List[Optional[str]] = \
            [None] * self.n_replicas
        self._dead_unswept: set = set()
        self._deaths = 0
        self._redispatched = 0
        self._unrouted_expired = 0
        # uri -> total placements so far (absent = 1, the first submit)
        self._attempts: Dict[str, int] = {}
        # (fields, eid, parked_at) the router could not place anywhere
        self._unrouted: collections.deque = collections.deque()
        # uri -> {state, src, dst, sent_at, retries} exported prefills
        # whose decode-side adoption has not acked yet — the retained
        # reference that makes the handoff two-phase
        self._pending_handoffs: Dict[str, dict] = {}
        self._handoff_acks = 0
        self._handoff_timeouts = 0
        self._handoff_retries = 0
        # ---- brownout controller (docs/serving_qos.md) -----------------
        # The POLICY object exists only when the knob is on: with
        # `brownout: false` _brownout_eval never runs, no engine ever
        # sees set_brownout, and every decision stays bit-identical.
        self._brownout_policy = (BrownoutPolicy(
            goodput_floor=float(self.config.brownout_goodput_floor),
            queue_high=int(self.config.brownout_queue_high),
            enter_ticks=int(self.config.brownout_enter_ticks),
            exit_ticks=int(self.config.brownout_exit_ticks),
            standard_max_new=int(self.config.brownout_standard_max_new),
            tick_s_high=float(self.config.brownout_tick_s_high))
            if getattr(self.config, "brownout", False) else None)
        self._brownout_state = BrownoutState()
        self._brownout_transitions = 0
        # chaos harness: parse the schedule eagerly so a bad spec
        # fails at assembly, not from a pump thread mid-request.
        # None/empty = injection off — every path bit-identical.
        faults = getattr(self.config, "fault_injection", None)
        self._fault = (FaultInjector(
            faults, seed=getattr(self.config, "fault_seed", 0))
            if faults else None)
        if self.n_replicas > 1:
            self._register_router_gauges()
        self._img_resize = None
        from concurrent.futures import ThreadPoolExecutor
        import os as _os

        self._decode_pool = ThreadPoolExecutor(
            max_workers=min(8, _os.cpu_count() or 4),
            thread_name_prefix="zoo-serving-decode")

    def _register_serving_gauges(self) -> None:
        """Expose the ``stats`` dict through the metrics registry:
        callbacks read under the stats lock at scrape time, so the
        Prometheus view and ``stats`` can never disagree."""

        def _stat(key, default=0):
            def read():
                with self._stats_lock:
                    return self.stats.get(key, default)
            return read

        m = self.telemetry.metrics
        m.gauge("zoo_serving_requests_total",
                "requests whose results were published",
                fn=_stat("requests"), kind="counter")
        m.gauge("zoo_serving_batches_total", "device dispatches",
                fn=_stat("batches"), kind="counter")
        m.gauge("zoo_serving_batch_fill",
                "fill fraction of the last dispatch (continuous: "
                "arena occupancy)", fn=_stat("batch_fill"))
        m.gauge("zoo_serving_predict_ms",
                "last dispatch latency, ms (continuous: last "
                "request's submit-to-publish)", fn=_stat("predict_ms"))
        m.gauge("zoo_serving_pending_results",
                "published results not yet known consumed",
                fn=lambda: len(self._written))
        # pre-register so the counters are scrapeable at zero, not born
        # on the first event (rate() needs the initial sample)
        m.counter("zoo_serving_requests_abandoned_total",
                  "published results pruned uncollected after the ttl")
        m.counter("zoo_serving_requests_cancelled_total",
                  "requests aborted by live cancellation (explicit "
                  "cancel or mid-stream disconnect)")
        m.counter("zoo_serving_stream_disconnects_total",
                  "streaming clients that disconnected mid-response")
        m.counter("zoo_serving_backpressure_rejections_total",
                  "admissions refused with 429 under a full backlog")
        # brownout families (docs/serving_qos.md "Overload & brownout"):
        # registered unconditionally so dashboards see stable names
        # whether or not the ladder is enabled — all zero when off
        m.gauge("zoo_brownout_level",
                "current brownout ladder level (0 = normal service)",
                fn=lambda: self._brownout_state.level)
        m.counter("zoo_brownout_transitions_total",
                  "brownout ladder level changes (either direction)")
        for cls in PRIORITIES:
            m.counter(f"zoo_brownout_shed_total_{cls}",
                      f"admissions refused with 429 because the "
                      f"brownout ladder browned the {cls} class out")
        m.gauge("zoo_brownout_deadline_shed_total",
                "requests shed at admission fleet-wide because their "
                "deadline had already passed (never reached prefill)",
                fn=lambda: sum(
                    getattr(e, "deadline_sheds", 0)
                    for e in getattr(self, "engines", ()) or ()),
                kind="counter")

    def _register_router_gauges(self) -> None:
        """The ``zoo_router_*`` families (docs/observability.md): fleet
        liveness plus per-replica placement counters and queue depths —
        the serve-smoke 2-replica leg asserts traffic spread on these."""
        m = self.telemetry.metrics
        m.gauge("zoo_router_replicas", "configured engine replicas",
                fn=lambda: self.n_replicas)
        m.gauge("zoo_router_replicas_live",
                "replicas with a live pump thread",
                fn=lambda: sum(1 for v in self._pump_live if v))
        m.gauge("zoo_router_rerouted_total",
                "entries drained from a dead replica's queue and "
                "re-placed on survivors",
                fn=lambda: self._rerouted_count, kind="counter")
        for r in range(self.n_replicas):
            m.gauge(f"zoo_router_routed_total_r{r}",
                    f"requests the router placed on replica {r}",
                    fn=(lambda _r=r: self._routed_counts[_r]),
                    kind="counter")
            m.gauge(f"zoo_router_queue_depth_r{r}",
                    f"replica {r} routed-but-unclaimed entries",
                    fn=(lambda _r=r: len(self._rqueues[_r])))
        # disaggregation families: registered for every multi-replica
        # fleet (zero without replica_roles) so dashboards see stable
        # names whether or not roles are configured
        m.gauge("zoo_router_role_handoffs_total",
                "prefill->decode KV chain handoffs completed",
                fn=lambda: self._role_handoffs, kind="counter")
        m.gauge("zoo_router_role_prefill_routed_total",
                "new requests placed on a prefill-role replica",
                fn=lambda: self._role_prefill_routed, kind="counter")
        m.gauge("zoo_router_role_decode_routed_total",
                "exported prefills placed on a decode-role replica",
                fn=lambda: self._role_decode_routed, kind="counter")
        self._h_handoff = m.histogram(
            "zoo_router_handoff_seconds",
            "wall seconds from prefill export to decode-side "
            "adoption enqueue (route + chain ship)")
        # crash-tolerance families (docs/debugging.md "Crash recovery
        # runbook"): stable names whether or not faults ever fire
        m.gauge("zoo_router_replica_deaths_total",
                "replicas the supervisor declared dead (escaped pump "
                "exception or missed heartbeats)",
                fn=lambda: self._deaths, kind="counter")
        m.gauge("zoo_router_requests_redispatched_total",
                "lost in-flight requests re-dispatched to survivors "
                "(at-least-once recovery)",
                fn=lambda: self._redispatched, kind="counter")
        m.gauge("zoo_engine_handoff_acks_total",
                "two-phase handoffs whose decode-side adoption acked "
                "(the source's retained state released)",
                fn=lambda: self._handoff_acks, kind="counter")
        m.gauge("zoo_engine_handoff_timeouts_total",
                "pending handoffs that hit the ack timeout",
                fn=lambda: self._handoff_timeouts, kind="counter")
        m.gauge("zoo_engine_handoff_retries_total",
                "timed-out handoffs re-dispatched to an alternate "
                "decode replica",
                fn=lambda: self._handoff_retries, kind="counter")

    # ---- lifecycle ----------------------------------------------------

    GROUP = b"serving"

    @classmethod
    def from_config(cls, config_path: str,
                    embedded_broker: bool = False) -> "ClusterServing":
        """ref-parity: the ``cluster-serving-start`` entry — one
        config.yaml names the broker, the knobs, and a SELF-DESCRIBING
        model artifact; the serving job assembles itself from it.

        ``model.path`` routes by artifact type: ``*.xml`` loads an
        OpenVINO IR, a SavedModel directory (local or remote gs://,
        s3://, hdfs:// — TF's filesystem layer resolves those) loads
        through TFNet, and ``*.pt``/``*.pth`` loads a torch module.
        (Flax/orbax exports need their module class and therefore the
        Python API — ``ClusterServing(InferenceModel().load_flax(...),
        cfg)``.)"""
        import os

        from analytics_zoo_tpu.net import _is_local_path

        cfg = ServingConfig.from_yaml(config_path)
        if cfg.continuous_batching:
            # none of the config-routable artifacts (IR / SavedModel /
            # torch) is a generator; fail at assembly time, pointing at
            # the knob, instead of from deep inside start()
            raise ValueError(
                f"{config_path}: continuous_batching: true requires a "
                f"generative model loaded via the Python API "
                f"(InferenceModel().load_flax_generator(...) + "
                f"ClusterServing(model, cfg)); config-file artifacts "
                f"(.xml IR / SavedModel / .pt) cannot serve in "
                f"continuous mode")
        path = cfg.model_path
        if not path:
            raise ValueError(
                f"{config_path}: model.path is required (a .xml IR, a "
                f"SavedModel dir, or a .pt torch module)")
        # existence FIRST for local paths: a typo'd path of ANY
        # extension must read as a typo, not as 'cannot infer the
        # format' or a derived-file error from deeper in a loader
        if _is_local_path(path) and not os.path.exists(path):
            raise FileNotFoundError(
                f"{config_path}: model.path {path!r} does not exist")
        im = InferenceModel()
        if path.endswith(".xml"):
            im.load_openvino(path)
        elif path.endswith((".pt", ".pth")):
            im.load_torch(path)
        elif not _is_local_path(path) or os.path.isdir(path):
            im.load_tf(path)
        else:
            raise ValueError(
                f"cannot infer the model format of {path!r}: expected "
                f"an OpenVINO .xml, a TF SavedModel directory, or a "
                f"torch .pt/.pth")
        return cls(im, cfg, embedded_broker=embedded_broker)

    def start(self) -> "ClusterServing":
        # the serving path never goes through init_orca_context, so the
        # compile cache is placed here, before the engines' first compile
        enable_compile_cache()
        self.client = RespClient(self.config.redis_host,
                                 self.config.redis_port)
        # one shared consumer group: every worker (thread here; other
        # ClusterServing PROCESSES on the same broker too) claims disjoint
        # entries atomically — the Flink-source-parallelism analog
        try:
            # MKSTREAM: a real redis-server refuses to create a group on a
            # stream that has no entries yet (the embedded broker
            # auto-creates either way)
            self.client.execute("XGROUP", "CREATE", INPUT_STREAM,
                                self.GROUP, "0-0", "MKSTREAM")
        except Exception as e:
            if "BUSYGROUP" not in str(e):
                raise
        self._threads = []
        if self.config.continuous_batching:
            # each pump thread owns ONE engine's device state; with
            # n_replicas > 1 a router thread claims from the shared
            # group and places requests on replicas (least-loaded /
            # pressure/SLO-aware), so horizontal scale inside one
            # process is more replicas — more ClusterServing PROCESSES
            # on the same broker still compose on top
            qos = None
            if self.config.qos_enabled:
                qos = QosPolicy(
                    weights={
                        "interactive":
                            float(self.config.qos_weight_interactive),
                        "standard":
                            float(self.config.qos_weight_standard),
                        "batch": float(self.config.qos_weight_batch)},
                    aging_s=float(self.config.qos_aging_s))
            meshes = self._replica_meshes()
            self.engines = [self.model.make_continuous_engine(
                max_slots=self.config.engine_slots,
                eos_id=self.config.eos_id,
                ticks_per_step=self.config.engine_ticks,
                cache_dtype=self.config.engine_cache_dtype,
                mesh=meshes[r],
                partition_rules=self.engine_partition_rules,
                kernel=self.config.engine_kernel,
                kv_dtype=self.config.engine_kv_dtype,
                paged=self.config.engine_paged,
                block_size=self.config.engine_block_size,
                n_blocks=self.config.engine_blocks,
                hbm_fraction=self.config.engine_hbm_fraction,
                enable_prefix_cache=self.config.engine_prefix_cache,
                chunked=self.config.engine_chunked,
                tick_token_budget=self.config.engine_tick_token_budget,
                speculation_k=self.config.engine_speculation_k,
                elastic_pool=self.config.engine_elastic_pool,
                kv_host_store_bytes=getattr(
                    self.config, "engine_kv_host_store_bytes", 0),
                prefix_directory=self._prefix_directory,
                replica_id=r,
                fault_injector=self._fault,
                telemetry=self.telemetries[r],
                qos=qos,
                flight=self.flights[r],
                flight_capacity=self.config.flight_capacity)
                for r in range(self.n_replicas)]
            self.engine = self.engines[0]   # back-compat attribute
            for r in range(self.n_replicas):
                self._pump_live[r] = True
                t = threading.Thread(target=self._loop_continuous,
                                     args=(f"w{r}", r), daemon=True,
                                     name=f"zoo-serving-cb-{r}")
                t.start()
                self._threads.append(t)
            if self.n_replicas > 1:
                rt = threading.Thread(target=self._loop_router,
                                      daemon=True,
                                      name="zoo-serving-router")
                rt.start()
                self._threads.append(rt)
        else:
            for w in range(max(1, self.config.workers)):
                t = threading.Thread(target=self._loop, args=(f"w{w}",),
                                     daemon=True, name=f"zoo-serving-{w}")
                t.start()
                self._threads.append(t)
        self._thread = self._threads[0]     # back-compat attribute
        logger.info("ClusterServing up (redis %s:%d, batch<=%d, "
                    "workers=%d%s)", self.config.redis_host,
                    self.config.redis_port, self.config.batch_size,
                    len(self._threads),
                    ", continuous" if self.config.continuous_batching
                    else "")
        return self

    def _replica_meshes(self) -> list:
        """Where each engine replica lives — chosen here from
        ``jax.devices()``, never left to "wherever the weights were
        loaded" (which is chip 0 for every replica).

        One replica keeps today's placement: ``engine_mesh`` as given,
        or no mesh (the default device).  Several replicas each own
        their own hardware: without ``engine_mesh`` replica r gets a
        one-device mesh over device r; with one, the mesh is cut along
        every non-``tp`` axis and replica r gets the r-th ``tp`` group.
        More replicas than chips (or tp groups) is an error on an
        accelerator — two engines on one chip only contend for it; the
        CPU backend, whose devices are one host's cores anyway, wraps
        round so single-device dry runs still drive a fleet."""
        import jax

        from analytics_zoo_tpu.parallel.mesh import make_mesh

        n = self.n_replicas
        if n == 1:
            return [self.engine_mesh]
        m = self.engine_mesh
        if m is None:
            groups = [[d] for d in jax.devices()]
        elif "tp" in m.axis_names:
            devs = np.moveaxis(m.devices, m.axis_names.index("tp"), -1)
            groups = [list(g) for g in devs.reshape(-1, devs.shape[-1])]
        else:
            groups = [[d] for d in m.devices.flat]
        if n > len(groups):
            if jax.default_backend() != "cpu":
                raise ValueError(
                    f"n_replicas={n} needs {n} device groups but only "
                    f"{len(groups)} exist ({len(jax.devices())} "
                    f"{jax.default_backend()} device(s)): each replica "
                    f"owns its own chip(s)")
            groups = [groups[r % len(groups)] for r in range(n)]
        return [make_mesh(axes={"tp": len(groups[r])}, devices=groups[r])
                for r in range(n)]

    def register_prefix(self, tokens) -> int:
        """Register a shared prompt prefix (system prompt) with the
        continuous engine; clients then send ``prefix=np.int32(id)``
        alongside a suffix-only prompt.  Python API, after ``start()``
        (the engine owns device state)."""
        if self.engine is None:
            raise RuntimeError(
                "register_prefix needs a RUNNING continuous engine: "
                "enable continuous_batching and call start() first")
        # every replica prefills the prefix into ITS pool/arena; the
        # id counters advance in lockstep (registrations are serialised
        # here), so one id is valid fleet-wide
        ids = [e.register_prefix(tokens) for e in self.engines]
        if len(set(ids)) != 1:
            raise RuntimeError(
                f"prefix ids diverged across replicas: {ids}")
        return ids[0]

    def unregister_prefix(self, pid: int) -> None:
        if self.engine is None:
            raise RuntimeError("no continuous engine running")
        for e in self.engines:
            e.unregister_prefix(pid)

    def stop(self):
        self._stop.set()
        for t in getattr(self, "_threads", []):
            t.join(timeout=5)
        for eng in self.engines:
            eng.fanout.close()
        if self.broker is not None:
            self.broker.stop()
        self._decode_pool.shutdown(wait=False)

    def reload_model(self, inference_model: InferenceModel
                     ) -> "ClusterServing":
        """Hot-swap the served model without stopping the loop (ref:
        ClusterServingHelper model hot-load from config).  The swap is one
        attribute assignment — the loop reads ``self.model`` once per
        dispatch, so in-flight batches finish on the old model and the
        next batch runs the new one; no request is dropped."""
        if self.config.continuous_batching:
            raise NotImplementedError(
                "hot reload under continuous batching would orphan the "
                "in-flight KV arena; drain and restart the serving job "
                "to swap models")
        self._check_pad_agreement(inference_model)
        if self.config.core_number is not None:
            inference_model.set_concurrency(self.config.core_number)
        self.model = inference_model
        logger.info("ClusterServing model hot-reloaded")
        return self

    def _check_pad_agreement(self, inference_model: InferenceModel):
        """A generator infers prompt lengths from ITS ``pad_id`` when no
        explicit lengths arrive; the batcher pads ragged prompts with
        ``config.prompt_pad_id``.  The dispatch path always threads
        explicit lengths, so a disagreement is harmless HERE — but the
        same model served outside this batcher (direct ``predict``) would
        miscount, so surface the inconsistency loudly without failing a
        live reload."""
        model_pad = getattr(inference_model, "prompt_pad_id", None)
        if self.config.prompt_col and model_pad is not None and \
                model_pad != self.config.prompt_pad_id:
            logger.warning(
                "serving prompt_pad_id %d != generator pad_id %d; batched "
                "serving threads explicit lengths so this is safe here, "
                "but direct predict() on this model would infer lengths "
                "from the generator's pad id — configure both the same",
                self.config.prompt_pad_id, model_pad)

    # ---- serving loop -------------------------------------------------

    def _read_batch(self, client: RespClient, consumer: str,
                    block_ms: int = 200) -> List[Dict[str, bytes]]:
        """Micro-batch via the shared consumer group: XREADGROUP claims
        entries ATOMICALLY for this consumer (no worker ever sees another
        worker's requests), blocking up to block_ms for the first one and
        topping up within batch_timeout_ms.  With a batch already in
        flight on the device the loop passes a tiny block_ms so finished
        results are written promptly instead of waiting out a full idle
        poll."""
        cfg = self.config

        def claim(count, wait_ms):
            return client.execute(
                "XREADGROUP", "GROUP", self.GROUP, consumer,
                "COUNT", count, "BLOCK", wait_ms, "STREAMS",
                INPUT_STREAM, ">")

        first = claim(cfg.batch_size, block_ms)
        if not first:
            return [], []
        entries = first[0][1]
        deadline = time.monotonic() + cfg.batch_timeout_ms / 1000.0
        while len(entries) < cfg.batch_size:
            wait_ms = int(max(0, (deadline - time.monotonic()) * 1000))
            if wait_ms <= 0:
                break
            more = claim(cfg.batch_size - len(entries), wait_ms)
            if not more:
                break
            entries.extend(more[0][1])
        out = []
        for eid, flat in entries:
            fields = {flat[i].decode(): flat[i + 1]
                      for i in range(0, len(flat), 2)}
            out.append(fields)
        # NOT acked here: entries stay pending (and XLEN counts them)
        # until their results are published, so XPENDING shows the true
        # in-flight window — _finish_entries acks+deletes after publish
        return out, [eid for eid, _ in entries]

    def _loop(self, consumer: str = "w0"):
        """Pipelined serving loop (one per worker): while batch N computes
        on the TPU, batch N+1 is read from the stream and decoded on the
        host (XLA dispatch is async; blocking happens only when N's
        results are written).  Each worker owns its RESP connection."""
        try:
            client = RespClient(self.config.redis_host,
                                self.config.redis_port)
        except OSError:
            logger.exception("serving worker %s could not connect to the "
                             "broker — worker not started", consumer)
            return
        pending = None      # (requests, ids, waiter, dispatched_at)
        try:
            while not self._stop.is_set():
                try:
                    # with work in flight, poll briefly so finished results
                    # are published as soon as the device is done
                    requests, ids = self._read_batch(
                        client, consumer, 2 if pending else 200)
                except (ConnectionError, OSError):
                    if self._stop.is_set():
                        break
                    time.sleep(0.05)
                    continue
                nxt = None
                if requests:
                    try:
                        nxt = self._dispatch_batch(client, requests, ids)
                    except Exception:
                        logger.exception("serving dispatch failed")
                        self._finish_entries(client, ids)
                if pending is not None:
                    try:
                        self._publish_batch(client, *pending)
                    except Exception:
                        logger.exception("serving publish failed")
                        self._finish_entries(client, pending[1])
                pending = nxt
            if pending is not None:
                try:
                    self._publish_batch(client, *pending)
                except Exception:
                    logger.exception("serving publish failed")
                    self._finish_entries(client, pending[1])
        finally:
            client.close()

    def _loop_continuous(self, consumer: str, replica: int = 0):
        """Continuous-batching pump: requests stream into the slot-arena
        engine as they arrive (in-flight joining); each request publishes
        the moment IT finishes, so a 2-token request never convoys behind
        a 32-token neighbour admitted earlier.

        With one replica the pump claims straight from the broker's
        consumer group (the historical path, bit-identical).  With
        ``n_replicas > 1`` a router thread owns the claiming and this
        pump pops its replica's routed queue; a ``kill_pump`` stops the
        claiming but the pump keeps stepping until ITS engine drains,
        so no admitted request is dropped by a graceful kill."""
        client = tok_client = None
        try:
            client = RespClient(self.config.redis_host,
                                self.config.redis_port)
            # the token-stream events have a connection of their own:
            # cancel, claim, the result pipelines and _finish_entries
            # are round trips, and would queue behind a tick's XADDs in
            # the broker's connection thread
            tok_client = RespClient(self.config.redis_host,
                                    self.config.redis_port)
        except OSError:
            logger.exception("continuous serving pump could not connect "
                             "to the broker — not started")
            if client is not None:
                client.close()
            self._pump_live[replica] = False
            return
        engine = self.engines[replica]
        routed = self.n_replicas > 1
        stop_ev = self._pump_stops[replica]
        pcol = self.config.prompt_col or "prompt"
        role = (self.replica_roles[replica]
                if self.replica_roles is not None else None)
        elastic = bool(self.config.engine_elastic_pool)
        next_resize = time.monotonic() + 0.25
        # brownout controller cadence: evaluated from replica 0's pump
        # (the same throttled-control-step pattern as elastic resize);
        # the single evaluation pushes the level to EVERY engine so the
        # fleet walks the ladder together
        brownout_every = max(0.05, float(
            getattr(self.config, "brownout_interval_s", 0.25)))
        next_brownout = time.monotonic() + brownout_every
        # streaming state is PUMP-THREAD-ONLY (on_done/on_token fire
        # inside engine.step() on this thread): the emitter buffers
        # per-token events between steps; one pipeline per step ships
        # them — never a per-token broker round-trip — and it is
        # written when the NEXT step's device call has been enqueued,
        # so the broker's and the front door's fan-out runs under the
        # device and not between two steps (flush_under_device below)
        emitter = TokenEmitter(max_events=engine.max_new_tokens + 4)
        streaming: set = set()              # uris with a live tok: stream
        cancelled_pending: set = set()      # cancels that beat admission

        def publish(uri: str, toks: np.ndarray, eid: bytes, t0: float,
                    req):
            if uri in streaming:
                # terminal marker rides the emitter BEHIND the final
                # tokens, so the flush preserves emission order
                streaming.discard(uri)
                emitter.finish(uri)
            att = self._attempts.get(uri, 1)
            try:
                cmds = [
                    ("HSET", RESULT_PREFIX + uri, "value",
                     encode_ndarray(toks)),
                    ("XADD", SIGNAL_PREFIX + uri, "*", "ok", "1"),
                    ("SADD", "__result_keys__", uri)]
                if att > 1:
                    # at-least-once: surface how many placements this
                    # request took (clients and the chaos smoke read it)
                    cmds.insert(1, ("HSET", RESULT_PREFIX + uri,
                                    "attempts", str(att)))
                client.pipeline(cmds)
            except Exception as e:
                # the slot is already freed: a swallowed publish failure
                # would be a silent vanish (client blocks to timeout).
                # Fall back to an error result on the OTHER connection so
                # the client fails fast; finish the entry either way.
                logger.exception("continuous publish failed for %r", uri)
                try:
                    self._publish_error(req, f"publish failed: {e!r}")
                except Exception:
                    logger.exception("error-publish also failed for %r",
                                     uri)
            self._finish_entries(client, [eid])
            dt = (time.perf_counter() - t0) * 1000
            cache = engine.cache_metrics()
            with self._stats_lock:
                self.stats["requests"] += 1
                self.stats["batches"] += 1
                # continuous mode: predict_ms is the last request's
                # submit-to-publish latency; fill is arena occupancy
                self.stats["predict_ms"] = dt
                self.stats["batch_fill"] = engine.n_active / max(
                    1, self.config.engine_slots)
                # KV-memory counters (paged mode adds pool occupancy /
                # prefix hit rate / evictions; both modes report
                # preemptions + peak co-residency)
                self.stats["cache"] = cache
                self._written.append((uri, time.monotonic()))
                self._inflight.pop(uri, None)
                self._uri_replica.pop(uri, None)
                self._attempts.pop(uri, None)

        # the continuous pump must prune too (the micro-batch path
        # prunes per publish): time-gated so the idle poll loop isn't
        # taking the stats lock hundreds of times a second.  The cadence
        # re-reads result_ttl_s (it is runtime-tunable) and caps at 5s
        # so a shortened ttl takes effect promptly.
        def _prune_cadence():
            return min(max(1.0, self.config.result_ttl_s / 4.0), 5.0)

        next_prune = time.monotonic() + _prune_cadence()

        def fail(u, exc, eid, ureq):
            self._drop_inflight(u)
            self._publish_error(ureq, f"admission failed: {exc!r}")
            if u in streaming:
                streaming.discard(u)
                emitter.error(u, f"admission failed: {exc!r}"[:200])
            self._finish_entries(client, [eid])

        # the engine cycle's lap clock (telemetry.LapClock): this thread
        # drives the engine from here on, and every stretch of the loop
        # below is booked to a named phase — the engine's step() names
        # its own and closes `submit` as it starts
        tm = engine.telemetry
        clock = tm.clock
        clock.drive("submit")
        lap = clock.lap
        dispatched = False      # a device call was enqueued in this pass

        def flush(overlapped: bool = False, wait: bool = False):
            n = self._flush_emitter(tok_client, emitter, wait=wait)
            if n:
                tm.c_flush_events.inc(n)
                if overlapped:
                    tm.c_flush_overlapped.inc(n)

        def flush_under_device():
            # engine.after_dispatch: the step's device call is enqueued
            # and the pump needs no GIL until its result is there
            nonlocal dispatched
            dispatched = True
            flush(overlapped=True)
            lap("flush")

        engine.after_dispatch = flush_under_device
        try:
            while not self._stop.is_set():
                dispatched = False
                now = time.monotonic()
                # heartbeat: the supervisor's liveness input.  Stamped
                # every pass (busy or idle) so a healthy-but-quiet pump
                # never looks dead; only a wedged/crashed one does.
                self._beats[replica] = now
                if self._fault is not None:
                    act = self._fault.pump_action(replica)
                    if act == "kill":
                        # planned retirement: same path an operator's
                        # /admin/kill_pump takes (graceful drain)
                        try:
                            self.kill_pump(replica)
                        except Exception:
                            logger.exception(
                                "injected kill_pump refused "
                                "(replica %d)", replica)
                    elif act == "crash":
                        # unplanned death: escapes the pump loop and
                        # exercises the supervisor's redispatch path
                        raise InjectedFault(
                            f"injected pump crash (replica {replica})")
                if replica == 0 and now >= next_prune:
                    next_prune = now + _prune_cadence()
                    self._prune_abandoned(client, now)
                lap("house")
                if routed:
                    self._drain_routed_cancels(client, replica, emitter,
                                               streaming,
                                               cancelled_pending)
                else:
                    self._drain_cancels(client, emitter, streaming,
                                        cancelled_pending)
                lap("cancel")
                busy = engine.n_active > 0 or engine.n_waiting > 0
                if routed:
                    requests, ids = self._pop_routed(
                        replica, 0.001 if busy else 0.2)
                    if stop_ev.is_set() and not requests and not busy:
                        break       # killed + drained: graceful exit
                else:
                    try:
                        requests, ids = self._read_batch(
                            client, consumer, 1 if busy else 200)
                    except (ConnectionError, OSError):
                        if self._stop.is_set():
                            break
                        time.sleep(0.05)
                        continue
                # with the engine empty the claim is the wait for a
                # request, not work the cycle is held up by
                lap("claim" if busy else "idle_wait")
                for r, eid in zip(requests, ids):
                    t0 = time.perf_counter()
                    try:
                        uri = r["uri"].decode()
                        prompt = self._decode_value(r[pcol])
                        # optional per-request generation controls (a
                        # capability the whole-batch path cannot offer:
                        # its one scan runs every row identically)
                        kw = {}
                        if "max_new" in r:
                            kw["max_new"] = int(np.asarray(
                                self._decode_value(r["max_new"])))
                        if "temperature" in r:
                            kw["temperature"] = float(np.asarray(
                                self._decode_value(r["temperature"])))
                        if "seed" in r:
                            kw["rng_seed"] = int(np.asarray(
                                self._decode_value(r["seed"])))
                        if "top_p" in r:
                            kw["top_p"] = float(np.asarray(
                                self._decode_value(r["top_p"])))
                        if "prefix" in r:
                            # prefix-cached request: the id from
                            # ClusterServing.register_prefix
                            kw["prefix"] = int(np.asarray(
                                self._decode_value(r["prefix"])))
                        # front-door control fields (frontdoor.py wire
                        # codecs: the input queue transports ndarrays,
                        # so priority is an index and tenant a byte
                        # array)
                        if "priority" in r:
                            kw["priority"] = decode_priority(
                                self._decode_value(r["priority"]))
                        if "tenant" in r:
                            kw["tenant"] = decode_str_field(
                                self._decode_value(r["tenant"]))
                        if "deadline" in r:
                            # wire deadline (absolute wall-clock ms,
                            # frontdoor.encode_deadline) -> this pump's
                            # monotonic domain; an already-passed one
                            # still submits — admission sheds it with a
                            # terminal deadline_exceeded, never prefill
                            kw["deadline_t"] = decode_deadline(
                                self._decode_value(r["deadline"]))
                        stream = "stream" in r and bool(int(np.asarray(
                            self._decode_value(r["stream"])
                        ).reshape(-1)[0]))
                        if uri in cancelled_pending:
                            # the cancel raced ahead of admission:
                            # never enters the engine
                            cancelled_pending.discard(uri)
                            self._publish_error(
                                {"uri": r["uri"]}, "cancelled")
                            if stream:
                                emitter.cancelled(uri)
                            self._finish_entries(client, [eid])
                            continue
                        if stream:
                            kw["on_token"] = emitter.emit
                            streaming.add(uri)
                        # capture only the uri, not the whole request
                        # dict (it holds the encoded prompt payload —
                        # a needless second copy for the generation's
                        # lifetime)
                        ureq = {"uri": r["uri"]}
                        if role == "prefill" and not stream and \
                                kw.get("temperature", 0.0) <= 0.0:
                            # prefill replica: export at first token
                            # and ship to a decode replica.  Streaming
                            # and sampled rows decode HERE — the
                            # emitter is pump-local and the handoff
                            # contract is greedy-only.
                            kw["handoff_cb"] = (
                                lambda state, _rep=replica:
                                self._handoff_request(_rep, state))
                        engine.submit(
                            uri, prompt,
                            on_done=(lambda u, toks, _eid=eid, _t0=t0,
                                     _r=ureq: publish(u, toks, _eid,
                                                      _t0, _r)),
                            on_error=(lambda u, exc, _eid=eid, _r=ureq:
                                      fail(u, exc, _eid, _r)),
                            **kw)
                        with self._stats_lock:
                            self._inflight[uri] = (time.monotonic(), eid)
                    except Exception as e:
                        try:
                            u = r["uri"].decode()
                            if u in streaming:
                                streaming.discard(u)
                                emitter.error(
                                    u, f"submit failed: {e!r}"[:200])
                        except Exception:
                            pass
                        self._publish_error(r, f"submit failed: {e!r}")
                        self._finish_entries(client, [eid])
                try:
                    engine.step()
                except Exception:
                    # a device/engine error must not silently kill the
                    # sole pump thread — every queued client would hang
                    # to timeout with no log.  Log, breathe, keep
                    # serving (admission of new work may still succeed;
                    # a persistent fault keeps logging loudly).
                    logger.exception("continuous engine step failed "
                                     "(replica %d)", replica)
                    # the flight ring holds the ticks leading here —
                    # exactly what a post-mortem needs; dump now (rate-
                    # limited, failure-isolated) while the state is hot
                    self.anomaly_monitors[replica].crash(
                        traceback.format_exc())
                    time.sleep(0.2)
                else:
                    self._diag_poll(engine, replica)
                    lap("observe")
                    if elastic and time.monotonic() >= next_resize:
                        # throttled elastic-pool control step (pump
                        # thread — the arenas are donated through the
                        # step programs, so resizes interleave with
                        # ticks, never race them)
                        next_resize = time.monotonic() + 0.25
                        try:
                            per_class = self.watchdogs[replica].status(
                            )["per_class"]
                            engine.maybe_autoresize(
                                {c: d["goodput"]
                                 for c, d in per_class.items()})
                        except Exception:
                            logger.exception(
                                "elastic pool autoresize failed "
                                "(replica %d)", replica)
                    if replica == 0 \
                            and self._brownout_policy is not None \
                            and time.monotonic() >= next_brownout:
                        next_brownout = (time.monotonic()
                                         + brownout_every)
                        try:
                            self._brownout_eval()
                        except Exception:
                            logger.exception(
                                "brownout controller step failed")
                    lap("control")
                # what the emitter holds now waits for the next device
                # call only if one is coming: this pass enqueued one and
                # the engine still has work.  Else (the engine went idle,
                # a cancelled or error marker with no step behind it, a
                # step that enqueued nothing) it leaves here, so no
                # event waits longer than one pass
                if not (dispatched and (engine.n_active > 0
                                        or engine.n_waiting > 0)):
                    flush()
                lap("flush")
            flush(wait=True)        # leaving in good order: nothing stays
        except Exception:
            # an exception escaping the pump loop used to die silently
            # in the thread, leaving a zombie entry in the router's
            # live set and stranding every admitted request.  Dump a
            # flight bundle (the ring holds the ticks leading here)
            # and declare the replica dead so the supervisor
            # re-dispatches its in-flight work to survivors.
            logger.exception("continuous pump crashed (replica %d)",
                             replica)
            try:
                self.anomaly_monitors[replica].crash(
                    traceback.format_exc())
            except Exception:
                logger.exception("crash bundle dump failed "
                                 "(replica %d)", replica)
            # the broker has every token of this attempt BEFORE the
            # supervisor can write a redispatch's `restart` marker on
            # its own connection: a stale token behind the marker would
            # pass the consumer's reset index watermark
            flush(wait=True)
            self._declare_dead(replica, "pump_exception")
        finally:
            engine.after_dispatch = None
            self._pump_live[replica] = False
            with self._rq_cond:
                self._rq_cond.notify_all()   # wake the router's sweep
            client.close()
            tok_client.close()

    def _diag_poll(self, engine, replica: int = 0) -> None:
        """One cheap anomaly check per pump iteration: three counter
        reads and a deque scan — the monitor only gets expensive when
        it actually triggers a bundle.  Each replica polls ITS monitor
        against ITS telemetry/watchdog, so one replica's pathology
        never hides behind a healthy fleet average."""
        tm = self.telemetries[replica]
        self.anomaly_monitors[replica].poll(
            alloc_fail_streak=engine.alloc_fail_streak,
            ticks=tm.c_ticks.value,
            compiles=(tm.c_jit_builds.value + tm.c_retraces.value),
            watchdog=self.watchdogs[replica])

    def _brownout_eval(self) -> None:
        """One broker-level brownout controller step (replica 0's pump,
        every ``brownout_interval_s``): aggregate the WORST signal on
        every axis across the fleet — min per-class windowed goodput,
        max effective queue depth, max alloc-fail streak, replica 0's
        recent tick trend from the flight ring — hand them to the pure
        ``plan_brownout``, and on a level change push the new level
        into every engine and leave a trace instant.  One controller,
        one ladder: the fleet degrades (and recovers) together, so a
        client never sees replica-dependent admission."""
        pol = self._brownout_policy
        if pol is None:
            return
        goodput = {
            cls: min(self.watchdogs[r].windowed_goodput(cls)
                     for r in range(self.n_replicas))
            for cls in PRIORITIES}
        queue_depth = 0
        streak = 0
        for r in range(self.n_replicas):
            eng = self.engines[r]
            queue_depth = max(queue_depth,
                              len(self._rqueues[r]) + eng.n_waiting)
            streak = max(streak, eng.alloc_fail_streak)
        tick_s = 0.0
        if self.flight is not None and len(self.flight):
            tail = self.flight.snapshot(last=8)
            tick_s = sum(t.get("dur_ms", 0.0) for t in tail) \
                / len(tail) / 1e3
        prev = self._brownout_state
        state = plan_brownout(pol, prev, goodput=goodput,
                              queue_depth=queue_depth,
                              alloc_fail_streak=streak, tick_s=tick_s)
        self._brownout_state = state
        if state.level == prev.level:
            return
        self._brownout_transitions += 1
        self.telemetry.brownout_transition(state.level, prev.level)
        log = (logger.warning if state.level > prev.level
               else logger.info)
        log("brownout level %d -> %d (goodput=%s queue=%d streak=%d "
            "tick_s=%.3f)", prev.level, state.level,
            {c: round(g, 3) for c, g in goodput.items()}, queue_depth,
            streak, tick_s)
        clamp = int(getattr(self.config, "brownout_standard_max_new",
                            0))
        for e in self.engines:
            e.set_brownout(state.level, standard_max_new=clamp)

    def brownout_level(self) -> int:
        """The fleet's current brownout ladder level (0 = normal) —
        the HTTP front door's per-class admission gate and /healthz
        read it here."""
        return self._brownout_state.level

    def _dump_bundle(self, reason: str, detail: dict) -> str:
        """AnomalyMonitor's dump callback: one self-contained bundle
        directory under ``diag_dir`` (docs/debugging.md), then prune
        to ``diag_max_bundles``."""
        engine = getattr(self, "engine", None)
        spec_acceptance = (engine.spec_acceptance()
                           if engine is not None
                           and hasattr(engine, "spec_acceptance")
                           else None)
        path = dump_bundle(
            self.config.diag_dir, reason=reason, detail=detail,
            flight=self.flight, telemetries=(self.telemetry,),
            config=dataclasses.asdict(self.config),
            logs=self.log_ring.snapshot(),
            slo=self.watchdog.status(),
            spec_acceptance=spec_acceptance)
        prune_bundles(self.config.diag_dir,
                      max(1, self.config.diag_max_bundles))
        return path

    def _flush_emitter(self, client: RespClient, emitter: TokenEmitter,
                       wait: bool = False) -> int:
        """Publish every token/terminal event buffered since the last
        flush in ONE pipeline — per-step, never per-token — and return
        how many went.  Each token event carries the flush's stamp
        (field ``s``: µs of ``time.monotonic()``, read once a flush),
        from which the front door books the fan-out's delay on it.
        The pipeline is written and NOT waited for: its
        replies are read by the next flush that has something to send,
        a device step later (if the broker has not answered by then,
        that wait is the back-pressure that bounds the backlog to one
        pipeline).  ``wait`` reads them at once (the pump leaving)."""
        def collect():
            try:
                client.collect()
            except Exception:
                logger.exception("token-stream publish failed")

        batch = emitter.drain()
        if batch or wait:
            collect()
        if not batch:
            return 0
        cmds = []
        stamp = b"%d" % (time.monotonic() * 1e6)
        for uri, events in batch:
            key = TOKEN_PREFIX + uri
            for kind, idx, val in events:
                if kind == "tok":
                    cmds.append(("XADD", key, "*", "i", idx, "t", val,
                                 b"s", stamp))
                elif kind == "done":
                    cmds.append(("XADD", key, "*", "done", "1"))
                elif kind == "cancelled":
                    cmds.append(("XADD", key, "*", "cancelled", "1"))
                else:
                    cmds.append(("XADD", key, "*", "error",
                                 str(val)[:500]))
        try:
            client.send(cmds)
        except Exception:
            logger.exception("token-stream publish failed")
            return 0
        if wait:
            collect()
        return len(cmds)

    def _drain_cancels(self, client: RespClient, emitter: TokenEmitter,
                       streaming: set, cancelled_pending: set) -> int:
        """Serve ``serving_cancel`` entries on the pump thread (the
        engine's ``abort`` contract): free the row's slot + BOTH pool
        tenants' blocks immediately, publish a fail-fast "cancelled"
        result, and terminate any live token stream.  Cancels that
        arrive before their request was claimed from the input stream
        park in ``cancelled_pending`` so admission skips them."""
        try:
            entries = client.execute("XRANGE", CANCEL_STREAM, "-", "+")
        except Exception:
            return 0
        if not entries:
            return 0
        ids = []
        for eid, flat in entries:
            ids.append(eid)
            f = {flat[i].decode(): flat[i + 1]
                 for i in range(0, len(flat), 2)}
            uri = f.get("uri", b"").decode()
            if uri:
                self._cancel_one(client, uri, emitter, streaming,
                                 cancelled_pending)
        try:
            client.execute("XDEL", CANCEL_STREAM, *ids)
        except Exception:
            logger.exception("cancel-stream trim failed")
        return len(ids)

    def _cancel_one(self, client: RespClient, uri: str,
                    emitter: TokenEmitter, streaming: set,
                    cancelled_pending: set, engine=None) -> None:
        engine = engine if engine is not None else self.engine
        with self._stats_lock:
            info = self._inflight.pop(uri, None)
        aborted = engine.abort(uri)
        if not aborted and info is None:
            # not in the engine and not tracked: either it already
            # published (don't clobber the result) or it is still in
            # the input stream — park the uri so admission skips it
            if uri not in streaming:
                if len(cancelled_pending) < 4096:
                    cancelled_pending.add(uri)
                return
        if uri in streaming:
            streaming.discard(uri)
            emitter.cancelled(uri)
        self.telemetry.req_cancelled(uri)
        # fail-fast error result so a blocked query() client returns
        # now instead of riding out its timeout
        self._publish_error({"uri": uri.encode()}, "cancelled")
        if info is not None:
            self._finish_entries(client, [info[1]])

    # ---- multi-replica router (serving/policy.py route_request) -------

    def _pop_routed(self, replica: int, wait_s: float):
        """A pump's claim path in multi-replica mode: pop up to
        batch_size routed entries from THIS replica's queue.  A killed
        pump claims nothing more — its unclaimed queue becomes the
        router's to re-place (``_reroute_dead``)."""
        cap = self.config.batch_size
        out = []
        with self._rq_cond:
            if self._pump_stops[replica].is_set():
                return [], []
            q = self._rqueues[replica]
            if not q:
                self._rq_cond.wait(wait_s)
                if self._pump_stops[replica].is_set():
                    return [], []
            while q and len(out) < cap:
                out.append(q.popleft())
        if not out:
            return [], []
        return [f for f, _ in out], [e for _, e in out]

    def _drain_routed_cancels(self, client: RespClient, replica: int,
                              emitter: TokenEmitter, streaming: set,
                              cancelled_pending: set) -> int:
        """Multi-replica cancel leg: the router already fanned the
        cancel stream out to owning replicas (``_route_cancels``); each
        pump serves its own share against ITS engine."""
        with self._rq_cond:
            if not self._rcancels[replica]:
                return 0
            uris = list(self._rcancels[replica])
            self._rcancels[replica].clear()
        for uri in uris:
            self._cancel_one(client, uri, emitter, streaming,
                             cancelled_pending,
                             engine=self.engines[replica])
        return len(uris)

    def replica_signals(self, replica: int) -> ReplicaSignals:
        """Snapshot one replica's live routing signals: effective load
        (routed-but-unclaimed + queued-in-engine + resident), pool
        pressure (paged engines only — arena replicas report no block
        counts and are never 'pressured' on that leg), and per-class
        SLO goodput from the replica's own watchdog."""
        eng = self.engines[replica]
        pool = getattr(eng, "_pool", None)
        per_class = self.watchdogs[replica].status()["per_class"]
        beat = self._beats[replica]
        return ReplicaSignals(
            replica=replica,
            live=self._pump_live[replica],
            queue_depth=(len(self._rqueues[replica])
                         + eng.n_waiting + eng.n_active),
            allocatable_blocks=(pool.allocatable()
                                if pool is not None else None),
            alloc_fail_streak=eng.alloc_fail_streak,
            goodput={c: d["goodput"] for c, d in per_class.items()},
            role=(self.replica_roles[replica]
                  if self.replica_roles is not None else None),
            heartbeat_age_s=((time.monotonic() - beat)
                             if beat > 0.0 else None))

    def router_status(self) -> dict:
        """Live routing view — the observability surface behind the
        ``zoo_router_*`` families and the serve-smoke 2-replica leg's
        assertions."""
        status = {
            "n_replicas": self.n_replicas,
            "live": list(self._pump_live),
            "routed": list(self._routed_counts),
            "rerouted": self._rerouted_count,
            "queue_depths": [len(q) for q in self._rqueues],
            "roles": (list(self.replica_roles)
                      if self.replica_roles is not None else None),
            "handoffs": self._role_handoffs,
            # supervisor view (docs/debugging.md § Crash recovery)
            "deaths": self._deaths,
            "death_reasons": list(self._death_reasons),
            "redispatched": self._redispatched,
            "handoff_acks": self._handoff_acks,
            "handoff_timeouts": self._handoff_timeouts,
            "handoff_retries": self._handoff_retries,
            "unrouted": len(self._unrouted),
            "unrouted_expired": self._unrouted_expired,
        }
        if self._fault is not None:
            status["faults"] = self._fault.snapshot()
        if self.engines:
            status["signals"] = [
                dataclasses.asdict(self.replica_signals(r))
                for r in range(self.n_replicas)]
        return status

    def kill_pump(self, replica: int) -> None:
        """Gracefully retire one replica: the router stops placing new
        work there at once, the pump claims nothing more but keeps
        stepping until every request already admitted to its engine
        has published, then exits; the replica's routed-but-unclaimed
        entries are swept onto survivors by the router.  The drain
        test and the serve-smoke 2-replica leg drive this path."""
        if not 0 <= replica < self.n_replicas:
            raise ValueError(f"no replica {replica} "
                             f"(n_replicas={self.n_replicas})")
        if self.n_replicas == 1:
            raise ValueError(
                "kill_pump on the sole pump would stop serving "
                "entirely — that is stop()")
        self._pump_live[replica] = False
        self._pump_stops[replica].set()
        with self._rq_cond:
            self._rq_cond.notify_all()

    def _route_one(self, client: RespClient, fields: Dict[str, bytes],
                   eid) -> None:
        """Place ONE claimed entry: cancel-raced entries die here
        without touching any engine; otherwise route_request ranks the
        live replicas on (pressure, SLO degradation, depth, round-
        robin distance) and the entry lands in the winner's queue."""
        try:
            uri = fields["uri"].decode()
        except Exception:
            uri = ""
        if uri and uri in self._router_cancelled:
            self._router_cancelled.discard(uri)
            self._publish_error({"uri": fields["uri"]}, "cancelled")
            self._finish_entries(client, [eid])
            return
        priority = None
        if "priority" in fields:
            try:
                priority = decode_priority(
                    self._decode_value(fields["priority"]))
            except Exception:
                priority = None
        sigs = [self.replica_signals(r)
                for r in range(self.n_replicas)]
        if self._prefix_directory is not None:
            # prefix locality: hash the prompt's full blocks exactly
            # like paged admission will and ask the fleet directory
            # which replica already holds the deepest leading run
            # (HBM index or host store).  Advisory only — a failed
            # decode leaves prefix_blocks at 0, never blocks routing.
            try:
                pcol = self.config.prompt_col or "prompt"
                if pcol in fields:
                    toks = np.asarray(self._decode_value(
                        fields[pcol])).reshape(-1)
                    bs = self.config.engine_block_size
                    # admission caps the usable match at (plen-1)//bs
                    # blocks (the last prompt token always recomputes)
                    hashes = chain_hashes(
                        [int(t) for t in toks],
                        bs)[: max(0, (len(toks) - 1) // bs)]
                    if hashes:
                        depths = self._prefix_directory.match_depths(
                            hashes)
                        sigs = [dataclasses.replace(
                                    s, prefix_blocks=depths.get(
                                        s.replica, 0))
                                for s in sigs]
            except Exception:
                logger.exception(
                    "prefix-locality probe failed; routing "
                    "locality-blind")
        # a NEW request always enters at its prefill phase; without
        # replica_roles every signal's role is None and the rank is
        # bit-identical to role-less routing
        r = route_request(sigs, priority, self._rr_cursor,
                          phase=("prefill" if self.replica_roles
                                 else None))
        if r is None:
            # no live pump anywhere: park the entry — the fleet may be
            # mid-recovery (a replica restarting, a supervisor sweep in
            # flight).  The router's unrouted sweep re-places it when a
            # pump returns, or expires it to a terminal error after
            # unrouted_ttl_s so no client waits forever.
            self._unrouted.append((fields, eid, time.monotonic()))
            return
        with self._rq_cond:
            self._rqueues[r].append((fields, eid))
            if uri:
                self._uri_replica[uri] = r
                while len(self._uri_replica) > 65536:
                    self._uri_replica.popitem(last=False)
            self._routed_counts[r] += 1
            if self.replica_roles is not None and \
                    self.replica_roles[r] == "prefill":
                self._role_prefill_routed += 1
            self._rr_cursor = (r + 1) % self.n_replicas
            self._rq_cond.notify_all()

    def _handoff_request(self, src: int, state: dict) -> None:
        """Place one exported prefill on a decode-heavy replica — runs
        on the SOURCE pump thread, inside the engine's ``handoff_cb``,
        the tick the prompt's first token lands.  ``route_request``
        ranks the fleet with ``phase="decode"``: decode-role replicas
        win, a pressured/degraded decode tier falls back across roles,
        and the source itself is the last resort (self-adoption — the
        request decodes where it prefilled; a beat slower, never
        wrong).  ``submit_handoff`` only enqueues host state under the
        destination's engine lock, so calling straight into another
        replica's engine from this thread is safe; all device writes
        happen later on the destination pump at admission.  The
        ``kill_pump`` drain contract holds unchanged: an exported
        request counts as admitted work on its DESTINATION, whose pump
        keeps stepping until its engine drains.

        Two-phase delivery (``handoff_ack_timeout_s > 0``): the state
        dict — which holds the exported chain's host tensors, keeping
        them referenced — is retained in ``_pending_handoffs`` until
        the destination's ``_admit_handoff`` fires the ``on_adopt``
        ack; the router's ``_sweep_handoffs`` re-dispatches a delivery
        whose ack never lands (dropped transfer, destination died
        mid-adoption) to an alternate replica, giving the handoff leg
        the same at-least-once contract as fresh admissions."""
        t0 = time.monotonic()
        uri = state.get("uri", "")
        sigs = [self.replica_signals(r)
                for r in range(self.n_replicas)]
        r = route_request(sigs, state.get("priority"),
                          self._rr_cursor, phase="decode")
        if r is None:
            r = src
        ack_timeout = getattr(self.config, "handoff_ack_timeout_s", 0.0)
        two_phase = bool(uri) and ack_timeout > 0 and r != src
        if two_phase:
            state = dict(state)
            state["on_adopt"] = self._ack_handoff
            self._pending_handoffs[uri] = {
                "state": state, "src": src, "dst": r,
                "sent_at": time.monotonic(), "retries": 0}
        deliver = True
        if self._fault is not None and r != src:
            act = self._fault.handoff_action()
            if act is not None:
                kind, delay = act
                if kind == "drop" and two_phase:
                    # swallowed delivery: the pending entry stays; the
                    # router's ack-timeout sweep recovers the request
                    deliver = False
                    logger.warning("fault injection dropped handoff "
                                   "of %r to replica %d", uri, r)
                elif kind == "drop":
                    logger.warning(
                        "drop_handoff fired but two-phase ack is off "
                        "(handoff_ack_timeout_s=0) — delivering "
                        "anyway, a drop would strand %r", uri)
                elif kind == "delay":
                    # a slow DCN transfer: the source pump stalls for
                    # the transfer time (ack sweep may beat it)
                    time.sleep(delay)
        if deliver:
            try:
                self.engines[r].submit_handoff(state)
            except Exception:
                if r == src:
                    if two_phase:
                        self._pending_handoffs.pop(uri, None)
                    # _handoff_slot catches this and error-publishes
                    # the request through its on_error
                    raise
                logger.exception(
                    "handoff of %r to replica %d failed; self-adopting "
                    "on replica %d", uri, r, src)
                if two_phase:
                    self._pending_handoffs.pop(uri, None)
                r = src
                self.engines[r].submit_handoff(state)
        with self._rq_cond:
            self._role_handoffs += 1
            if self.replica_roles is not None and \
                    self.replica_roles[r] == "decode":
                self._role_decode_routed += 1
            if uri:
                # cancels/abandonment now belong to the decode side
                self._uri_replica[uri] = r
            self._rq_cond.notify_all()   # wake an idle decode pump
        if self._h_handoff is not None:
            self._h_handoff.record(time.monotonic() - t0)

    def _ack_handoff(self, uri: str, dst: int) -> None:
        """Adoption ack — fired by the DESTINATION engine's
        ``_admit_handoff`` under its lock, so this must stay record-
        only (no locks, no engine calls): pop the pending entry (its
        drop releases the source-side chain references) and count the
        ack.  ``pop`` with a default keeps a late duplicate ack (a
        retried delivery whose first copy survived after all)
        harmless."""
        if self._pending_handoffs.pop(uri, None) is not None:
            self._handoff_acks += 1

    def _sweep_handoffs(self, client: RespClient) -> None:
        """Router-side ack-timeout sweep: a pending handoff whose
        adoption never acked within ``handoff_ack_timeout_s`` is
        re-dispatched to an alternate replica (``pick_retry_target``
        excludes the unresponsive destination; the source itself is
        the last resort), bounded by ``retry_budget`` — beyond it the
        request error-terminates rather than ping-ponging forever."""
        timeout = getattr(self.config, "handoff_ack_timeout_s", 0.0)
        if timeout <= 0 or not self._pending_handoffs:
            return
        now = time.monotonic()
        budget = int(getattr(self.config, "retry_budget", 2))
        for uri in list(self._pending_handoffs):
            info = self._pending_handoffs.get(uri)
            if info is None:        # acked while we swept
                continue
            verdict = plan_handoff_recovery(
                age_s=now - info["sent_at"], timeout_s=timeout,
                retries=info["retries"], retry_budget=budget)
            if verdict == "wait":
                continue
            self._handoff_timeouts += 1
            if verdict == "give_up":
                self._pending_handoffs.pop(uri, None)
                logger.error("handoff of %r never adopted after %d "
                             "retries — error-terminating", uri,
                             info["retries"])
                self._publish_error(
                    {"uri": uri.encode()},
                    f"handoff adoption failed after "
                    f"{info['retries']} retries")
                with self._stats_lock:
                    held = self._inflight.pop(uri, None)
                self._uri_replica.pop(uri, None)
                self._attempts.pop(uri, None)
                if held is not None:
                    self._finish_entries(client, [held[1]])
                continue
            sigs = [self.replica_signals(r)
                    for r in range(self.n_replicas)]
            r = pick_retry_target(
                sigs, info["state"].get("priority"), self._rr_cursor,
                exclude=(info["dst"],), phase="decode")
            if r is None:
                r = info["src"]
            logger.warning("handoff of %r to replica %d timed out "
                           "(no adoption ack in %.1fs) — retrying on "
                           "replica %d", uri, info["dst"], timeout, r)
            info["retries"] += 1
            info["dst"] = r
            info["sent_at"] = now
            self._handoff_retries += 1
            try:
                self.engines[r].submit_handoff(info["state"])
            except Exception:
                logger.exception("handoff retry of %r to replica %d "
                                 "failed; next sweep retries", uri, r)
                continue
            with self._rq_cond:
                self._uri_replica[uri] = r
                self._rq_cond.notify_all()

    def _route_cancels(self, client: RespClient) -> int:
        """Router-side cancel fan-out: owning replicas get the uri in
        their cancel set; uris the router never placed park in
        ``_router_cancelled`` so a late-claimed entry dies at routing
        time (the single-pump path's ``cancelled_pending``, lifted to
        the router)."""
        try:
            entries = client.execute("XRANGE", CANCEL_STREAM, "-", "+")
        except Exception:
            return 0
        if not entries:
            return 0
        ids = []
        with self._rq_cond:
            for eid, flat in entries:
                ids.append(eid)
                f = {flat[i].decode(): flat[i + 1]
                     for i in range(0, len(flat), 2)}
                uri = f.get("uri", b"").decode()
                if not uri:
                    continue
                r = self._uri_replica.get(uri)
                if r is not None:
                    self._rcancels[r].add(uri)
                elif len(self._router_cancelled) < 4096:
                    self._router_cancelled.add(uri)
            self._rq_cond.notify_all()
        try:
            client.execute("XDEL", CANCEL_STREAM, *ids)
        except Exception:
            logger.exception("cancel-stream trim failed")
        return len(ids)

    def _reroute_dead(self, client: RespClient) -> None:
        """Sweep dead replicas' unclaimed queues onto survivors — the
        other half of the graceful-kill contract: admitted work drains
        in place, unclaimed work moves."""
        moved = []
        with self._rq_cond:
            for r in range(self.n_replicas):
                if self._pump_live[r] or not self._rqueues[r]:
                    continue
                while self._rqueues[r]:
                    moved.append(self._rqueues[r].popleft())
        for fields, eid in moved:
            self._rerouted_count += 1
            self._route_one(client, fields, eid)

    # ---- supervisor: liveness, death, at-least-once redispatch --------

    def _declare_dead(self, replica: int, reason: str) -> None:
        """UNPLANNED death: mark the replica dead (idempotent), stop
        routing to it, and queue it for the router's redispatch sweep.
        Distinct from ``kill_pump`` — a graceful kill drains admitted
        work in place and never lands here; a declared death's
        in-flight requests are lost and must be re-placed."""
        with self._rq_cond:
            if self._death_reasons[replica] is not None:
                return
            self._death_reasons[replica] = reason
            self._deaths += 1
            self._pump_live[replica] = False
            self._pump_stops[replica].set()
            self._dead_unswept.add(replica)
            self._rq_cond.notify_all()
        logger.error("replica %d declared dead (%s) — its in-flight "
                     "requests will be re-dispatched", replica, reason)

    def _supervise(self, client: RespClient) -> None:
        """One router-loop supervision pass: (a) heartbeat-miss death
        (opt-in via ``supervisor_miss_s``; escaped pump exceptions
        declare themselves regardless), (b) redispatch of dead
        replicas' lost in-flight requests, (c) handoff ack-timeout
        sweep, (d) parked-unrouted TTL sweep.  Every DECISION here is
        a pure ``policy.py`` function (replica_dead / plan_redispatch
        / pick_retry_target / plan_handoff_recovery) that the sim's
        ``FleetModel`` exercises identically."""
        miss = float(getattr(self.config, "supervisor_miss_s", 0.0))
        if miss > 0.0:
            now = time.monotonic()
            for r in range(self.n_replicas):
                if (self._pump_live[r]
                        and not self._pump_stops[r].is_set()
                        and self._beats[r] > 0.0
                        and replica_dead(now - self._beats[r], miss)):
                    self._declare_dead(r, "heartbeat_miss")
        while True:
            with self._rq_cond:
                if not self._dead_unswept:
                    break
                dead = self._dead_unswept.pop()
            self._redispatch_replica(client, dead)
        self._sweep_handoffs(client)
        self._sweep_unrouted(client)

    def _reread_entry(self, client: RespClient,
                      eid) -> Optional[Dict[str, bytes]]:
        """Re-read one UNACKED input-stream entry by id — the broker
        retains every claimed entry until ``_finish_entries`` acks it,
        which is exactly what makes at-least-once redispatch possible:
        the original request fields survive the replica that was
        serving them."""
        try:
            if isinstance(eid, bytes):
                eid = eid.decode()
            entries = client.execute("XRANGE", INPUT_STREAM, eid, eid)
        except Exception:
            logger.exception("redispatch re-read failed for entry %r",
                             eid)
            return None
        want = eid.encode() if isinstance(eid, str) else eid
        for got, flat in entries or []:
            # trust nothing: a broker with sloppy range semantics must
            # not make us resurrect the WRONG request N times while the
            # real lost one stays stranded
            if got == want:
                return {flat[i].decode(): flat[i + 1]
                        for i in range(0, len(flat), 2)}
        return None

    def _redispatch_replica(self, client: RespClient,
                            dead: int) -> None:
        """Re-place a dead replica's lost in-flight requests on
        survivors with at-least-once semantics: ``plan_redispatch``
        decides retry / terminal-error (budget or deadline exhausted)
        / terminal-cancelled per request; a retry re-reads the
        original entry from the unacked stream, bumps the attempt
        counter, and XADDs a ``restart`` marker on the token stream so
        streaming clients see the emitted-token index reset instead of
        a silent splice."""
        with self._stats_lock:
            lost = [(uri, info) for uri, info in self._inflight.items()
                    if self._uri_replica.get(uri) == dead]
        budget = int(getattr(self.config, "retry_budget", 2))
        deadline = float(getattr(self.config, "request_deadline_s",
                                 0.0))
        now = time.monotonic()
        for uri, (t_submit, eid) in lost:
            with self._stats_lock:
                if self._inflight.pop(uri, None) is None:
                    continue        # published while we swept
            attempt = self._attempts.get(uri, 1)
            was_cancelled = (uri in self._rcancels[dead]
                             or uri in self._router_cancelled)
            verdict = plan_redispatch(
                attempt=attempt, retry_budget=budget,
                cancelled=was_cancelled, age_s=now - t_submit,
                deadline_s=deadline)
            if verdict == "cancel":
                self._rcancels[dead].discard(uri)
                self._router_cancelled.discard(uri)
                self._publish_error({"uri": uri.encode()}, "cancelled")
                self._finish_entries(client, [eid])
                self._uri_replica.pop(uri, None)
                self._attempts.pop(uri, None)
                continue
            if verdict == "error":
                why = ("deadline" if deadline > 0.0
                       and now - t_submit > deadline else "retry budget")
                self._publish_error(
                    {"uri": uri.encode()},
                    f"replica {dead} died; {why} exhausted "
                    f"(attempts={attempt})")
                self._finish_entries(client, [eid])
                self._uri_replica.pop(uri, None)
                self._attempts.pop(uri, None)
                continue
            fields = self._reread_entry(client, eid)
            if fields is None:
                self._publish_error(
                    {"uri": uri.encode()},
                    f"replica {dead} died; original request entry "
                    f"lost — cannot redispatch")
                self._finish_entries(client, [eid])
                self._uri_replica.pop(uri, None)
                self._attempts.pop(uri, None)
                continue
            self._attempts[uri] = attempt + 1
            self._redispatched += 1
            logger.warning("re-dispatching %r (attempt %d/%d) after "
                           "replica %d died", uri, attempt + 1,
                           max(1, budget), dead)
            if "stream" in fields:
                # client-visible restart: the consumer resets its
                # emitted-token index to 0 (queues.stream_events /
                # the SSE leg surface it as a `restart` event)
                try:
                    client.execute("XADD", TOKEN_PREFIX + uri, "*",
                                   "restart", str(attempt + 1))
                except Exception:
                    logger.exception("restart marker publish failed "
                                     "for %r", uri)
            self._route_one(client, fields, eid)
            r2 = self._uri_replica.get(uri)
            if r2 is not None:
                try:
                    self.telemetries[r2].req_redispatched(
                        uri, attempt + 1)
                except Exception:
                    pass
        # the dead replica's pending cancels follow their requests:
        # re-placed uris move to the new owner's cancel set, the rest
        # park router-side so a late-claimed entry still dies
        with self._rq_cond:
            orphans = list(self._rcancels[dead])
            self._rcancels[dead].clear()
            for uri in orphans:
                r = self._uri_replica.get(uri)
                if r is not None and r != dead:
                    self._rcancels[r].add(uri)
                elif len(self._router_cancelled) < 4096:
                    self._router_cancelled.add(uri)
            self._rq_cond.notify_all()

    def _sweep_unrouted(self, client: RespClient) -> None:
        """Parked-unrouted sweep: entries ``_route_one`` could not
        place (zero live replicas) wait bounded — re-placed the moment
        a pump is live again, error-terminated after
        ``unrouted_ttl_s`` so no client waits forever (the HTTP front
        door additionally 503s new submits while the fleet is dead)."""
        if not self._unrouted:
            return
        ttl = float(getattr(self.config, "unrouted_ttl_s", 5.0))
        now = time.monotonic()
        any_live = any(self._pump_live)
        for _ in range(len(self._unrouted)):
            fields, eid, parked = self._unrouted.popleft()
            if any_live:
                self._route_one(client, fields, eid)
            elif ttl > 0 and now - parked > ttl:
                self._unrouted_expired += 1
                self._publish_error(
                    {"uri": fields.get("uri", b"")},
                    f"no live replicas for {ttl:.1f}s — request "
                    f"expired unplaced")
                self._finish_entries(client, [eid])
            else:
                self._unrouted.append((fields, eid, parked))

    def _loop_router(self) -> None:
        """Router thread (``n_replicas > 1``): the SOLE claimer of the
        broker's consumer group — XREADGROUP as consumer "router" —
        placing each entry via ``_route_one``.  Short claim blocks keep
        the cancel fan-out and the dead-replica sweep responsive."""
        try:
            client = RespClient(self.config.redis_host,
                                self.config.redis_port)
        except OSError:
            logger.exception("router could not connect to the broker "
                             "— multi-replica serving not started")
            return
        try:
            while not self._stop.is_set():
                self._route_cancels(client)
                self._reroute_dead(client)
                self._supervise(client)
                try:
                    requests, ids = self._read_batch(client, "router",
                                                     20)
                except (ConnectionError, OSError):
                    if self._stop.is_set():
                        break
                    time.sleep(0.05)
                    continue
                for fields, eid in zip(requests, ids):
                    self._route_one(client, fields, eid)
        finally:
            client.close()

    def _finish_entries(self, client: RespClient, ids):
        """Ack + delete consumed stream entries (after their results —
        value or error — are published); one pipeline round-trip."""
        if not ids:
            return
        try:
            client.pipeline([("XACK", INPUT_STREAM, self.GROUP, *ids),
                             ("XDEL", INPUT_STREAM, *ids)])
        except Exception:
            logger.exception("serving ack failed")

    def _decode_value(self, v: bytes) -> np.ndarray:
        """One request field -> ndarray.  IMG! payloads are compressed
        image bytes: native C++ decode (GIL released, RGB-normalised) +
        optional resize to the configured model input shape; everything
        else is a dense tensor (b64 npy)."""
        if not v.startswith(IMG_MAGIC):
            arr = decode_ndarray(v)
            if arr.dtype.kind in "SUO":
                # a byte/object tensor can never feed a jitted model;
                # fail THIS request with the cause named instead of
                # crashing the whole batch at dispatch
                raise ValueError(
                    f"request field decodes to dtype {arr.dtype} — send "
                    f"numeric ndarrays, or ImageBytes/enqueue_image for "
                    f"encoded images")
            return arr
        from analytics_zoo_tpu.data.image import decode_image_bytes

        img = decode_image_bytes(v[len(IMG_MAGIC):])
        if self.config.image_shape:
            if self._img_resize is None:
                from analytics_zoo_tpu.data.image import ImageResize

                h, w = self.config.image_shape
                self._img_resize = ImageResize(int(h), int(w))
            img = self._img_resize(img)
        return img

    def _publish_error(self, req: Dict[str, bytes], msg: str):
        """One request failed decode/shape checks: publish an error result
        so its client fails fast instead of blocking to timeout.  (The
        stream entry is already consumed — without this the request would
        vanish.)"""
        try:
            uri = req["uri"].decode()
            self.client.pipeline([
                ("HSET", RESULT_PREFIX + uri, "error", msg[:500]),
                ("XADD", SIGNAL_PREFIX + uri, "*", "ok", "0"),
                # index it like a normal result so dequeue()-only clients
                # still observe (and consume) the failure
                ("SADD", "__result_keys__", uri)])
            with self._stats_lock:
                self._written.append((uri, time.monotonic()))
        except Exception:
            logger.exception("failed to publish serving error")

    def _dispatch_batch(self, client: RespClient,
                        requests: List[Dict[str, bytes]], ids: List[bytes]):
        """Decode + enqueue the forward on the device; returns the in-flight
        handle without blocking on the result.  Image payloads decode on a
        thread pool — the native decoder releases the GIL, so a batch of
        JPEGs decodes in parallel while the previous batch computes.
        A request that fails to decode (or whose shape disagrees with the
        batch) gets an ERROR result published and its entry finished; the
        rest of the batch still runs — one bad payload must never
        black-hole its batchmates."""
        # control fields are NEVER model inputs: discovered columns
        # treating e.g. a stray `prefix` id as a second input would make
        # pre_pad read it as per-row prompt lengths — silently wrong
        # generations.  The continuous pump honors these fields; the
        # batch path cannot (its one scan runs every row identically),
        # so a request carrying any of them error-publishes rather than
        # silently serving different semantics than asked for.
        control = {"prefix", "max_new", "temperature", "seed", "top_p"}
        cols = self.config.input_cols or \
            [k for k in requests[0] if k != "uri" and k not in control]
        # a model may LEGITIMATELY have an input named e.g.
        # "temperature" (explicit input_cols); only fields that are not
        # inputs count as controls here
        reject = control - set(cols)
        per_req: List[Optional[List[np.ndarray]]] = [None] * len(requests)

        def decode_req(i_req):
            i, r = i_req
            try:
                present = sorted(reject & set(r))
                if present:
                    raise ValueError(
                        f"per-request controls {present} need "
                        f"continuous_batching: true (the batch path "
                        f"runs every row identically)")
                per_req[i] = [self._decode_value(r[c]) for c in cols]
            except Exception as e:
                self._publish_error(r, f"decode failed: {e!r}")

        heavy = any(r.get(c, b"").startswith(IMG_MAGIC)
                    for r in requests for c in cols)
        items = list(enumerate(requests))
        if heavy and len(requests) >= 4:
            list(self._decode_pool.map(decode_req, items))
        else:
            for it in items:
                decode_req(it)
        # generative serving: ragged prompts right-pad to the batch max
        # BEFORE the shape check, and their true lengths ride along as an
        # extra model input (load_flax_generator contract)
        req_lengths: List[Optional[int]] = [None] * len(requests)
        prompts_active = bool(self.config.prompt_col) and \
            self.config.prompt_col in cols
        if prompts_active:
            ci = cols.index(self.config.prompt_col)
            # per-request bounds check FIRST — an over-long or empty
            # prompt must error alone, not (via the shared pad width)
            # black-hole its batchmates at dispatch
            limit = getattr(self.model, "max_prompt_width", None)
            for i, (r, v) in enumerate(zip(requests, per_req)):
                if v is None:
                    continue
                if np.asarray(v[ci]).ndim != 1:
                    # error it here, not via the generic shape check — a
                    # malformed prompt as the batch's first request would
                    # otherwise set ref_shapes and fail valid batchmates
                    self._publish_error(
                        r, f"prompt must be a 1-D token array, got shape "
                           f"{np.asarray(v[ci]).shape}")
                    per_req[i] = None
                    continue
                n = len(v[ci])
                if n < 1 or (limit is not None and n > limit):
                    self._publish_error(
                        r, f"prompt length {n} outside [1, {limit}]")
                    per_req[i] = None
            # every surviving row passed the 1-D check above, so each one
            # gets a recorded length here — dispatch relies on that
            widths = [len(v[ci]) for v in per_req if v is not None]
            wmax = max(widths) if widths else 0
            for i, v in enumerate(per_req):
                if v is None:
                    continue
                arr = np.asarray(v[ci])
                req_lengths[i] = len(arr)
                if len(arr) < wmax:
                    v[ci] = np.concatenate(
                        [arr, np.full(wmax - len(arr),
                                      self.config.prompt_pad_id,
                                      arr.dtype)])
        # shape check against the first good request: mismatches error out
        # individually instead of failing np.stack for everyone
        ref_shapes = next((tuple(a.shape for a in v)
                           for v in per_req if v is not None), None)
        good_reqs, good_ids, good_vals, good_lens, done_ids = \
            [], [], [], [], []
        for r, eid, v, ln in zip(requests, ids, per_req, req_lengths):
            if v is None:
                done_ids.append(eid)        # error already published
                continue
            if tuple(a.shape for a in v) != ref_shapes:
                self._publish_error(
                    r, f"input shape {[a.shape for a in v]} != batch "
                       f"shape {list(ref_shapes)}")
                done_ids.append(eid)
                continue
            good_reqs.append(r)
            good_ids.append(eid)
            good_vals.append(v)
            good_lens.append(ln)
        self._finish_entries(client, done_ids)
        if not good_reqs:
            return None
        arrays = [np.stack([v[ci] for v in good_vals])
                  for ci in range(len(cols))]
        if prompts_active:
            # every row that survived the prompt checks above has a
            # length; threading them unconditionally means the model never
            # falls back to re-inferring lengths from its own pad id
            assert all(ln is not None for ln in good_lens)
            arrays.append(np.asarray(good_lens, np.int32))
        try:
            waiter = self.model.predict_async(*arrays)
        except Exception as e:
            # dispatch itself failed (e.g. an incompatible hot-reloaded
            # model): the stream entries are already consumed, so every
            # request must get an error result, not a silent vanish
            logger.exception("serving model dispatch failed")
            for r in good_reqs:
                self._publish_error(r, f"model dispatch failed: {e!r}")
            self._finish_entries(client, good_ids)
            return None
        return good_reqs, good_ids, waiter, time.perf_counter()

    def _publish_batch(self, client: RespClient, requests, ids, waiter,
                       t0: float):
        preds = np.asarray(waiter())    # blocks until the device is done
        dt = (time.perf_counter() - t0) * 1000
        uris = [r["uri"].decode() for r in requests]
        cmds = []
        for uri, p in zip(uris, preds):
            cmds.append(("HSET", RESULT_PREFIX + uri,
                         "value", encode_ndarray(p)))
            # wake the XREAD-blocked client AFTER the hash is in place
            # (pipelined commands execute in order on the broker)
            cmds.append(("XADD", SIGNAL_PREFIX + uri, "*", "ok", "1"))
        # maintain the dequeue-all index (client OutputQueue.dequeue);
        # a set, pruned by the client on consume, so it stays bounded by
        # the number of UNREAD results rather than total requests served
        cmds.append(("SADD", "__result_keys__", *uris))
        client.pipeline(cmds)
        self._finish_entries(client, ids)   # results are visible: ack+del
        now = time.monotonic()
        with self._stats_lock:
            self._written.extend((u, now) for u in uris)
            self.stats["requests"] += len(requests)
            self.stats["batches"] += 1
            self.stats["batch_fill"] = len(requests) / self.config.batch_size
            self.stats["predict_ms"] = dt
        self._prune_abandoned(client, now)

    def _prune_abandoned(self, client: RespClient, now: float):
        """One pipeline round-trip per pruned uri, on the calling worker's
        own connection — pruning a TTL burst must not serialise every
        worker through the shared client's lock.  Each pruned result is
        counted (``zoo_serving_requests_abandoned_total``) and leaves a
        terminal ``request_abandoned`` event in the trace — a client
        that timed out and walked away used to vanish without a sign.

        Continuous mode also prunes IN-FLIGHT rows here: a request
        resident (or queued) in the engine longer than the ttl has no
        collector left, so it is aborted — the engine frees its slot
        and every KV block it holds, target AND draft pools alike for
        a speculative row — and its stream entry is acked so the group
        never redelivers dead work."""
        ttl = self.config.result_ttl_s
        engines = list(getattr(self, "engines", ()))
        if engines:
            with self._stats_lock:
                stale = [(u, te) for u, te in self._inflight.items()
                         if now - te[0] > ttl]
                for u, _ in stale:
                    del self._inflight[u]
            for u, (t_sub, eid) in stale:
                # False = the row completed in the race window; its
                # publish already handled the entry.  A uri lives in at
                # most ONE replica's engine, so any() stops there.
                if any(e.abort(u) for e in engines):
                    self.telemetry.req_abandoned(u, now - t_sub)
                    self._finish_entries(client, [eid])
                    # a streaming abandoner's token stream dies with it
                    try:
                        client.execute("DEL", TOKEN_PREFIX + u)
                    except Exception:
                        pass
        while True:
            with self._stats_lock:
                if not self._written or \
                        now - self._written[0][1] <= ttl:
                    return
                uri, written_at = self._written.popleft()
            client.pipeline([
                ("DEL", RESULT_PREFIX + uri, SIGNAL_PREFIX + uri,
                 TOKEN_PREFIX + uri),
                ("SREM", "__result_keys__", uri)])
            self.telemetry.req_abandoned(uri, now - written_at)

    def _drop_inflight(self, uri: str) -> None:
        with self._stats_lock:
            self._inflight.pop(uri, None)

    # ---- front door (serving/frontdoor.py) ----------------------------

    def stream_events(self, uri: str, timeout: float = 30.0,
                      poll_s: float = 1.0):
        """Tail a ``stream=True`` request's per-token stream — the
        Redis-queue analog of the HTTP SSE path (same events:
        token / done / cancelled / error, plus ping heartbeats).
        Opens its own broker connection so it can block without
        serialising the shared client."""
        outq = OutputQueue(self.config.redis_host, self.port)
        try:
            yield from outq.stream_events(uri, timeout=timeout,
                                          poll_s=poll_s)
        finally:
            outq.close()

    def cancel(self, uri: str) -> None:
        """Request live cancellation: the pump aborts the row on its
        next loop iteration, freeing both pool tenants' blocks
        immediately (vs. the ``result_ttl_s`` prune).  Idempotent;
        callable from any thread."""
        self.client.execute("XADD", CANCEL_STREAM, "*", "uri", uri)

    def mode_flags(self) -> Dict[str, bool]:
        """Engine mode booleans for /healthz: which serving features
        this job composed (the engine object is authoritative for
        speculation — it knows whether a draft actually loaded)."""
        eng = getattr(self, "engine", None)
        return {
            "continuous": bool(self.config.continuous_batching),
            "paged": bool(self.config.engine_paged),
            "chunked": bool(self.config.engine_chunked),
            "speculative": bool(
                eng is not None and
                getattr(eng, "draft_model", None) is not None),
            "qos": bool(self.config.qos_enabled),
            "brownout": bool(getattr(self.config, "brownout", False)),
        }

    # ---- observability (SURVEY §5: queue depth = backlog metric) ------

    def backlog(self) -> int:
        return int(self.client.execute("XLEN", INPUT_STREAM))

    def accepting_replicas(self) -> Optional[int]:
        """Live pump count for readiness checks, or ``None`` when pump
        liveness doesn't apply (micro-batch mode, or the job not yet
        started).  The HTTP front door treats only an explicit 0 as
        fleet-dead: /healthz flips ``accepting: false`` and submits
        503 with a finite Retry-After instead of accepting work that
        can never be placed."""
        if not self.config.continuous_batching or not self._threads:
            return None
        return sum(1 for v in self._pump_live if v)
