"""InferenceModel — unified, thread-safe batched inference.

Reference surface (SURVEY.md §2.3; ref: Scala pipeline/inference/
InferenceModel.scala + AbstractModel/FloatModel, OpenVinoInferenceSupportive
JNI): one handle that loads BigDL/Caffe/TF/Torch/OpenVINO-IR models and
serves thread-safe ``predict`` from a pool of native predictors (int8
calibration optional).

TPU re-design: the "multi-format zoo" collapses to flax modules + orbax
param trees (anything exported by ``Estimator.save``); XLA replaces the
predictor pool — compiled executables are thread-safe, so concurrency
needs only a lock around the compile cache, not N model replicas.
Variable request sizes hit a BUCKETED jit cache (next-pow2 padding), the
TPU analog of OpenVINO's fixed-shape compiled networks: a bounded set of
compiled programs, no recompile per request size.  The reference's int8
calibration role is filled by ``load_flax(..., quantize="int8")`` —
weight-only symmetric int8 with dequant fused into the jitted forward
(learn/quantize.py; measured ~4x weight compression, sub-5% logit
deviation, no calibration set needed).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def filter_prompt_buckets(prompt_buckets: Sequence[int],
                          max_position: int,
                          max_new_tokens: int) -> Tuple[int, ...]:
    """Prompt buckets usable by a generator: a bucket only counts if the
    padded prompt + generation still fits the model's position table.
    Shared by load_flax_generator and ContinuousEngine so the two entry
    paths can never disagree about which prompts are servable."""
    limit = int(max_position) - int(max_new_tokens)
    out = tuple(b for b in sorted(set(int(b) for b in prompt_buckets))
                if b <= limit)
    if not out:
        raise ValueError(
            f"no prompt bucket fits: max_position {max_position} - "
            f"max_new_tokens {max_new_tokens} = {limit} < smallest "
            f"bucket {min(prompt_buckets)}")
    return out


class InferenceModel:
    """ref-parity methods: load / predict / (doLoadTF etc. collapse to
    ``load``).

    Args:
      concurrent_num: kept for API parity (the reference sized its
        predictor pool with it); XLA needs no pool, so it only caps the
        semaphore guarding host-side staging memory.
    """

    def __init__(self, concurrent_num: int = 4,
                 batch_buckets: Sequence[int] = (1, 8, 32, 128)):
        self._apply_fn: Optional[Callable] = None
        self._variables = None
        self._buckets = tuple(sorted(batch_buckets))
        self._jit: Optional[Callable] = None
        self._jit_outer = True  # False = host-loop apply_fn (spec decode)
        self.spec_stats = None  # cumulative speculative-decoding stats
        self._compile_lock = threading.Lock()
        self._sem = threading.Semaphore(max(1, concurrent_num))
        self._takes_train: Optional[str] = None
        # optional host-side input normaliser (generator prompt padding)
        self._pre_pad: Optional[Callable] = None
        # generator-only serving bounds (load_flax_generator sets them)
        self.max_prompt_width: Optional[int] = None
        self.prompt_pad_id: Optional[int] = None

    # ---- loading -----------------------------------------------------

    def _install_quantized(self, variables, quantize,
                           allow_mxu: bool = False):
        """Shared weight-quantization staging for every load path:
        quantize the tree, stage it in device memory ONCE (the numpy
        leaves quantize_params builds would otherwise be re-uploaded on
        every predict call), and install the fused dequant.

        ``int8_mxu`` (on-MXU execution) is only valid where the model is
        a flax-linen tree the method interceptor can rewrite —
        ``load_flax`` sets ``allow_mxu``; importer-wrapped models
        (OpenVINO/TF/torch translators) and the generation scan keep the
        weight-only modes."""
        self.quant_stats = None
        self._int8_mxu = False
        if quantize == "int8_mxu" and not allow_mxu:
            raise ValueError(
                "quantize='int8_mxu' is only supported by load_flax "
                "(flax-linen models); use 'int8' (weight-only) here")
        if quantize:
            from analytics_zoo_tpu.learn.quantize import (
                dequantize, quantize_params)

            mode = quantize
            if quantize == "int8_mxu":
                mode = "int8"           # same storage format
                self._int8_mxu = True
            variables, self.quant_stats = quantize_params(variables,
                                                          mode)
            variables = jax.device_put(variables)
            self._dequant = None if self._int8_mxu else dequantize
        else:
            self._dequant = None
        return variables

    def load_flax(self, model, variables,
                  quantize: Optional[str] = None) -> "InferenceModel":
        """Serve a flax module with a {'params': ..., [...]} tree.

        quantize: None | "int8" (weight-only symmetric int8, per-channel
        scales, dequant fused into the jitted forward — the reference's
        OpenVINO int8 role; the memory-capacity mode) | "int8_mxu"
        (on-MXU int8: dynamic per-tensor activation quantization and
        int8 x int8 -> int32 Dense/Conv — the speed mode, ~2x MXU
        int8 rate; docs/serving.md) | "bf16" (cast weights to bfloat16).
        ``self.quant_stats`` reports the measured weight-bytes compression.
        """
        import inspect

        self.model = model
        self._variables = self._install_quantized(variables, quantize,
                                                  allow_mxu=True)
        self._takes_train = None    # re-derive per model: a stale value
        #                             from a previous load would pass an
        #                             unexpected kwarg into the new model
        try:
            sig = inspect.signature(type(model).__call__)
            if "train" in sig.parameters:
                self._takes_train = "train"
            elif "deterministic" in sig.parameters:
                self._takes_train = "deterministic"
        except (TypeError, ValueError):
            pass

        int8_mxu = self._int8_mxu

        def apply_fn(variables, *feats):
            if self._dequant is not None:
                variables = self._dequant(variables)
            kw = {}
            if self._takes_train == "train":
                kw["train"] = False
            elif self._takes_train == "deterministic":
                kw["deterministic"] = True
            if int8_mxu:
                from analytics_zoo_tpu.learn.quantize import int8_call

                return int8_call(model, variables, *feats, **kw)
            return model.apply(variables, *feats, **kw)

        with self._compile_lock:
            # publish the new model and drop the stale wrapper as one
            # step: a predict() compiling concurrently must not publish
            # a wrapper built from the OLD apply_fn over this reset
            self._apply_fn = apply_fn
            self._jit = None    # new model -> stale compiled wrapper
        self._pre_pad = None    # a stale generator pad hook would corrupt
        #                         plain-model inputs
        self.max_prompt_width = None    # ditto the serving bounds limit
        self.prompt_pad_id = None
        self._gen_max_new_tokens = None
        self._jit_outer = True  # ditto a stale host-loop (draft) flag
        self.spec_stats = None  # ditto stale speculative stats
        self._spec_draft = False
        return self

    def load_flax_generator(self, model, variables, max_new_tokens: int,
                            prompt_buckets: Sequence[int] = (16, 32, 64,
                                                             128),
                            pad_id: int = 0,
                            quantize: Optional[str] = None,
                            draft_model=None, draft_variables=None,
                            speculation_k: int = 4
                            ) -> "InferenceModel":
        """Serve autoregressive GENERATION from a TransformerLM: predict
        takes right-padded prompts [B, P] (+ optional per-row lengths [B])
        and returns [B, max_new_tokens] generated token ids.

        The prompt dim is padded up to ``prompt_buckets`` (the seq-dim
        analog of the batch buckets) so the KV-cache generation scan
        compiles a bounded set of shapes.  When lengths are omitted they
        are inferred as the non-``pad_id`` trailing-pad width of each row.
        ``quantize``: None | "int8" | "bf16" — same weight-only scheme as
        ``load_flax`` (dequant fused into the jitted scan), covering the
        int8-LLM-serving role.  No reference counterpart (SURVEY.md §2.5:
        no generative LM upstream) — the serving face of
        models/lm.generate.

        ``draft_model``/``draft_variables`` switch decoding to
        SPECULATIVE (models/speculative.py): the draft proposes
        ``speculation_k`` tokens per round and the target verifies them
        in one cached forward — identical greedy output, fewer
        host round-trips per token by the acceptance rate.  Per-request
        stats land in ``self.spec_stats``.  ``quantize`` applies to
        the TARGET only (the draft is small; quantizing it buys little).
        """
        from analytics_zoo_tpu.models.lm import generate

        if (draft_model is None) != (draft_variables is None):
            raise ValueError("pass draft_model and draft_variables "
                             "together (or neither)")
        self.model = model
        self._variables = self._install_quantized(variables, quantize)
        self._takes_train = None
        # a bucket only counts if the padded prompt + generation still
        # fits the model's position table — otherwise a prompt that
        # genuinely fits would fail generate()'s length check after
        # bucket padding.  Speculative decoding needs k+1 extra cache
        # slack (verify overshoot) and must fit BOTH models' position
        # tables, so its limit is tighter — validated HERE so a request
        # the serving bounds-check admits can never fail at predict time.
        eff_max_pos = model.max_position
        eff_new = max_new_tokens
        if draft_model is not None:
            eff_max_pos = min(model.max_position,
                              draft_model.max_position)
            eff_new = max_new_tokens + int(speculation_k) + 1
        pbuckets = filter_prompt_buckets(prompt_buckets,
                                         eff_max_pos, eff_new)
        # serving batcher reads these to bounds-check ragged prompts
        # per-request and to cross-check its own pad id against the
        # generator's (a mismatch would silently miscount prompt lengths)
        self.max_prompt_width = pbuckets[-1]
        self.prompt_pad_id = int(pad_id)
        # continuous-batching serving builds its engine from these
        self._gen_max_new_tokens = int(max_new_tokens)
        self._gen_prompt_buckets = pbuckets

        if draft_model is not None:
            from analytics_zoo_tpu.models.speculative import (
                speculative_generate)

            # host-loop apply_fn: a fused dequant would re-run EAGERLY
            # per request (no outer jit to fold it into) — dequantize
            # once at load instead, like make_continuous_engine
            if self._dequant is not None:
                self._variables = jax.device_put(
                    self._dequant(self._variables))
                self._dequant = None

            def apply_fn(variables, prompts, lengths):
                # host-loop orchestration (each round is jitted inside);
                # _compiled() must NOT wrap this in an outer jit
                toks, stats = speculative_generate(
                    model, variables, draft_model, draft_variables,
                    prompts, max_new_tokens, k=speculation_k,
                    prompt_len=lengths)
                # CUMULATIVE since load (lock: predicts may run from
                # several serving threads; chunked predicts call this
                # once per chunk) — a per-request hook would be racy.
                # Batch-bucket padding adds phantom all-pad rows whose
                # lengths are 0 (pre_pad rejects real empty prompts):
                # count only REAL rows or the acceptance diagnostic
                # reflects padding, not traffic.
                real = np.asarray(lengths) > 0
                with self._spec_stats_lock:
                    agg = self.spec_stats or {
                        "rounds": 0, "emitted_tokens": 0,
                        "row_rounds": 0}
                    agg["rounds"] += stats["rounds"]
                    agg["emitted_tokens"] += int(
                        stats["per_row_emitted"][real].sum())
                    agg["row_rounds"] += stats["rounds"] * int(real.sum())
                    agg["mean_accepted_per_round"] = (
                        agg["emitted_tokens"] / max(1, agg["row_rounds"]))
                    self.spec_stats = agg
                return toks

            self._jit_outer = False
            self._spec_stats_lock = threading.Lock()
            self.spec_stats = None
            self._spec_draft = True
            self._spec_draft_model = draft_model
            self._spec_draft_variables = draft_variables
            self._spec_k = int(speculation_k)
        else:
            def apply_fn(variables, prompts, lengths):
                if self._dequant is not None:
                    variables = self._dequant(variables)
                return generate(model, variables, prompts,
                                max_new_tokens, prompt_len=lengths)

            self._jit_outer = True
            self.spec_stats = None      # stale draft-run stats would lie
            self._spec_draft = False

        def pre_pad(inputs):
            prompts = np.asarray(inputs[0])
            if len(inputs) > 1:
                lengths = np.asarray(inputs[1], np.int32)
            else:
                nonpad = prompts != pad_id
                # length = index of last non-pad + 1 (right padding)
                lengths = np.where(
                    nonpad.any(axis=1),
                    prompts.shape[1] - np.argmax(nonpad[:, ::-1], axis=1),
                    0).astype(np.int32)
            if (lengths <= 0).any():
                raise ValueError(
                    "empty prompt (length 0) — generation needs at least "
                    "one real token per row")
            pb = _next_bucket(prompts.shape[1], pbuckets)
            if prompts.shape[1] < pb:
                prompts = np.concatenate(
                    [prompts, np.full((len(prompts), pb - prompts.shape[1]),
                                      pad_id, prompts.dtype)], axis=1)
            elif prompts.shape[1] > pb:
                raise ValueError(
                    f"prompt length {prompts.shape[1]} exceeds the largest "
                    f"usable prompt bucket {pb}")
            return prompts, lengths

        with self._compile_lock:
            # same publish discipline as load_flax: new apply_fn and
            # wrapper reset are atomic against a concurrent compile
            self._apply_fn = apply_fn
            self._jit = None
        self._pre_pad = pre_pad
        return self

    def make_continuous_engine(self, max_slots: int = 8,
                               eos_id: Optional[int] = None,
                               ticks_per_step: int = 1,
                               cache_dtype=None,
                               kernel: str = "gather",
                               kv_dtype: Optional[str] = None,
                               mesh=None, partition_rules=None,
                               paged: bool = False,
                               block_size: int = 16,
                               n_blocks: Optional[int] = None,
                               hbm_fraction: Optional[float] = None,
                               enable_prefix_cache: bool = True,
                               chunked: bool = False,
                               tick_token_budget: Optional[int] = None,
                               speculation_k: Optional[int] = None,
                               elastic_pool: bool = False,
                               kv_host_store_bytes: int = 0,
                               prefix_directory=None,
                               replica_id: int = 0,
                               fault_injector=None,
                               telemetry=None, qos=None,
                               flight=None, flight_capacity: int = 2048):
        """Build a ``serving.continuous.ContinuousEngine`` from a model
        loaded via ``load_flax_generator`` (quantized weights dequantize
        once at build — the engine trades the at-rest memory win for
        per-token speed; keep the batch path for memory-bound serving).

        ``mesh`` (with a ``tp`` axis) serves models beyond one chip's
        HBM: weights + KV arena shard over tp (docs/serving.md
        'tp-sharded generation').

        ``paged=True`` swaps the per-slot KV arena for the block-pool
        cache (serving/paged_cache.py: pay-as-you-grow block
        allocation, automatic prefix sharing, preemption-to-queue —
        docs/serving_memory.md); ``block_size``/``n_blocks``/
        ``hbm_fraction``/``enable_prefix_cache`` size and tune it.
        ``kernel="fused"`` reads the pool through the Pallas
        paged-attention kernel instead of the gather reference, and
        ``kv_dtype="int8"`` stores blocks quantized with per-row
        scales (~1.9x more blocks at equal HBM) — both paged-only
        (docs/serving_memory.md 'Fused kernel & int8 blocks').

        ``chunked=True`` turns on the token-budget tick scheduler:
        prompts prefill in ``tick_token_budget``-bounded chunks fused
        with active decodes in one device call per tick — long joiners
        stop stalling residents (docs/serving_memory.md 'Scheduler').

        A draft-loaded handle (``load_flax_generator(draft_model=...)``)
        builds a SPECULATIVE engine; it composes with ``paged`` and
        ``chunked`` freely (docs/serving_memory.md 'Composed modes').
        ``speculation_k`` overrides the per-round proposal depth stored
        at load (``None`` keeps it); it is rejected without a draft.

        ``qos`` (a ``serving.frontdoor.QosPolicy``) turns admission and
        prefill-grant order into a weighted fair share over (priority
        class, tenant) — the serving front door's scheduler
        (docs/serving_qos.md).  ``None`` keeps plain FIFO.

        ``elastic_pool=True`` (paged only) arms the elastic block
        pool: the engine probes free HBM for a grow ceiling at build
        and ``maybe_autoresize``/``resize_pool`` then move ``n_blocks``
        in block-granular steps at the eviction boundary
        (docs/serving_memory.md 'Disaggregation & elastic pools').

        ``kv_host_store_bytes`` (paged only, no draft) arms the tiered
        KV memory: evicted prefix chains spill to a bounded host-RAM
        store and re-admit at admission via a host->HBM copy instead
        of a re-prefill; ``prefix_directory`` (a shared
        ``serving.kv_store.PrefixDirectory``) plus ``replica_id``
        additionally publish this engine's prefix residency fleet-wide
        for locality-aware routing (docs/serving_memory.md
        'Tiered KV memory').

        ``flight`` / ``flight_capacity`` configure the engine's
        always-on per-tick flight recorder (serving/flight.py;
        ``flight_capacity=0`` disables, a shared
        ``flight.FlightRecorder`` can be passed in so the serving
        layer can bundle it — docs/debugging.md)."""
        from analytics_zoo_tpu.serving.continuous import ContinuousEngine

        if getattr(self, "_gen_max_new_tokens", None) is None:
            raise ValueError("continuous batching needs a model loaded "
                             "via load_flax_generator")
        variables = self._variables
        if self._dequant is not None:
            variables = jax.device_put(self._dequant(variables))
        spec = {}
        if getattr(self, "_spec_draft", False):
            # a draft-loaded handle builds a SPECULATIVE engine: the
            # spec-tightened prompt buckets stored at load (k+1 slack,
            # both position tables) are exactly the engine's own limit
            spec = dict(draft_model=self._spec_draft_model,
                        draft_variables=self._spec_draft_variables,
                        speculation_k=(self._spec_k
                                       if speculation_k is None
                                       else int(speculation_k)))
        elif speculation_k is not None:
            raise ValueError(
                "speculation_k needs a draft model: load one via "
                "load_flax_generator(draft_model=..., "
                "draft_variables=...)")
        return ContinuousEngine(
            self.model, variables,
            max_new_tokens=self._gen_max_new_tokens,
            max_slots=max_slots,
            prompt_buckets=self._gen_prompt_buckets,
            eos_id=eos_id, pad_id=self.prompt_pad_id,
            ticks_per_step=ticks_per_step, cache_dtype=cache_dtype,
            kernel=kernel, kv_dtype=kv_dtype,
            mesh=mesh, partition_rules=partition_rules,
            paged=paged, block_size=block_size, n_blocks=n_blocks,
            hbm_fraction=hbm_fraction,
            enable_prefix_cache=enable_prefix_cache,
            chunked=chunked, tick_token_budget=tick_token_budget,
            elastic_pool=elastic_pool,
            kv_host_store_bytes=kv_host_store_bytes,
            prefix_directory=prefix_directory, replica_id=replica_id,
            fault_injector=fault_injector,
            telemetry=telemetry,
            qos=qos, flight=flight, flight_capacity=flight_capacity,
            **spec)

    def load_openvino(self, xml_path: str, bin_path: str = None,
                      quantize: Optional[str] = None) -> "InferenceModel":
        """ref-parity: InferenceModel.loadOpenVINO — an OpenVINO IR
        (.xml + .bin) served on TPU via the net/openvino_ir.py
        translator; ``quantize="int8"`` covers the IR int8-calibration
        role (weight-only, no calibration set needed)."""
        from analytics_zoo_tpu.net.openvino_ir import OpenVINONet

        net = OpenVINONet.from_ir(xml_path, bin_path)
        return self.load_flax(net, net.init(None), quantize=quantize)

    def load_tf(self, path_or_fn, signature: str = "serving_default",
                quantize: Optional[str] = None) -> "InferenceModel":
        """ref-parity: InferenceModel.loadTF — a SavedModel dir (local or
        remote gs://, s3://, hdfs://; TF's filesystem layer resolves it),
        keras file, or concrete tf.function served on TPU via the TFNet
        translator."""
        from analytics_zoo_tpu.net import Net

        net = Net.load_tf(path_or_fn, signature=signature)
        return self.load_flax(net, net.init(None), quantize=quantize)

    def load_torch(self, module) -> "InferenceModel":
        """ref-parity: InferenceModel.loadTorch — a torch nn.Module (or
        path torch.load can read) served on TPU via TorchNet conversion."""
        from analytics_zoo_tpu.net import Net, TorchNet

        net = module if isinstance(module, TorchNet) \
            else Net.load_torch(module)
        return self.load_flax(net, net.init(None))

    def load(self, path: str, model) -> "InferenceModel":
        """Restore an ``Estimator.save`` export for `model` (flax module).

        The orbax payload is {'params': ..., optional 'batch_stats': ...}
        (see learn/estimator.py save()).
        """
        import os

        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        restored = ckptr.restore(os.path.abspath(path))
        return self.load_flax(model, restored)

    # ---- predict -----------------------------------------------------

    def _compiled(self) -> Callable:
        # one jit wrapper; jax's own per-shape trace cache (driven by the
        # bucket padding in predict) bounds compilations.  Host-loop
        # apply_fns (speculative decoding) jit their own inner rounds
        # and must not be wrapped again.
        if not getattr(self, "_jit_outer", True):
            return self._apply_fn
        with self._compile_lock:
            if self._jit is None:
                self._jit = jax.jit(self._apply_fn)
            return self._jit

    def predict(self, *inputs: np.ndarray) -> np.ndarray:
        """Batched forward; inputs are [N, ...] host arrays. N is padded
        up to the next bucket so compiled-shape count stays bounded."""
        return self.predict_async(*inputs)()

    def predict_async(self, *inputs: np.ndarray) -> Callable[[], np.ndarray]:
        """Dispatch the forward WITHOUT blocking on the device.

        Returns a zero-arg callable that blocks until the result is ready
        and yields the numpy output.  XLA dispatch is asynchronous, so the
        host can batch/decode the next request while this one computes —
        the serving loop's pipelining hook."""
        if self._apply_fn is None:
            raise RuntimeError("load a model first")
        if self._pre_pad is not None:
            inputs = self._pre_pad(inputs)
        n = len(inputs[0])
        bucket = _next_bucket(n, self._buckets)
        if n > bucket:          # n above the largest bucket: chunk
            # serial chunking keeps device memory bounded to ONE chunk in
            # flight (dispatch-all would stage the entire input in HBM)
            return lambda: self._predict_chunked(inputs, bucket)
        padded = []
        for a in inputs:
            a = np.asarray(a)
            if len(a) < bucket:
                pad = np.zeros((bucket - len(a),) + a.shape[1:], a.dtype)
                a = np.concatenate([a, pad])
            padded.append(a)
        with self._sem:
            out = self._compiled()(
                self._variables, *padded)
        # start the D2H transfer now, so the fetch overlaps the next
        # batch's compute instead of following it
        jax.tree.map(lambda x: x.copy_to_host_async(), out)
        return lambda: jax.tree.map(lambda x: np.asarray(x)[:n], out)

    def _predict_chunked(self, inputs, bucket: int):
        n = len(inputs[0])
        outs = []
        for lo in range(0, n, bucket):
            outs.append(self.predict(*[np.asarray(a)[lo:lo + bucket]
                                       for a in inputs]))
        return jax.tree.map(lambda *xs: np.concatenate(xs), *outs)

    def set_concurrency(self, n: int) -> "InferenceModel":
        """Resize the host-staging semaphore (ServingConfig.core_number)."""
        self._sem = threading.Semaphore(max(1, n))
        return self
