"""Estimator — unified fit/evaluate/predict on the TPU mesh.

Reference surface (SURVEY.md §2.3): ``zoo.orca.learn.*.Estimator`` —
``from_keras`` / ``from_torch`` / ``from_graph`` / ``from_bigdl`` backends,
each a different distributed runtime (BigDL DistriOptimizer over Spark
BlockManager, Ray actors + gloo DDP, MultiWorkerMirroredStrategy, horovod).

TPU-native re-design: **one** runtime. The entire DistriOptimizer /
AllReduceParameter machinery (ref: pipeline/estimator/Estimator.scala and
BigDL's block-partitioned all-reduce) collapses into a single pjit-compiled
``train_step`` whose gradient synchronisation is the XLA-emitted
reduce-scatter/all-gather over ICI implied by the state/data shardings.
There are no runners, no actors, no parameter blocks: the mesh IS the
cluster and the compiled step IS the optimizer loop body.

``Estimator.from_flax`` is the native constructor; ``from_keras`` /
``from_torch`` names are kept as shims that accept creator functions
returning flax modules (SURVEY's creator-fn contract), so reference users
find the entry points they know.
"""

from __future__ import annotations

import inspect
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.common.config import TrainConfig
from analytics_zoo_tpu.common.context import (
    OrcaContext, effective_process_count as _nhosts,
    effective_process_index as _hidx)
from analytics_zoo_tpu.common.log import MetricLogger, logger
from analytics_zoo_tpu.data.loader import (
    DataCreator, NumpyBatchIterator, device_prefetch, make_global_batch)
from analytics_zoo_tpu.learn.metrics import (
    EpochAccumulator, resolve_metrics)
from analytics_zoo_tpu.learn.objectives import get_loss
from analytics_zoo_tpu.learn.train_state import ZooTrainState, create_train_state
from analytics_zoo_tpu.learn.triggers import EveryEpoch, Trigger
from analytics_zoo_tpu.parallel.mesh import batch_axes, make_mesh
from analytics_zoo_tpu.parallel.partition import (
    DP_RULES, PartitionRules, data_process_groups, data_sharding,
    state_sharding, with_sharding_constraint)
from jax.sharding import PartitionSpec as P


def _cpu_sync_every(mesh) -> int:
    """Dispatch-drift barrier interval for MULTI-device XLA:CPU meshes
    (0 = no barrier).  XLA:CPU's in-process collectives kill the process
    when one participant misses a 40 s rendezvous window; with many
    virtual devices on few host cores, a long unsynchronised dispatch
    queue lets per-device execution drift that far (observed ~30 async
    steps on an 8-device mesh on a 1-core host).  Single-device runs
    have no rendezvous, and TPU runs must not pay a mid-epoch D2H
    round-trip — both stay barrier-free."""
    if jax.default_backend() != "cpu":
        return 0
    return 8 if mesh.devices.size > 1 else 0


def _model_accepts(model, kwarg: str) -> bool:
    try:
        sig = inspect.signature(type(model).__call__)
    except (TypeError, ValueError):
        return False
    return kwarg in sig.parameters


class FlaxEstimator:
    """Train/eval/predict a flax module on the mesh.

    Args:
      model: flax ``nn.Module``.
      loss: name or callable ``(preds, labels) -> scalar``.
      optimizer: optax transform (or learning-rate float -> adam(lr)).
      metrics: names or callables evaluated on (preds, labels).
      feature_cols / label_cols: which batch keys feed the model / loss.
        Features are passed positionally in order.
      partition_rules: param-path regex -> PartitionSpec (default: DP).
      mesh: defaults to the active context's mesh (or a fresh dp mesh).
    """

    def __init__(
        self,
        model,
        loss: Union[str, Callable],
        optimizer,
        *,
        metrics: Sequence[Union[str, Callable]] = (),
        feature_cols: Sequence[str] = ("x",),
        label_cols: Sequence[str] = ("y",),
        partition_rules: PartitionRules = DP_RULES,
        mesh=None,
        config: Optional[TrainConfig] = None,
        model_dir: Optional[str] = None,
        param_loss: Optional[Callable] = None,
        lora=None,
        initial_variables=None,
    ):
        self.model = self._maybe_convert_torch(model)
        # Optional penalty over the param tree (keras-API W_regularizer
        # lowering) added to the training loss inside the jitted step.
        self.param_loss = param_loss
        self.loss_fn = get_loss(loss)
        if isinstance(optimizer, (int, float)):
            optimizer = optax.adam(float(optimizer))
        # LoRA (learn/lora.py): adapters join the params tree under
        # __lora__, the optimizer is masked to them, and _forward merges
        # W + scale·A@B before apply — one transform, every model.
        # pretrained weights to seed instead of random init (HF imports,
        # Estimator.save exports): a {'params': ...} tree or bare params
        self._initial_variables = initial_variables
        self.lora = lora
        if lora is not None:
            from analytics_zoo_tpu.learn.lora import wrap_optimizer

            optimizer = wrap_optimizer(optimizer, True)
        self.tx = optimizer
        self.metric_fns = resolve_metrics(metrics)
        self.feature_cols = tuple(feature_cols)
        self.label_cols = tuple(label_cols)
        if lora is not None:
            from analytics_zoo_tpu.learn.lora import LORA_RULES

            partition_rules = tuple(LORA_RULES) + tuple(partition_rules)
        self.rules = partition_rules
        self.config = config or TrainConfig()
        self.model_dir = model_dir
        if mesh is None:
            try:
                mesh = OrcaContext.get_context().mesh
            except RuntimeError:
                mesh = make_mesh(axes={"dp": -1})
        self.mesh = mesh
        self.state: Optional[ZooTrainState] = None
        self._state_sharding = None
        self._data_sharding = data_sharding(self.mesh)
        # (n_groups, my_group, group_of_process): how the process boundary
        # lies relative to the batch axes.  dp across hosts -> one data
        # shard per process; a pp/ep/tp-only boundary -> processes are
        # batch REPLICAS and must feed identical rows (see
        # parallel.partition.data_process_groups).
        self._data_groups = data_process_groups(self._data_sharding)
        self._takes_train = _model_accepts(model, "train")
        self._takes_det = _model_accepts(model, "deterministic")
        self._jit_train_step = None
        self._jit_eval_step = None
        self._jit_predict_step = None
        self._epoch = 0
        self._global_step = 0
        self._prof_active = False

    @staticmethod
    def _maybe_convert_torch(model):
        """torch nn.Modules become TorchNets HERE — the common depth — so
        every entry point (from_flax/from_torch/AutoEstimator trials) gets
        conversion, not just the from_torch facade."""
        try:
            import torch
        except ImportError:
            return model
        if isinstance(model, torch.nn.Module):
            from analytics_zoo_tpu.net import TorchNet

            return TorchNet.from_torch(model)
        return model

    # ------------------------------------------------------------------
    # model application helpers
    # ------------------------------------------------------------------

    def _apply_kwargs(self, train: bool) -> Dict[str, Any]:
        kw: Dict[str, Any] = {}
        if self._takes_train:
            kw["train"] = train
        elif self._takes_det:
            kw["deterministic"] = not train
        return kw

    def _forward(self, params, batch_stats, batch, rng, train: bool):
        """Returns (preds, new_batch_stats, aux_loss).

        ``aux_loss`` is the sum of everything modules sowed into the
        ``"losses"`` collection during a TRAIN forward (MoE load-balancing
        losses, models/moe.py; any custom regulariser a user sows) — added
        to the training loss by _train_step.  Eval applies run without
        mutable collections, so sown losses drop out there (eval loss stays
        comparable across MoE/dense models)."""
        if self.lora is not None:
            # gradients flow to the adapters THROUGH this merge; the
            # base kernels' grads are computed too but the masked
            # optimizer discards them (learn/lora.py)
            from analytics_zoo_tpu.learn.lora import merge_lora

            params = merge_lora(params, self.lora)
        variables = {"params": params}
        has_bs = batch_stats is not None
        if has_bs:
            variables["batch_stats"] = batch_stats
        feats = [batch[c] for c in self.feature_cols]
        kw = self._apply_kwargs(train)
        rngs = {"dropout": rng} if (train and rng is not None) else None
        if train:
            out, mut = self.model.apply(
                variables, *feats, mutable=["batch_stats", "losses"],
                rngs=rngs, **kw)
            leaves = jax.tree.leaves(mut.get("losses", {}))
            # whether the model sows aux losses is STATIC (trace-time):
            # models without them never pay a metrics entry
            self._has_aux_losses = bool(leaves)
            aux = sum((jnp.sum(leaf) for leaf in leaves),
                      jnp.float32(0.0))
            new_bs = mut["batch_stats"] if has_bs else None
            return out, new_bs, aux
        out = self.model.apply(variables, *feats, rngs=rngs, **kw)
        return out, batch_stats, jnp.float32(0.0)

    def _labels(self, batch):
        ys = [batch[c] for c in self.label_cols]
        return ys[0] if len(ys) == 1 else tuple(ys)

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------

    def _train_step(self, state: ZooTrainState, batch):
        accum = int(getattr(self.config, "accum_steps", 1) or 1)
        if accum > 1:
            return self._train_step_accum(state, batch, accum)
        rng = state.step_rng()

        def loss_of(params):
            preds, new_bs, aux = self._forward(
                params, state.batch_stats, batch, rng, train=True)
            loss = self.loss_fn(preds, self._labels(batch)) + aux
            if self.param_loss is not None:
                loss = loss + self.param_loss(params)
            return loss, (preds, new_bs, aux)

        (loss, (preds, new_bs, aux)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params)
        new_state = state.apply_gradients(grads=grads, batch_stats=new_bs)
        mets = {"loss": loss}
        if getattr(self, "_has_aux_losses", False):
            # observability: the sown component (MoE load balance etc.)
            # reported beside the total it is already inside of
            mets["aux_loss"] = aux
        labels = self._labels(batch)
        for name, fn in self.metric_fns:
            mets[name] = fn(preds, labels)
        return new_state, mets

    def _train_step_accum(self, state: ZooTrainState, batch, accum: int):
        """Gradient accumulation: the global batch is split into `accum`
        microbatches scanned sequentially; averaged grads feed ONE optimizer
        update, so the math equals the full-batch step (for mean-reduced
        losses) at 1/accum the activation memory.  The reference has no
        counterpart (its effective batch scaled with executor count,
        SURVEY.md §2.3); on TPU this is how a big global batch fits HBM —
        remat trades FLOPs for memory, accumulation trades steps for it."""
        rng = state.step_rng()
        baxes = batch_axes(self.mesh) or None

        def split(v):
            b = v.shape[0]
            if b % accum:
                raise ValueError(
                    f"global batch {b} not divisible by "
                    f"accum_steps={accum}")
            mb = v.reshape((accum, b // accum) + v.shape[1:])
            # keep microbatch rows sharded over the dp-like axes
            return with_sharding_constraint(mb, P(None, baxes))

        mbs = {k: split(v) for k, v in batch.items()}

        def loss_of(params, mb, bs, r):
            preds, new_bs, aux = self._forward(params, bs, mb, r,
                                               train=True)
            loss = self.loss_fn(preds, self._labels(mb)) + aux
            if self.param_loss is not None:
                loss = loss + self.param_loss(params)
            return loss, (preds, new_bs, aux)

        def body(carry, xs):
            g_acc, loss_acc, aux_acc, bs = carry
            mb, i = xs
            (loss, (preds, new_bs, aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(
                state.params, mb, bs, jax.random.fold_in(rng, i))
            g_acc = jax.tree.map(jnp.add, g_acc, grads)
            return (g_acc, loss_acc + loss, aux_acc + aux, new_bs), preds

        zeros = jax.tree.map(jnp.zeros_like, state.params)
        (g_acc, loss_sum, aux_sum, bs_final), preds = jax.lax.scan(
            body, (zeros, jnp.float32(0.0), jnp.float32(0.0),
                   state.batch_stats),
            (mbs, jnp.arange(accum)))
        grads = jax.tree.map(lambda g: g / accum, g_acc)
        new_state = state.apply_gradients(grads=grads,
                                          batch_stats=bs_final)
        # models may return pytree predictions (e.g. SSD's (locs, cls))
        preds = jax.tree.map(
            lambda p: p.reshape((-1,) + p.shape[2:]), preds)
        mets = {"loss": loss_sum / accum}
        if getattr(self, "_has_aux_losses", False):
            mets["aux_loss"] = aux_sum / accum
        labels = self._labels(batch)
        for name, fn in self.metric_fns:
            mets[name] = fn(preds, labels)
        return new_state, mets

    def _eval_step(self, state: ZooTrainState, batch, weights):
        """Masked eval: per-sample losses/metrics via singleton-batch vmap,
        weighted by `weights` (0 for padding rows)."""
        preds, _, _ = self._forward(
            state.params, state.batch_stats, batch, None, train=False)
        labels = self._labels(batch)

        def per_sample(fn):
            def one(p, l):
                if isinstance(l, tuple):
                    return fn(p[None], tuple(x[None] for x in l))
                return fn(p[None], l[None])
            return jax.vmap(one)

        w = weights.astype(jnp.float32)
        denom = jnp.maximum(w.sum(), 1.0)
        loss = (per_sample(self.loss_fn)(preds, labels) * w).sum() / denom
        if self.param_loss is not None:
            # keep eval loss comparable to the training loss (keras includes
            # regularization penalties in evaluate)
            loss = loss + self.param_loss(state.params)
        mets = {"loss": loss}
        for name, fn in self.metric_fns:
            mets[name] = (per_sample(fn)(preds, labels) * w).sum() / denom
        return mets

    def _predict_step(self, state: ZooTrainState, batch):
        preds, _, _ = self._forward(
            state.params, state.batch_stats, batch, None, train=False)
        return preds

    def _set_cols(self, feature_cols, label_cols):
        """Column changes must invalidate compiled steps: the traces close
        over the column names, and jax's cache would otherwise silently hit
        on an old trace reading the old columns."""
        fc = tuple(feature_cols) if feature_cols else self.feature_cols
        lc = tuple(label_cols) if label_cols else self.label_cols
        if (fc, lc) != (self.feature_cols, self.label_cols):
            self.feature_cols, self.label_cols = fc, lc
            self._jit_train_step = None
            self._jit_eval_step = None
            self._jit_predict_step = None

    def _build_jits(self):
        # accum_steps is baked into the train-step trace: a config change
        # after the first fit must invalidate the cached jit (same
        # requirement _set_cols documents for column names)
        accum = int(getattr(self.config, "accum_steps", 1) or 1)
        if self._jit_train_step is not None and \
                getattr(self, "_jit_accum", accum) != accum:
            self._jit_train_step = None   # eval/predict don't see accum
        if self._jit_train_step is None:
            donate = self.config.donate_state and not self.config.debug_nans
            self._jit_train_step = jax.jit(
                self._train_step,
                donate_argnums=(0,) if donate else (),
                out_shardings=(self._state_sharding, None))
            self._jit_accum = accum
        if self._jit_eval_step is None:
            self._jit_eval_step = jax.jit(self._eval_step)
            self._jit_predict_step = jax.jit(self._predict_step)

    # ------------------------------------------------------------------
    # state init
    # ------------------------------------------------------------------

    def _ensure_state(self, sample_batch: Dict[str, np.ndarray]):
        if self.state is not None:
            return
        seed = self.config.seed
        # Init batch must divide the mesh's batch axes (shard_map paths are
        # strict about divisibility), so tile the sample up to one row per
        # batch-mesh slice instead of using a single row.
        from analytics_zoo_tpu.parallel.mesh import mesh_batch_size

        nb = max(1, mesh_batch_size(self.mesh))

        def rows(c):
            v = np.asarray(sample_batch[c])
            if len(v) >= nb:
                return v[:nb]
            reps = -(-nb // max(1, len(v)))
            return np.tile(v, (reps,) + (1,) * (v.ndim - 1))[:nb]

        feats = [jnp.asarray(rows(c)) for c in self.feature_cols]
        # Per-column (row_shape, dtype) — lets save() persist enough to
        # rebuild state on load without the caller resupplying sample data.
        self.sample_spec = {
            c: (tuple(np.asarray(sample_batch[c]).shape[1:]),
                str(np.asarray(sample_batch[c]).dtype))
            for c in sample_batch}
        kw = self._apply_kwargs(train=False)

        def init_fn():
            # RNG keys are created INSIDE the traced function: a key built
            # eagerly and closed over would be embedded as a program
            # constant, and materialising that constant does a hidden
            # device->host fetch at lowering time.
            root = jax.random.key(seed)
            init_rng, train_rng = jax.random.split(root)
            variables = self.model.init(
                {"params": init_rng, "dropout": init_rng}, *feats, **kw)
            if self.lora is not None:
                from analytics_zoo_tpu.learn.lora import (
                    LORA_KEY, init_lora)

                variables = dict(variables)
                variables["params"] = dict(variables["params"])
                variables["params"][LORA_KEY] = init_lora(
                    variables["params"], self.lora,
                    jax.random.fold_in(root, 2))
            return create_train_state(train_rng, self.model.apply,
                                      variables, self.tx)

        shapes = jax.eval_shape(init_fn)
        self._state_sharding = state_sharding(self.mesh, shapes, self.rules)
        if self._initial_variables is not None:
            self.state = self._build_seeded_state(shapes, seed)
        else:
            self.state = jax.jit(
                init_fn, out_shardings=self._state_sharding)()
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(self.state.params))
        logger.info("initialised %s params=%s mesh=%s",
                    type(self.model).__name__, f"{n_params:,}",
                    dict(self.mesh.shape))

    def _build_seeded_state(self, shapes, seed):
        """Build the train state DIRECTLY from caller-provided weights
        (initial_variables) — the random base init is never materialised
        (a full throwaway tree would double peak HBM at exactly the
        large-checkpoint imports this serves).  Each leaf lands with the
        state's dtype and sharding; shape mismatches fail loud naming
        the problem.  With LoRA the seeded tree is the FROZEN BASE and
        adapters get their usual fresh init (same seed-derived values as
        the unseeded path).  A source tree saved from a LoRA run may
        carry a ``__lora__`` subtree — it is DROPPED (seed
        ``merged_params()`` instead to bake adapters in)."""
        from analytics_zoo_tpu.learn.lora import LORA_KEY, init_lora

        src = self._initial_variables
        src_extra = {}
        if isinstance(src, dict) and "params" in src:
            src_extra = {k: v for k, v in src.items() if k != "params"}
            src = src["params"]
        if isinstance(src, dict) and LORA_KEY in src:
            src = {k: v for k, v in src.items() if k != LORA_KEY}

        dst_params = shapes.params
        if self.lora is not None:
            dst_params = {k: v for k, v in dst_params.items()
                          if k != LORA_KEY}
        shapes_dst = jax.tree.map(lambda x: tuple(x.shape), dst_params)
        shapes_src = jax.tree.map(lambda x: tuple(np.asarray(x).shape),
                                  src)
        if shapes_dst != shapes_src:
            raise ValueError(
                "initial_variables do not match the model's param "
                "shapes — wrong checkpoint for this architecture?")
        # batch-stats models (BatchNorm): fresh running statistics under
        # pretrained weights silently corrupt inference — require them
        if shapes.batch_stats is not None and "batch_stats" not in \
                src_extra:
            raise ValueError(
                "this model carries batch_stats (BatchNorm running "
                "statistics); initial_variables must include them "
                "(pass the full saved variables, not just params) — "
                "fresh statistics under pretrained weights would "
                "silently corrupt inference")

        pspec = self._state_sharding.params
        base_spec = ({k: v for k, v in pspec.items() if k != LORA_KEY}
                     if self.lora is not None else pspec)
        params_dev = jax.tree.map(
            lambda dst, sh, s: jax.device_put(
                np.asarray(s).astype(dst.dtype), sh),
            dst_params, base_spec, src)
        bs_dev = None
        if shapes.batch_stats is not None:
            bs_dev = jax.tree.map(
                lambda dst, sh, s: jax.device_put(
                    np.asarray(s).astype(dst.dtype), sh),
                shapes.batch_stats, self._state_sharding.batch_stats,
                src_extra["batch_stats"])

        lora_cfg = self.lora

        def assemble(params, batch_stats):
            root = jax.random.key(seed)
            _, train_rng = jax.random.split(root)
            if lora_cfg is not None:
                params = {**params,
                          LORA_KEY: init_lora(params, lora_cfg,
                                              jax.random.fold_in(root,
                                                                 2))}
            variables = {"params": params}
            if batch_stats is not None:
                variables["batch_stats"] = batch_stats
            return create_train_state(train_rng, self.model.apply,
                                      variables, self.tx)

        return jax.jit(assemble,
                       out_shardings=self._state_sharding,
                       static_argnames=())(params_dev, bs_dev)

    # ------------------------------------------------------------------
    # observability (SURVEY §5; ref: KerasNet.set_tensorboard ->
    # BigDL TrainSummary under log_dir/app_name)
    # ------------------------------------------------------------------

    def set_tensorboard(self, log_dir: str, app_name: str = "zoo"):
        import os

        self.config.tensorboard_dir = os.path.join(log_dir, app_name,
                                                   "train")
        self.config.metrics_jsonl = os.path.join(log_dir, app_name,
                                                 "train.jsonl")
        os.makedirs(self.config.tensorboard_dir, exist_ok=True)
        return self

    def set_profile(self, logdir: str, start_step: int = 5,
                    n_steps: int = 5):
        """Capture a jax.profiler trace for `n_steps` once training reaches
        `start_step` (skips compile/warmup noise)."""
        self.config.profile = (logdir, start_step, n_steps)
        return self

    # ------------------------------------------------------------------
    # public API (reference parity: fit/evaluate/predict/save/load)
    # ------------------------------------------------------------------

    def fit(
        self,
        data,
        epochs: int = 1,
        batch_size: Optional[int] = None,
        validation_data=None,
        feature_cols: Optional[Sequence[str]] = None,
        label_cols: Optional[Sequence[str]] = None,
        checkpoint_trigger: Optional[Trigger] = None,
        callbacks: Sequence[Callable[[Dict], None]] = (),
        auto_resume: bool = False,
    ) -> List[Dict[str, float]]:
        """Train. `batch_size` is GLOBAL (reference semantics: total across
        the cluster); when omitted it falls back to the data container's
        own batch_size (TFDataset carries one) and then 32. Returns
        per-epoch stats dicts (reference: Orca runner stats lists).

        ``auto_resume=True`` makes the call restart-idempotent (SURVEY §5
        elastic recovery; pairs with scripts/run_elastic.py): if
        ``config.checkpoint_dir`` holds a checkpoint, restore it and
        train only the REMAINING epochs toward the ``epochs`` total —
        a respawned process group continues where the dead one stopped,
        with no resume logic in user code."""
        batch_size = _resolve_batch(batch_size, data, "batch_size")
        if validation_data is None:
            validation_data = getattr(data, "val", None)
        self._set_cols(feature_cols, label_cols)
        n_hosts = _nhosts()
        n_groups, my_group, _ = self._data_groups
        if batch_size < 1 or batch_size % n_groups:
            raise ValueError(f"global batch {batch_size} must be positive "
                             f"and divisible by data-shard group count "
                             f"{n_groups}")
        # rows each PROCESS contributes per step: one data shard per
        # GROUP; group-mates (processes replicated along the batch dim,
        # e.g. across a pp boundary) feed identical rows
        per_host = batch_size // n_groups
        shuffle = not self.config.deterministic
        from analytics_zoo_tpu.data.feature_set import DiskFeatureSet
        is_disk = isinstance(data, DiskFeatureSet)
        self._check_host_local_source(data)
        if is_disk:
            # DISK tier streams through the native prefetch thread.  Each
            # host streams its OWN shard file (host-local data, like
            # XShards).
            n_local = len(data)
        else:
            arrays = _host_local(data, self._data_groups)
            n_local = len(next(iter(arrays.values())))
        min_steps = None
        if n_hosts > 1:
            # Host-local sources (disk shards, XShards) may hold uneven row
            # counts; every host must run the SAME step count or the
            # collective program deadlocks.  One allgather of the row count
            # settles the global minimum — and must happen BEFORE any
            # per-host record access or iterator validation (sample_block
            # on an empty shard, batch-size checks) so a too-small host
            # raises the same error everywhere instead of deadlocking its
            # peers inside a collective.
            fp = data.fingerprint() if is_disk else 0
            gathered = _allgather_counts(n_local, fp)
            min_rows = int(gathered[:, 0].min())
            pairs = [tuple(r) for r in gathered.tolist() if r[0] > 0]
            if is_disk and not _allow_shared_disk() and \
                    len(set(pairs)) < len(pairs):
                raise ValueError(
                    "two or more hosts opened an identical DiskFeatureSet "
                    "shard (same row count and content fingerprint) — that "
                    "is ONE replicated/shared file, which would train its "
                    "rows once per host.  Spill per-host shards (use a "
                    "'{host}' placeholder in the path); if these really "
                    "are distinct shards, set "
                    "ANALYTICS_ZOO_TPU_ALLOW_SHARED_DISK=1")
            min_steps = min_rows // per_host
            if min_steps < 1:
                raise ValueError(
                    f"global batch {batch_size} needs {per_host} rows per "
                    f"host but the smallest host shard holds only "
                    f"{min_rows} rows")
        if is_disk:
            self._ensure_state(data.sample_block())
            it = data.batch_iterator(
                per_host, shuffle=shuffle,
                seed=self.config.seed + my_group)
        else:
            self._ensure_state(arrays)
            it = NumpyBatchIterator(
                arrays, per_host, shuffle=shuffle, drop_remainder=True,
                seed=self.config.seed + my_group)
        if min_steps is not None and min_steps < it.steps_per_epoch():
            it = _StepLimitIterator(it, min_steps)
        self._build_jits()
        if auto_resume:
            if not self.config.checkpoint_dir:
                raise ValueError(
                    "fit(auto_resume=True) needs config.checkpoint_dir — "
                    "there is nowhere to resume from")
            mgr = self._checkpoint_manager(self.config.checkpoint_dir)
            latest = mgr.latest_step()
            if n_hosts > 1:
                # hosts must AGREE on the resume point before any of them
                # commits to an epoch count (mismatched counts deadlock
                # the collective program — same reason fit allgathers row
                # counts).  Disagreement means checkpoint_dir is not the
                # shared storage the contract requires (e.g. a replaced
                # VM with an empty local disk): fail the same way on
                # every host.
                seen = _allgather_counts(
                    -1 if latest is None else int(latest))[:, 0]
                if len(set(seen.tolist())) > 1:
                    raise ValueError(
                        f"auto_resume: hosts see different latest "
                        f"checkpoints {seen.tolist()} under "
                        f"{self.config.checkpoint_dir!r} — the dir must "
                        f"be shared storage (gs://...) visible to every "
                        f"host")
            if latest is not None:
                self.load_checkpoint(self.config.checkpoint_dir)
                logger.info(
                    "auto-resume: restored step %d (epoch %d) from %s",
                    self._global_step, self._epoch,
                    self.config.checkpoint_dir)
                if self._global_step % max(1, it.steps_per_epoch()):
                    logger.warning(
                        "auto-resume: restored step %d is mid-epoch "
                        "(steps_per_epoch=%d); resume is EPOCH-"
                        "granular, so the partial epoch's leading "
                        "batches will be trained again — use an epoch-"
                        "boundary checkpoint_trigger (EveryEpoch) when "
                        "exact-once matters", self._global_step,
                        it.steps_per_epoch())
            if self._epoch >= epochs:
                logger.info("auto-resume: %d epochs already complete",
                            self._epoch)
                return []
            epochs = epochs - self._epoch
            # continue the shuffle-seed schedule where the dead
            # incarnation stopped (deterministic mode is unaffected)
            inner = getattr(it, "_it", it)
            if hasattr(inner, "epoch"):
                inner.epoch = self._epoch
        # NOTE: _global_step is tracked host-side (incremented per step,
        # synced from device only on checkpoint restore).  Reading
        # int(self.state.step) here would be a blocking D2H fetch on
        # every fit() entry, for a number the host already has.
        trigger = checkpoint_trigger or (
            EveryEpoch() if self.config.checkpoint_dir else None)
        mlog = MetricLogger(jsonl_path=self.config.metrics_jsonl,
                            tensorboard_dir=self.config.tensorboard_dir,
                            log_every=self.config.log_every_steps)
        prof = self.config.profile      # (logdir, start_step, n_steps)
        prof_active = False
        history: List[Dict[str, float]] = []
        for cb in callbacks:
            # stateful stop-requesting callbacks (EarlyStopping) restart
            # fresh per fit; ordinary callbacks are never touched (same
            # opt-in principle as requests_stop)
            if getattr(cb, "requests_stop", False):
                getattr(cb, "reset", lambda: None)()
        log_every = max(1, self.config.log_every_steps)
        debug_nans_was = None
        if self.config.debug_nans:
            debug_nans_was = jax.config.jax_debug_nans
            jax.config.update("jax_debug_nans", True)
        try:
            return self._fit_epochs(
                epochs, it, batch_size, validation_data, trigger, mlog,
                prof, history, log_every, callbacks)
        finally:
            # fault injection / data errors must not leak an active trace
            # (next start_trace would fail) or an open jsonl handle
            if self._prof_active:
                jax.profiler.stop_trace()
                self._prof_active = False
            if debug_nans_was is not None:
                jax.config.update("jax_debug_nans", debug_nans_was)
            mlog.close()

    def _fit_epochs(self, epochs, it, batch_size, validation_data, trigger,
                    mlog, prof, history, log_every, callbacks):
        prof_active = False
        sync_every = _cpu_sync_every(self.mesh)
        for _ in range(epochs):
            t0 = time.perf_counter()
            n_steps = 0
            step_mets: List[Dict[str, jax.Array]] = []
            for gbatch in device_prefetch(
                    it.epoch_batches(), self.mesh,
                    sharding=self._data_sharding,
                    pack=bool(getattr(self.config, "pack_transfer", True))):
                # Hot loop: never block on device values here — metrics stay
                # on-device (async dispatch continues); host sync happens
                # only at log points and epoch end.
                if prof and not prof_active and \
                        self._global_step >= prof[1]:
                    jax.profiler.start_trace(prof[0])
                    prof_active = self._prof_active = True
                self.state, mets = self._jit_train_step(self.state, gbatch)
                step_mets.append(mets)
                n_steps += 1
                self._global_step += 1
                if sync_every and n_steps % sync_every == 0:
                    jax.block_until_ready(mets["loss"])
                if prof_active and self._global_step >= prof[1] + prof[2]:
                    jax.block_until_ready(mets["loss"])
                    jax.profiler.stop_trace()
                    prof_active = self._prof_active = False
                    prof = None
                if self.config.fault_inject_step and \
                        self._global_step == self.config.fault_inject_step:
                    raise RuntimeError(
                        f"injected fault at step {self._global_step} "
                        "(TrainConfig.fault_inject_step)")
                if n_steps % log_every == 0:
                    # one batched D2H for the whole metric dict — per-leaf
                    # np.asarray pays a device round-trip per metric
                    mlog.log(self._global_step, jax.device_get(mets),
                             n_samples=batch_size * log_every)
                if trigger and trigger({"step": self._global_step,
                                        "epoch": self._epoch}):
                    self._maybe_checkpoint()
            # Epoch barrier: stack every step's metrics on-device into ONE
            # array per metric and fetch those.  The fetch IS the barrier
            # (the values exist only once the epoch's last step has run,
            # so `dt` never credits compute still in the device queue),
            # and it must be O(metrics) transfers, not O(steps x metrics)
            # — device_get on a list of per-step dicts pays a device
            # round-trip per leaf.
            acc = EpochAccumulator()
            if step_mets:
                fetched = _fetch_stacked(step_mets)
                dt = time.perf_counter() - t0
                for i in range(n_steps):
                    acc.add({k: float(v[i]) for k, v in fetched.items()},
                            batch_size)
            else:
                dt = time.perf_counter() - t0
            self._epoch += 1
            stats = acc.result()
            stats["num_samples"] = float(n_steps * batch_size)
            stats["samples_per_sec"] = (n_steps * batch_size) / dt if dt else 0
            if validation_data is not None:
                val = self.evaluate(validation_data, batch_size=batch_size)
                stats.update({f"val_{k}": v for k, v in val.items()})
            if trigger and trigger({"step": self._global_step,
                                    "epoch": self._epoch, "epoch_end": True,
                                    "metrics": stats}):
                self._maybe_checkpoint()
            stop = False
            for cb in callbacks:
                ret = cb({"epoch": self._epoch, **stats})
                # only callbacks that OPT IN (requests_stop attr, e.g.
                # EarlyStopping) may stop training via their return value
                # — an ordinary logger returning something truthy must
                # never silently truncate a 50-epoch run
                if getattr(cb, "requests_stop", False):
                    stop = bool(ret) or stop
            logger.info("epoch %d: %s", self._epoch,
                        {k: round(v, 5) for k, v in stats.items()})
            history.append(stats)
            if _nhosts() > 1 and any(
                    getattr(cb, "requests_stop", False)
                    for cb in callbacks):
                # hosts must agree on the epoch count or the next
                # collective deadlocks: any host's stop stops everyone.
                # (Gated on a stop-capable callback existing — no
                # per-epoch barrier for ordinary multihost fits.)
                stop = bool(_allgather_counts(int(stop))[:, 0].max())
            if stop:
                logger.info("early stop at epoch %d", self._epoch)
                break
        return history

    def _check_host_local_source(self, data):
        """Host-local sources (DiskFeatureSet/XShards) hold DISJOINT rows
        per process; on a mesh whose process boundary is NOT along the
        batch axes (batch-replica groups), those rows cannot satisfy the
        required replication — raise instead of feeding inconsistent
        global arrays.  Applies to fit, evaluate and predict alike."""
        from analytics_zoo_tpu.data.feature_set import DiskFeatureSet
        from analytics_zoo_tpu.data.shards import XShards

        n_groups = self._data_groups[0]
        n_hosts = _nhosts()
        if n_groups != n_hosts and isinstance(
                data, (DiskFeatureSet, XShards)):
            raise ValueError(
                "host-local data sources (DiskFeatureSet/XShards) hold "
                "DISJOINT rows per process, but this mesh's process "
                f"boundary makes {n_hosts} processes form {n_groups} "
                "batch-replica group(s) that must feed identical rows. "
                "Feed replicated in-memory arrays, or lay the mesh out "
                "with the batch (dp/fsdp) axes across processes")

    def _local_n(self, data):
        """Host-local row count WITHOUT touching any records (safe to call
        before the multihost alignment collective even on an empty shard).
        Returns (n_local, arrays-or-None); arrays are reused downstream so
        in-memory data is normalised exactly once."""
        from analytics_zoo_tpu.data.feature_set import DiskFeatureSet

        if isinstance(data, DiskFeatureSet):
            return len(data), None
        arrays = _host_local(data, self._data_groups)
        return len(next(iter(arrays.values()))), arrays

    def _local_eval_stream(self, data, per_host, arrays=None):
        """Iterator of host-local fixed-order chunks of <= per_host rows.
        The DISK tier streams block-by-block (never materialised to DRAM —
        the whole point of the tier); everything else uses the arrays
        `_local_n` already normalised."""
        from analytics_zoo_tpu.data.feature_set import DiskFeatureSet

        if isinstance(data, DiskFeatureSet):
            return data.batches(per_host, shuffle=False,
                                drop_remainder=False)
        if arrays is None:
            arrays = _host_local(data, self._data_groups)
        n = len(next(iter(arrays.values())))

        def gen():
            for lo in range(0, n, per_host):
                yield {k: v[lo:lo + per_host] for k, v in arrays.items()}

        return gen()

    def _chunk_plan(self, n_local: int, per_host: int):
        """Multihost chunk alignment for eval/predict.

        Hosts hold uneven row counts (disk shards, XShards); each chunk is
        one collective (`make_array_from_process_local_data`), so all hosts
        must emit the SAME number of chunks.  One allgather of the row
        counts lets every host derive every other host's deterministic
        chunk sizes locally.  Returns ``(n_chunks, global_counts)`` where
        ``global_counts[j]`` is the true row total of chunk j across hosts,
        or None on a single host.
        """
        if _nhosts() == 1:
            return None
        counts = _allgather_counts(n_local)[:, 0]
        if counts.min() == 0:
            # every host raises the same error (the allgather already ran)
            # instead of a zero-row host dying early and deadlocking peers
            raise ValueError(
                f"evaluate/predict need rows on every host, but local row "
                f"counts are {counts.tolist()} (host order)")

        def sizes(n):
            s = [per_host] * (n // per_host)
            if n % per_host:
                s.append(n % per_host)
            return s

        per_host_sizes = [sizes(int(c)) for c in counts]
        n_chunks = max(len(s) for s in per_host_sizes)
        # global row totals must count each DATA-SHARD GROUP once: batch
        # replica processes (e.g. across a pp boundary) hold the same rows,
        # so sum over one representative process per group
        _, _, gop = self._data_groups
        reps = {}
        for p in range(len(per_host_sizes)):
            g = gop[p] if gop and p < len(gop) else p
            reps.setdefault(g, p)
        rep_sizes = [per_host_sizes[p] for p in sorted(reps.values())]
        gcounts = [sum(s[j] for s in rep_sizes if j < len(s))
                   for j in range(n_chunks)]
        return n_chunks, gcounts

    def _sample_of(self, data) -> Dict[str, np.ndarray]:
        from analytics_zoo_tpu.data.feature_set import DiskFeatureSet

        if isinstance(data, DiskFeatureSet):
            return data.sample_block()
        return _host_local(data, self._data_groups)

    def evaluate(self, data, batch_size: Optional[int] = None,
                 feature_cols=None, label_cols=None) -> Dict[str, float]:
        batch_size = _resolve_batch(batch_size, data, "batch_per_thread")
        self._set_cols(feature_cols, label_cols)
        per_host = max(1, batch_size // self._data_groups[0])
        self._check_host_local_source(data)
        # multihost alignment FIRST — before any record access, so a bad
        # host raises everywhere instead of deadlocking peers (see fit)
        n_local, arrays = self._local_n(data)
        plan = self._chunk_plan(n_local, per_host)
        sample = arrays if arrays is not None else self._sample_of(data)
        self._ensure_state(sample)
        self._build_jits()
        acc = EpochAccumulator()
        stream = self._local_eval_stream(data, per_host, arrays)
        mets_list, counts = [], []
        sync_every = _cpu_sync_every(self.mesh)
        for j, chunk in enumerate(
                _padded_chunks(stream, plan and plan[0], sample)):
            real = len(next(iter(chunk.values())))
            chunk, w = _pad_batch(chunk, per_host)
            gbatch = make_global_batch(self.mesh, chunk, self._data_sharding)
            gw = make_global_batch(self.mesh, {"w": w},
                                   self._data_sharding)["w"]
            # keep metrics on-device: blocking here would serialise eval
            # steps and pay a device round-trip per chunk
            mets_list.append(self._jit_eval_step(self.state, gbatch, gw))
            # ...except on the multi-device CPU mesh, where an
            # unbounded dispatch queue can breach XLA:CPU's 40 s
            # collective-rendezvous wall (_cpu_sync_every)
            if sync_every and len(mets_list) % sync_every == 0:
                jax.block_until_ready(mets_list[-1])
            # exact global row count per chunk: the zero-weight padding
            # rows never enter the metric averages
            counts.append(real if plan is None else plan[1][j])
        if mets_list:
            fetched = _fetch_stacked(mets_list)
            for i, cnt in enumerate(counts):
                acc.add({k: float(v[i]) for k, v in fetched.items()}, cnt)
        return acc.result()

    def predict(self, data, batch_size: Optional[int] = None,
                feature_cols=None) -> np.ndarray:
        batch_size = _resolve_batch(batch_size, data, "batch_per_thread")
        self._set_cols(feature_cols, None)
        per_host = max(1, batch_size // self._data_groups[0])
        self._check_host_local_source(data)
        # multihost alignment FIRST — before any record access (see fit)
        n_local, arrays = self._local_n(data)
        plan = self._chunk_plan(n_local, per_host)
        sample = arrays if arrays is not None else self._sample_of(data)
        for c in self.feature_cols:
            if c not in sample:
                raise KeyError(f"feature col {c!r} missing from predict data")
        self._ensure_state(sample)
        self._build_jits()
        outs, window = [], []
        single_host = _nhosts() == 1
        stream = self._local_eval_stream(data, per_host, arrays)
        for chunk in _padded_chunks(stream, plan and plan[0], sample):
            chunk = {k: v for k, v in chunk.items()
                     if k in self.feature_cols}
            real = len(next(iter(chunk.values())))
            chunk, _ = _pad_batch(chunk, per_host)
            gbatch = make_global_batch(self.mesh, chunk, self._data_sharding)
            preds = self._jit_predict_step(self.state, gbatch)
            # slice on-device, fetch in windowed batches: chunks pipeline
            # (no per-chunk round-trip) while device memory stays bounded
            # to `window` chunks of outputs instead of the whole dataset
            local = preds if single_host else _local_rows(preds)
            window.append(jax.tree.map(lambda a: a[:real], local))
            if len(window) >= 8:
                outs.extend(jax.device_get(window))
                window.clear()
        outs.extend(jax.device_get(window))
        return jax.tree.map(lambda *xs: np.concatenate(xs), *outs)

    # ------------------------------------------------------------------
    # checkpointing (Orbax; ref parity: set_checkpoint / save / load)
    # ------------------------------------------------------------------

    def _ckpt_items(self):
        return {"params": self.state.params,
                "opt_state": self.state.opt_state,
                "step": self.state.step,
                "batch_stats": self.state.batch_stats,
                "rng": jax.random.key_data(self.state.rng),
                "epoch": self._epoch}

    def _maybe_checkpoint(self):
        if self.config.checkpoint_dir:
            self.save_checkpoint(self.config.checkpoint_dir)

    def save_checkpoint(self, path: str):
        import orbax.checkpoint as ocp

        mgr = self._checkpoint_manager(path)
        mgr.save(int(self.state.step),
                 args=ocp.args.StandardSave(self._ckpt_items()))
        mgr.wait_until_finished()

    def load_checkpoint(self, path: str, step: Optional[int] = None):
        """Sharding-aware restore: arrays come back with this estimator's
        partition layout even if saved under a different mesh."""
        import orbax.checkpoint as ocp

        mgr = self._checkpoint_manager(path)
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {path}")
        if self.state is None:
            raise RuntimeError(
                "call fit/evaluate once (or _ensure_state) before "
                "load_checkpoint so state structure is known")
        tpl = self._ckpt_items()
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if isinstance(x, jax.Array) else x, tpl)
        restored = mgr.restore(step, args=ocp.args.StandardRestore(abstract))
        self.state = self.state.replace(
            params=restored["params"], opt_state=restored["opt_state"],
            step=restored["step"], batch_stats=restored["batch_stats"],
            rng=jax.random.wrap_key_data(restored["rng"]))
        self._epoch = int(restored.get("epoch", 0))
        # re-sync the host-side step counter (the one deliberate D2H read)
        self._global_step = int(np.asarray(restored["step"]))

    def _checkpoint_manager(self, path: str):
        import orbax.checkpoint as ocp

        path = _abs(path)
        if not hasattr(self, "_ckpt_mgrs"):
            self._ckpt_mgrs = {}
        if path not in self._ckpt_mgrs:
            self._ckpt_mgrs[path] = ocp.CheckpointManager(
                path,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self.config.keep_checkpoints, create=True))
        return self._ckpt_mgrs[path]

    def save(self, path: str):
        """Export trained params (+batch_stats) — the reference's
        Estimator.save model export."""
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        payload = {"params": self.state.params}
        if self.state.batch_stats is not None:
            payload["batch_stats"] = self.state.batch_stats
        ckptr.save(_abs(path), payload, force=True)
        ckptr.wait_until_finished()

    def load(self, path: str, sample_data=None):
        import orbax.checkpoint as ocp

        if self.state is None:
            if sample_data is None:
                raise ValueError("load before first fit needs sample_data "
                                 "to build the state structure")
            self._ensure_state(DataCreator.to_arrays(sample_data))
        ckptr = ocp.StandardCheckpointer()
        tpl = {"params": self.state.params}
        if self.state.batch_stats is not None:
            tpl["batch_stats"] = self.state.batch_stats
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), tpl)
        restored = ckptr.restore(_abs(path), abstract)
        self.state = self.state.replace(
            params=restored["params"],
            batch_stats=restored.get("batch_stats"))

    def get_model(self):
        """(model, params) — ref parity: Estimator.get_model."""
        return self.model, None if self.state is None else self.state.params

    def lora_params(self):
        """The adapter tree alone — megabytes, the thing a fine-tune
        ships (learn/lora.py)."""
        from analytics_zoo_tpu.learn.lora import split_lora

        if self.lora is None or self.state is None:
            raise RuntimeError("no LoRA state: pass lora=LoRAConfig(...) "
                               "and fit/evaluate first")
        return split_lora(self.state.params)[1]

    def merged_params(self):
        """Base params with adapters folded in (W + scale·A@B) — plain
        tree for serving/InferenceModel, no __lora__ key."""
        from analytics_zoo_tpu.learn.lora import merge_lora

        if self.lora is None or self.state is None:
            raise RuntimeError("no LoRA state: pass lora=LoRAConfig(...) "
                               "and fit/evaluate first")
        return jax.device_get(merge_lora(self.state.params, self.lora))


def _abs(path: str) -> str:
    import os

    from analytics_zoo_tpu.common import fs

    # remote checkpoint dirs (gs://...) pass through verbatim — orbax
    # resolves the scheme via etils/tensorstore; os.path.abspath would
    # mangle the URI into a local path and silently checkpoint to disk
    if fs.is_remote(path):
        return path
    return os.path.abspath(path)


def _fetch_stacked(mets_list, chunk: int = 512):
    """Fetch a list of per-step scalar-metric dicts as dict of (n,) numpy
    arrays in O(metrics x n/chunk) device transfers.

    Two scaling traps this avoids: device_get on the raw list pays a full
    round-trip per leaf (O(n x metrics)), while one giant stack builds
    an HLO with n operands
    (trace/lowering time explodes for long epochs).  Chunked eager stacks
    keep both costs linear with small constants.  The first stack dispatch
    is also the real epoch completion barrier's work — values must exist.
    """
    keys = list(mets_list[0].keys())
    stacked = {}
    for k in keys:
        vals = [m[k] for m in mets_list]
        stacked[k] = [jnp.stack(vals[i:i + chunk])
                      for i in range(0, len(vals), chunk)]
    # ONE device_get for every metric's chunks — per-key fetches would
    # pay a full round-trip per metric
    fetched = jax.device_get(stacked)
    return {k: np.concatenate(parts) for k, parts in fetched.items()}


def _resolve_batch(batch_size, data, attr: str) -> int:
    """Explicit batch_size wins; otherwise the data container's own
    metadata (TFDataset carries the reference's batch_size /
    batch_per_thread); otherwise the historical default of 32."""
    if batch_size is not None:
        return batch_size
    meta = getattr(data, attr, None)
    if isinstance(meta, int) and meta > 0:
        return meta
    return 32


def _allow_shared_disk() -> bool:
    """Kill-switch for the replicated-shard heuristic (distinct shards can
    in principle collide on the count+content fingerprint)."""
    import os

    return os.environ.get("ANALYTICS_ZOO_TPU_ALLOW_SHARED_DISK", "") == "1"


def _allgather_counts(n_local: int, fingerprint: int = 0) -> np.ndarray:
    """All hosts' (row count, content fingerprint) pairs, in process order
    (one tiny collective; replaces any out-of-band host coordination the
    reference did through the Spark driver).  Shape (n_hosts, 2); callers
    that only need counts use column 0 / ``.min()``."""
    from jax.experimental import multihost_utils

    return np.atleast_2d(np.asarray(multihost_utils.process_allgather(
        np.array([n_local, fingerprint], np.int64))))


class _StepLimitIterator:
    """Caps an epoch iterator at `max_steps` batches so every host runs the
    same number of collective steps even with uneven local row counts."""

    def __init__(self, it, max_steps: int):
        self._it = it
        self.max_steps = max_steps

    def steps_per_epoch(self) -> int:
        return min(self._it.steps_per_epoch(), self.max_steps)

    def epoch_batches(self):
        it = self._it
        e0 = getattr(it, "epoch", None)
        gen = it.epoch_batches()

        def limited():
            n = 0
            for b in gen:
                yield b
                n += 1
                if n >= self.max_steps:
                    break
            # release the source promptly (disk readers hold a ring buffer
            # + prefetch thread in their finally blocks)
            if hasattr(gen, "close"):
                gen.close()
            # NumpyBatchIterator only advances its epoch counter when its
            # generator runs to natural exhaustion; truncation would freeze
            # the shuffle seed at epoch 0 — advance it here if the source
            # didn't (disk iterators advance eagerly).
            if e0 is not None and getattr(it, "epoch", None) == e0:
                it.epoch = e0 + 1

        return limited()


def _padded_chunks(stream, n_chunks, sample):
    """Yield `stream`'s chunks, then zero-row chunks (shaped like `sample`'s
    columns) until `n_chunks` total — hosts that run out of rows still
    participate in the remaining collectives.  n_chunks=None: no padding."""
    j = 0
    for chunk in stream:
        yield chunk
        j += 1
    if n_chunks is not None and j < n_chunks:
        empty = {k: np.zeros((0,) + np.asarray(v).shape[1:],
                             np.asarray(v).dtype)
                 for k, v in sample.items()}
        while j < n_chunks:
            yield empty
            j += 1


def _host_local(data, groups=None) -> Dict[str, np.ndarray]:
    """Normalise `data` to this host's local rows.

    XShards are already host-disjoint (readers slice files per host);
    in-memory dicts/tuples are assumed REPLICATED across hosts (the natural
    way users pass ndarrays) and are row-sliced per DATA-SHARD GROUP here
    (`groups` = estimator._data_groups) — otherwise every host would feed
    identical rows into the global batch, silently training on duplicates.
    Group-mates (processes that are batch replicas, e.g. across a pp-only
    process boundary) intentionally keep identical rows.  Row counts
    truncate to the per-group share so every host runs the same step count
    (collective programs must agree)."""
    from analytics_zoo_tpu.data.shards import XShards

    arrays = DataCreator.to_arrays(data)
    ngroups, gi, _ = groups or (_nhosts(), _hidx(),
                                None)
    if _nhosts() == 1 or ngroups == 1 or \
            isinstance(data, XShards):
        return arrays
    n = len(next(iter(arrays.values())))
    per_group = n // ngroups
    lo = gi * per_group
    return {k: v[lo:lo + per_group] for k, v in arrays.items()}


def _pad_batch(batch: Dict[str, np.ndarray], to: int):
    n = len(next(iter(batch.values())))
    w = np.zeros(to, np.float32)
    w[:n] = 1.0
    if n == to:
        return batch, w
    out = {}
    for k, v in batch.items():
        pad = np.zeros((to - n,) + v.shape[1:], v.dtype)
        out[k] = np.concatenate([v, pad])
    return out, w


def _local_rows(preds) -> Any:
    """Fetch this host's rows of a (possibly sharded) prediction pytree."""
    def one(a):
        if _nhosts() == 1:
            return np.asarray(a)
        # multihost: concatenate this host's row shards in order, deduping
        # replicas (a replicated dim yields one shard per device with the
        # same rows and index[0].start of None).
        by_start = {}
        for s in a.addressable_shards:
            start = (s.index[0].start or 0) if s.index and \
                isinstance(s.index[0], slice) else 0
            by_start.setdefault(start, s)
        ordered = [by_start[k] for k in sorted(by_start)]
        return np.concatenate([np.asarray(s.data) for s in ordered])
    return jax.tree.map(one, preds)


def _route_train_config(config, kw):
    """`config` on the constructor facade is the reference's model-creator
    config dict; a TrainConfig passed there is clearly meant for the
    estimator — route it into kw instead of silently dropping it."""
    if isinstance(config, TrainConfig):
        kw.setdefault("config", config)
        return None
    return config


class Estimator:
    """Constructor facade — reference parity with zoo.orca.learn.*.Estimator."""

    @staticmethod
    def from_flax(*, model=None, model_creator=None, loss=None,
                  optimizer=None, config: Optional[dict] = None,
                  **kw) -> FlaxEstimator:
        config = _route_train_config(config, kw)
        if model is None:
            if model_creator is None:
                raise ValueError("need model or model_creator")
            model = model_creator(config or {})
        if optimizer is None:
            optimizer = optax.adam(1e-3)
        return FlaxEstimator(model, loss or "mse", optimizer, **kw)

    # Reference entry-point names. from_keras accepted tf.keras models;
    # here it accepts our keras/flax modules so orchestration code ports by
    # swapping the model definition.
    from_keras = from_flax

    @staticmethod
    def from_torch(*, model=None, model_creator=None, loss=None,
                   optimizer=None, config: Optional[dict] = None,
                   **kw) -> FlaxEstimator:
        """ref-parity: zoo.orca.learn.pytorch.Estimator.from_torch.

        A real torch nn.Module is converted to JAX via TorchNet (torch.fx
        graph -> pure function + param pytree, ref TorchNet.scala) and then
        trained by the same pjit Estimator; flax modules pass through."""
        config = _route_train_config(config, kw)
        if model is None:
            if model_creator is None:
                raise ValueError("need model or model_creator")
            model = model_creator(config or {})
        if optimizer is None:
            optimizer = optax.adam(1e-3)
        # conversion happens inside FlaxEstimator.__init__ (all paths)
        return FlaxEstimator(model, loss or "mse", optimizer, **kw)
    from_graph = from_flax
    from_bigdl = from_flax

    @staticmethod
    def from_openvino(*, model_path: Optional[str] = None,
                      bin_path: Optional[str] = None, **kw):
        """ref-parity name: zoo.orca.learn.openvino.Estimator.from_openvino
        (batch inference with OpenVINO IR over Spark partitions).

        The IR's ``.xml + .bin`` FORMAT is read directly
        (net/openvino_ir.py translates the graph to one XLA-compiled
        function; no IE runtime involved) and served by the same
        predict/evaluate machinery as every other estimator.  Like the
        reference's OpenVINO estimator, this one is INFERENCE-ONLY:
        ``fit`` raises (an IR is a frozen deployment artifact — train
        the original model instead)."""
        from analytics_zoo_tpu.net.openvino_ir import OpenVINONet

        if not model_path:
            raise ValueError("from_openvino needs model_path=<model.xml>")
        net = OpenVINONet.from_ir(model_path, bin_path)
        est = FlaxEstimator(net, kw.pop("loss", None) or "mse",
                            optax.sgd(0.0), **kw)

        def _no_fit(*a, **k):
            raise NotImplementedError(
                "OpenVINO estimators are inference-only (the IR is a "
                "frozen artifact — ref parity with "
                "zoo.orca.learn.openvino); use predict/evaluate, or "
                "train the original model via from_flax/from_torch")

        est.fit = _no_fit
        return est
