"""Context bootstrap — the ``init_orca_context`` analog.

Reference behavior (SURVEY.md §3.1, ref: pyzoo/zoo/orca/common.py,
pyzoo/zoo/common/nncontext.py, pyzoo/zoo/ray/raycontext.py): one call builds
the whole cluster substrate — SparkContext with BigDL engine config, plus
optionally a Ray cluster bootstrapped inside the Spark executors.

TPU-native inversion: there is no JVM and no subprocess zoo.  One call

- (multihost) runs ``jax.distributed.initialize`` so all TPU-VM hosts join a
  coordinator (this replaces spark-submit + RayOnSpark barrier launch), and
- builds the global device `Mesh` (this replaces executor allocation),
- installs a process-wide ``OrcaContext`` singleton carrying config, mesh and
  RNG seed (this replaces the ZooContext/OrcaContext config singletons).

`cluster_mode` parity:
  reference: local | yarn-client | yarn-cluster | k8s | standalone | spark-submit
  here:      local (this process's devices) | multihost (TPU pod slice)
Other reference modes are provisioning concerns that do not exist on TPU VMs;
they raise with a pointer to `multihost`.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh

from analytics_zoo_tpu.common.compile_cache import enable_compile_cache
from analytics_zoo_tpu.common.config import MeshConfig, ZooConfig
from analytics_zoo_tpu.parallel import mesh as mesh_lib

logger = logging.getLogger("analytics_zoo_tpu")


class ZooContext:
    """Process-wide state: config, mesh, seed.  Created by `init_context`."""

    def __init__(self, config: ZooConfig, mesh: Mesh):
        self.config = config
        self.mesh = mesh
        self.seed = config.train.seed

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def num_processes(self) -> int:
        return jax.process_count()

    def __repr__(self):
        return (f"ZooContext(mesh={dict(self.mesh.shape)}, "
                f"devices={self.num_devices}, "
                f"process={self.process_index}/{self.num_processes})")


class _OrcaContextMeta(type):
    """Config singleton with attribute-style access, matching the reference's
    ``OrcaContext`` (ref: pyzoo/zoo/orca/common.py OrcaContextMeta):
    ``OrcaContext.pandas_read_backend``-style global knobs."""

    _ctx: Optional[ZooContext] = None
    _lock = threading.Lock()
    # reference-parity global knobs
    pandas_read_backend: str = "pandas"
    serialize_data_creator: bool = False
    log_output: bool = True

    def get_context(cls) -> ZooContext:
        if cls._ctx is None:
            raise RuntimeError(
                "No context initialised — call init_orca_context() first")
        return cls._ctx


class OrcaContext(metaclass=_OrcaContextMeta):
    pass


def init_context(
    cluster_mode: str = "local",
    *,
    config: Optional[ZooConfig] = None,
    mesh_axes: Optional[Dict[str, int]] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    num_devices: Optional[int] = None,
    seed: Optional[int] = None,
    **extra: Any,
) -> ZooContext:
    """Initialise the framework context. Returns a :class:`ZooContext`.

    Args:
      cluster_mode: "local" (devices visible to this process) or "multihost"
        (join/initialise a jax.distributed coordinator across TPU-VM hosts
        first — the RayOnSpark-launch analog).
      mesh_axes: e.g. ``{"dp": -1}`` (default), ``{"dp": -1, "tp": 4}``.
      coordinator_address/num_processes/process_id: multihost bootstrap; when
        omitted, jax auto-detects from the TPU metadata server.
    """
    import copy

    enable_compile_cache()

    cfg = copy.deepcopy(config) if config is not None else ZooConfig()
    if mesh_axes is not None:
        cfg.mesh = MeshConfig(axes=dict(mesh_axes))
    if seed is not None:
        cfg.train.seed = seed
    cfg.extra.update(extra)

    if cluster_mode in ("multihost", "tpu-pod", "distributed"):
        # Replaces: conda-pack + spark-submit + barrier-mode `ray start`
        # (SURVEY.md §3.1). One collective handshake, no subprocesses.
        # Explicit args > ZOO_* env (set by scripts/run_elastic.py so
        # training scripts stay supervisor-agnostic) > jax autodetect
        # from the TPU metadata server.
        import os as _os

        if coordinator_address is None:
            coordinator_address = _os.environ.get("ZOO_COORDINATOR")
        if num_processes is None and "ZOO_NUM_PROCESSES" in _os.environ:
            num_processes = int(_os.environ["ZOO_NUM_PROCESSES"])
        if process_id is None and "ZOO_PROCESS_ID" in _os.environ:
            process_id = int(_os.environ["ZOO_PROCESS_ID"])
        kwargs: Dict[str, Any] = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        try:
            jax.distributed.initialize(**kwargs)
        except RuntimeError as e:  # already initialised is fine
            if "already" not in str(e).lower():
                raise
    elif cluster_mode != "local":
        raise ValueError(
            f"cluster_mode={cluster_mode!r}: Spark-era modes (yarn/k8s/"
            f"standalone) have no TPU equivalent; use 'local' or 'multihost'")

    devices = None
    if num_devices is not None:
        avail = jax.devices()
        if num_devices > len(avail):
            raise ValueError(
                f"num_devices={num_devices} but only {len(avail)} devices "
                f"are available")
        devices = avail[:num_devices]
    m = mesh_lib.make_mesh(cfg.mesh, devices=devices)
    ctx = ZooContext(cfg, m)
    with _OrcaContextMeta._lock:
        _OrcaContextMeta._ctx = ctx
    logger.info("initialised %r", ctx)
    return ctx


def init_orca_context(cluster_mode: str = "local", **kwargs) -> ZooContext:
    """Reference-parity alias (ref: zoo.orca.init_orca_context)."""
    return init_context(cluster_mode, **kwargs)


def stop_orca_context() -> None:
    """Tear down the context (ref: zoo.orca.stop_orca_context).

    On TPU there are no executor processes to kill; we just drop the
    singleton and (if we initialised it) leave jax.distributed running —
    shutting it down mid-process is unsafe for later re-init.
    """
    with _OrcaContextMeta._lock:
        _OrcaContextMeta._ctx = None


# ---------------------------------------------------------------------------
# process-local execution scope (distributed HPO trial isolation)
# ---------------------------------------------------------------------------

# Deliberately PROCESS-wide, not thread-local: the scope must be visible
# to worker threads the scoped code spawns (device_prefetch's H2D thread
# calls make_global_batch, whose multihost branch keys on
# effective_process_count()).  Distributed HPO runs one scoped trial at a
# time per process, so a process-wide flag cannot leak across trials.
_LOCAL_SCOPE = {"on": False}


def in_local_process_scope() -> bool:
    return _LOCAL_SCOPE["on"]


def effective_process_count() -> int:
    """``jax.process_count()``, except inside :func:`local_process_scope`
    where it is 1 — multihost code paths (data splitting, row-count
    allgathers, early-stop agreement) must treat a scoped trial as a
    single-host program or concurrent per-process trials would issue
    mismatched cross-process collectives and deadlock."""
    return 1 if in_local_process_scope() else jax.process_count()


def effective_process_index() -> int:
    return 0 if in_local_process_scope() else jax.process_index()


@contextlib.contextmanager
def local_process_scope(mesh_axes: Optional[Dict[str, int]] = None):
    """Re-scope the framework to THIS process for the duration: the
    context mesh covers only ``jax.local_devices()`` and every
    process-count-dependent branch acts single-host.

    This is the trial-isolation analog of the reference giving each Ray
    Tune trial its own actor + resources (ref: SURVEY §3.6
    RayTuneSearchEngine): during distributed HPO each process trains a
    DIFFERENT config concurrently, so nothing inside a trial may
    synchronise with peers.  File-path conventions (``{host}`` shard
    naming) intentionally keep the REAL process index."""
    ctx = OrcaContext.get_context()
    old_mesh = ctx.mesh
    from analytics_zoo_tpu.common.config import MeshConfig as _MC

    local = mesh_lib.make_mesh(_MC(axes=dict(mesh_axes or {"dp": -1})),
                               devices=jax.local_devices())
    _LOCAL_SCOPE["on"] = True
    ctx.mesh = local
    try:
        yield ctx
    finally:
        ctx.mesh = old_mesh
        _LOCAL_SCOPE["on"] = False
