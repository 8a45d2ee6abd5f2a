"""Device-mesh construction.

The reference's cluster substrate is Spark executors + Ray workers
(ref: pyzoo/zoo/ray/raycontext.py, pyzoo/zoo/common/nncontext.py); ours is a
`jax.sharding.Mesh` over TPU chips.  All parallelism in the framework is
expressed as named mesh axes + `PartitionSpec`s — XLA emits the collectives
(psum / all_gather / reduce_scatter / ppermute) over ICI/DCN, which replaces
the reference's entire zoo of communication backends (Spark BlockManager
all-reduce, gloo, MPI, TF collectives; SURVEY.md §2.3).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from analytics_zoo_tpu.common.config import MeshConfig

# Canonical axis order: batch-like (outermost, over DCN if multi-slice) first,
# then model axes (want fastest ICI).
CANONICAL_AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")


def resolve_axis_sizes(
    axes: Dict[str, int], n_devices: int
) -> Dict[str, int]:
    """Resolve -1 ("fill") entries so that prod(sizes) == n_devices.

    At most one -1 is allowed.  Fixed axes must divide n_devices.
    """
    fills = [k for k, v in axes.items() if v == -1]
    if len(fills) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {fills}")
    fixed = int(np.prod([v for v in axes.values() if v != -1], dtype=np.int64))
    if fills:
        if n_devices % fixed != 0:
            raise ValueError(
                f"Fixed mesh axes {axes} (product {fixed}) do not divide "
                f"device count {n_devices}")
        resolved = dict(axes)
        resolved[fills[0]] = n_devices // fixed
        return resolved
    if fixed != n_devices:
        raise ValueError(
            f"Mesh axes {axes} (product {fixed}) != device count {n_devices}")
    return dict(axes)


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axes: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Build a Mesh from a MeshConfig (or explicit axis dict).

    Uses `jax.make_mesh` so the logical mesh is laid out along the physical
    ICI topology (axis order: later axes get the fastest links — we order
    model axes last via CANONICAL_AXES).
    """
    if axes is None:
        axes = (config or MeshConfig()).axes
    devices = list(devices if devices is not None else jax.devices())
    sizes = resolve_axis_sizes(dict(axes), len(devices))
    # Drop size-1 axes? No — keep them: PartitionSpecs referencing them stay
    # valid, and scaling up is a config change, not a code change.
    names = sorted(sizes.keys(),
                   key=lambda n: CANONICAL_AXES.index(n)
                   if n in CANONICAL_AXES else len(CANONICAL_AXES))
    shape = tuple(sizes[n] for n in names)
    # jax.make_mesh defaults to Explicit axis types, which changes
    # sharding semantics under jit (shardings become part of array types and
    # ops like x @ x.T error on duplicate axes).  We want classic Auto/pjit
    # semantics: request it explicitly.
    axis_types = (jax.sharding.AxisType.Auto,) * len(names)
    if devices == list(jax.devices()):
        try:
            return jax.make_mesh(shape, tuple(names), axis_types=axis_types)
        except (ValueError, RuntimeError):
            pass  # fall through to manual reshape (e.g. odd device subsets)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, tuple(names), axis_types=axis_types)


def single_device_mesh(axis: str = "dp") -> Mesh:
    return make_mesh(axes={axis: 1}, devices=[jax.devices()[0]])


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes over which the batch dim is sharded (dp-like axes present)."""
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)


def mesh_batch_size(mesh: Mesh) -> int:
    return int(math.prod(mesh.shape[a] for a in batch_axes(mesh)) or 1)
