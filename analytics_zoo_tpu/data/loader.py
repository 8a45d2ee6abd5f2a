"""Host->HBM batch pipeline: shuffle, batch, shard, prefetch.

Replaces the reference's FeatureSet/DataSet minibatch stream and the
per-backend loader glue (SURVEY.md §2.2: Scala feature/dataset/ DRAM/PMEM
tiers; pyzoo/zoo/tfpark/tf_dataset.py; orca data-creator contract).

TPU shape of the problem: the hot loop consumes one *globally-sharded* batch
per step.  Each host materialises only its local rows (its XShards), and
`jax.make_array_from_process_local_data` assembles the global jax.Array over
the mesh's batch axes.  A small prefetch deque overlaps host-side batch
assembly + H2D transfer with device compute (the DRAM->HBM double-buffer
analog of FeatureSet's memory tiers).
"""

from __future__ import annotations

from functools import lru_cache as _functools_cache
from typing import Any, Callable, Dict, Iterator, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from analytics_zoo_tpu.common.context import \
    effective_process_count as _nhosts
from analytics_zoo_tpu.data.shards import XShards, shard_len
from analytics_zoo_tpu.parallel.partition import data_sharding


class NumpyBatchIterator:
    """Epoch iterator over a dict of host-local ndarrays.

    Yields dicts of ndarrays with leading dim = per-host batch size.
    Shuffles with a per-epoch seed (deterministic-data-order mode is then
    just a fixed seed — the reference's implicit Spark-partition order was
    not even reproducible; SURVEY.md §5 race-detection notes).
    """

    def __init__(self, arrays: Dict[str, np.ndarray], batch_size: int, *,
                 shuffle: bool = True, drop_remainder: bool = True,
                 seed: int = 0):
        if not arrays:
            raise ValueError("empty arrays dict")
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        lens = {k: len(v) for k, v in arrays.items()}
        if len(set(lens.values())) != 1:
            raise ValueError(f"ragged arrays: {lens}")
        self.arrays = arrays
        self.n = next(iter(lens.values()))
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_remainder = drop_remainder
        self.seed = seed
        self.epoch = 0
        if batch_size > self.n:
            raise ValueError(
                f"per-host batch {batch_size} > host rows {self.n}")

    def steps_per_epoch(self) -> int:
        if self.drop_remainder:
            return self.n // self.batch_size
        return -(-self.n // self.batch_size)

    def epoch_batches(self) -> Iterator[Dict[str, np.ndarray]]:
        end = (self.n // self.batch_size) * self.batch_size \
            if self.drop_remainder else self.n
        if self.shuffle:
            # permute ONCE per epoch per column, then serve contiguous
            # zero-copy slices — measured 1.8x the per-batch fancy-index
            # gather (and the per-step critical path drops to a view).
            # Cost: one transient dataset copy per epoch, the standard
            # DRAM-tier time-memory trade (BASELINE.md NCF profile).
            rng = np.random.default_rng(self.seed + self.epoch)
            idx = rng.permutation(self.n)
            arrays = {k: v[idx] for k, v in self.arrays.items()}
        else:
            arrays = self.arrays
        for lo in range(0, end, self.batch_size):
            yield {k: v[lo:lo + self.batch_size] for k, v in arrays.items()}
        self.epoch += 1


def shards_to_iterator(shards: XShards, per_host_batch: int,
                       **kw) -> NumpyBatchIterator:
    return NumpyBatchIterator(shards.to_numpy_dict(), per_host_batch, **kw)


def make_global_batch(mesh: Mesh, batch: Dict[str, np.ndarray],
                      sharding: Optional[NamedSharding] = None,
                      pack: bool = False) -> Dict[str, jax.Array]:
    """Host-local batch dict -> globally-sharded jax.Array dict.

    ``pack=True`` ships the whole batch as ONE row-major uint8 buffer
    (one ``device_put``/assembly instead of one per column) and unpacks
    on-device via slice + bitcast.  Each transfer has a fixed dispatch
    cost (per-call runtime overhead), so for many-column batches
    (recommenders: user, item, label, ...) packing collapses k fixed
    costs into one.  The
    pack itself is a single host memcpy at DRAM bandwidth.
    """
    sh = sharding or data_sharding(mesh)
    if pack:
        packed = _pack_rows(batch)
        if packed is not None:
            buf, spec = packed
            if _nhosts() == 1:
                gbuf = jax.device_put(buf, sh)
            else:
                gbuf = jax.make_array_from_process_local_data(sh, buf)
            if gbuf.shape[0] != buf.shape[0]:
                # multihost: the assembled array holds GLOBAL rows (local
                # x data-shard groups); globalise the spec's leading dims
                spec = tuple(
                    (k, (gbuf.shape[0],) + shape[1:], dt, rb)
                    for (k, shape, dt, rb) in spec)
            return _unpacker(spec)(gbuf)
    if _nhosts() == 1:
        return {k: jax.device_put(v, sh) for k, v in batch.items()}
    return {k: jax.make_array_from_process_local_data(sh, v)
            for k, v in batch.items()}


def _pack_rows(batch: Dict[str, np.ndarray]):
    """Pack columns (all sharing leading dim B) into a [B, total_row_bytes]
    uint8 buffer + a static spec for on-device unpacking.  Returns None if
    the batch can't be packed (mismatched leading dims)."""
    cols = []
    spec = []
    B = None
    for k, v in batch.items():
        v = np.asarray(v)
        # match device_put semantics under disabled x64: 64-bit dtypes
        # canonicalize to their 32-bit counterparts BEFORE byte-packing
        canon = jax.dtypes.canonicalize_dtype(v.dtype)
        v = np.ascontiguousarray(v, dtype=canon)
        if B is None:
            B = v.shape[0]
        if v.ndim == 0 or v.shape[0] != B:
            return None
        rows = v.view(np.uint8).reshape(B, -1)
        spec.append((k, v.shape, v.dtype.str, rows.shape[1]))
        cols.append(rows)
    if not cols:
        return None
    return np.concatenate(cols, axis=1), tuple(spec)


@_functools_cache
def _unpacker(spec):
    """Jitted on-device unpack for a packed-row buffer: per column, slice
    its byte range and bitcast back to the original dtype/shape.  Row
    sharding (dp over dim 0) propagates through — no reshard."""
    from jax import lax

    def unpack(buf):
        out = {}
        off = 0
        for name, shape, dtypestr, rowbytes in spec:
            dt = np.dtype(dtypestr)
            sl = lax.slice_in_dim(buf, off, off + rowbytes, axis=1)
            off += rowbytes
            if dt == np.bool_:
                arr = sl.reshape(shape) != 0
            elif dt.itemsize == 1:
                arr = lax.bitcast_convert_type(sl, dt).reshape(shape)
            else:
                arr = lax.bitcast_convert_type(
                    sl.reshape(shape[0], -1, dt.itemsize), dt)
                arr = arr.reshape(shape)
            out[name] = arr
        return out

    return jax.jit(unpack)


def device_prefetch(batches: Iterator[Dict[str, np.ndarray]], mesh: Mesh, *,
                    depth: int = 3,
                    sharding: Optional[NamedSharding] = None,
                    pack: bool = False
                    ) -> Iterator[Dict[str, jax.Array]]:
    """Overlap H2D transfer with compute: keep `depth` batches in flight,
    staged by a background thread.

    On the TPU ``device_put`` itself is asynchronous and its transfer
    overlaps compute from any thread (checked on the v5e, PR 21: the call
    returns in under a millisecond, and a 256 MB put beside a 0.38 s
    program costs nothing extra).  What the worker thread overlaps with
    the main thread's dispatch is the HOST side of staging — the numpy
    gather, the pack and the dispatch of the next batches — by filling a
    bounded queue (depth = HBM staging bound); numpy gather + device_put
    release the GIL for the copy, so the threads genuinely overlap.

    On the CPU backend the transfer is a host memcpy — there is nothing
    to overlap — and a ``device_put`` issued from a second thread can
    deadlock against a concurrently-executing jitted program in the XLA
    CPU client (observed on forced multi-device hosts: worker pinned in
    ``device_put``, consumer pinned in the jit step, indefinitely), so
    stage inline on the consumer thread there.
    """
    import queue as _queue
    import threading

    sh = sharding or data_sharding(mesh)
    if jax.default_backend() == "cpu":
        for b in batches:
            yield make_global_batch(mesh, b, sh, pack=pack)
        return
    q: _queue.Queue = _queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    _END = object()

    def worker():
        try:
            for b in batches:
                if stop.is_set():
                    return
                q.put(make_global_batch(mesh, b, sh, pack=pack))
            q.put(_END)
        except BaseException as e:  # surface reader errors to the consumer
            q.put(e)

    t = threading.Thread(target=worker, daemon=True,
                         name="zoo-device-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # unblock the worker if it is waiting on a full queue
        while t.is_alive():
            try:
                q.get_nowait()
            except _queue.Empty:
                t.join(timeout=0.1)


class DataCreator:
    """The reference's data-creator contract (SURVEY.md §2.2: estimators
    accept ``data_creator(config) -> loader``).  Anything acceptable to
    `Estimator.fit` normalises through here: XShards, dict of ndarrays,
    (x, y) tuples, or a callable(config) returning one of those."""

    @staticmethod
    def to_arrays(data: Any, config: Optional[dict] = None,
                  feature_cols: Optional[Sequence[str]] = None,
                  label_cols: Optional[Sequence[str]] = None
                  ) -> Dict[str, np.ndarray]:
        if callable(data):
            data = data(config or {})
        # TFDataset bridging adapter (tfpark surface; duck-typed — also
        # covers subclasses — to keep the data layer import-free of tfpark)
        if not isinstance(data, dict) and callable(
                getattr(data, "to_arrays", None)):
            data = data.to_arrays()
        # FeatureSet tiers (import locally — feature_set imports loader)
        from analytics_zoo_tpu.data import feature_set as _fs
        if isinstance(data, _fs.DiskFeatureSet):
            data = data.to_dram()       # eval/predict paths materialise
        if isinstance(data, _fs.FeatureSet):
            d = dict(data.arrays)
        elif isinstance(data, XShards):
            d = data.to_numpy_dict()
        elif isinstance(data, dict):
            d = {k: np.asarray(v) for k, v in data.items()}
        elif isinstance(data, (tuple, list)) and len(data) == 2:
            x, y = data
            d = {}
            if isinstance(x, dict):
                d.update({k: np.asarray(v) for k, v in x.items()})
            else:
                d["x"] = np.asarray(x)
            if isinstance(y, dict):
                d.update({k: np.asarray(v) for k, v in y.items()})
            else:
                d["y"] = np.asarray(y)
        else:
            raise TypeError(f"unsupported data type {type(data)}")
        if feature_cols or label_cols:
            sel = {}
            for c in list(feature_cols or []) + list(label_cols or []):
                if c not in d:
                    raise KeyError(f"column {c!r} not in data "
                                   f"(have {sorted(d)})")
                sel[c] = d[c]
            d = sel
        return d
