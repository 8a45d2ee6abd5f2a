#!/usr/bin/env python
"""Example: multi-host training — 2 processes, one jax.distributed
coordinator, host-local data shards, one global model.

Run:  python examples/multihost_fit.py
(self-spawns 2 worker processes on this box with 4 virtual CPU devices
each — the single-box analog of 2 TPU-VM hosts; on a real pod each host
runs the same worker code with its own process_id.)

What it demonstrates:
  * ``init_orca_context("multihost", ...)`` joining the coordinator
    (the Spark-submit + RayOnSpark analog — SURVEY §3.1),
  * replicated ndarray inputs deduplicated across hosts automatically,
  * per-host DiskFeatureSet shards ({host} path placeholder),
  * a checkpoint written collectively by both hosts.
"""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def worker(pid: int, nprocs: int, port: int, workdir: str):
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np
    import optax
    import flax.linen as nn

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.common.config import TrainConfig
    from analytics_zoo_tpu.data.feature_set import FeatureSet
    from analytics_zoo_tpu.learn import Estimator

    ctx = init_orca_context(
        "multihost", coordinator_address=f"localhost:{port}",
        num_processes=nprocs, process_id=pid, mesh_axes={"dp": -1})
    print(f"[host {pid}] joined: {ctx}", flush=True)

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(nn.tanh(nn.Dense(32)(x)))

    rng = np.random.default_rng(0)          # same data on every host —
    x = rng.normal(size=(512, 8)).astype(np.float32)   # fit() dedups
    y = x.sum(1, keepdims=True).astype(np.float32)

    est = Estimator.from_flax(model=MLP(), loss="mse",
                              optimizer=optax.adam(1e-2),
                              config=TrainConfig(seed=0))
    hist = est.fit({"x": x, "y": y}, epochs=3, batch_size=64)
    if pid == 0:
        for i, h in enumerate(hist):
            print(f"[host 0] epoch {i + 1}: loss={h['loss']:.4f}",
                  flush=True)

    # per-host disk shards: each host spills ITS half and streams it
    half = len(x) // nprocs
    lo = pid * half
    dfs = FeatureSet({"x": x[lo:lo + half], "y": y[lo:lo + half]}).to_disk(
        os.path.join(workdir, "shard_{host}.zrec"))
    h2 = est.fit(dfs, epochs=1, batch_size=64)
    if pid == 0:
        print(f"[host 0] disk-tier epoch: loss={h2[-1]['loss']:.4f} "
              f"({int(h2[-1]['num_samples'])} global samples)", flush=True)

    est.save_checkpoint(os.path.join(workdir, "ckpt"))
    if pid == 0:
        print(f"[host 0] collective checkpoint written; final step "
              f"{int(est.state.step)}", flush=True)


def main():
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    workdir = tempfile.mkdtemp(prefix="zoo_multihost_")
    # the launcher itself stays off JAX (a parent that has initialised a
    # backend holds the device its workers need); the two workers are a
    # CPU dry run of the multihost path
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             str(i), "2", str(port), workdir], env=env)
        for i in range(2)
    ]
    try:
        rcs = [p.wait(timeout=600) for p in procs]
    finally:
        # a crashed worker leaves its peer blocked in a gloo collective —
        # never leak a hung process (same pattern as tests/test_multihost)
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(rcs):
        raise SystemExit(f"worker exit codes: {rcs}")
    print("multihost example complete")


if __name__ == "__main__":
    if "--worker" in sys.argv:
        i = sys.argv.index("--worker")
        worker(int(sys.argv[i + 1]), int(sys.argv[i + 2]),
               int(sys.argv[i + 3]), sys.argv[i + 4])
    else:
        main()
