#!/usr/bin/env python
"""Elastic training supervisor — crash-and-restart orchestration.

SURVEY §5 failure model: JAX's coordination service detects a dead host
(lost heartbeat) and ABORTS the surviving processes; recovery is a fresh
incarnation of the whole process group restoring the last checkpoint.
This supervisor automates that loop on one machine (the single-box
multi-process doctrine; on a real pod, the platform's VM manager
respawns hosts and the same `fit(auto_resume=True)` contract applies):

    python scripts/run_elastic.py --nprocs 2 --max-restarts 3 -- \
        python train.py --my-args...

The training script needs NO resume logic: it calls
``init_orca_context("multihost")`` (coordinator/process-id arrive via
ZOO_COORDINATOR / ZOO_NUM_PROCESSES / ZOO_PROCESS_ID env, set here) and
``est.fit(..., auto_resume=True)`` with a ``checkpoint_dir`` — a
respawned group restores the last checkpoint and trains only the
remaining epochs.

Exit status: 0 when an incarnation finishes with every worker at rc=0;
non-zero when ``--max-restarts`` incarnations all failed.

Runbook: docs/architecture.md "Failure recovery".
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def run_group(cmd, nprocs: int, incarnation: int,
              extra_env: dict, timeout_s: float = 0) -> list:
    """One incarnation: spawn nprocs workers, wait for all, return
    returncodes.  On the FIRST failure the rest are terminated — they
    are either already aborting (coordination-service detection) or
    doomed to hang in the dead peer's collective.  ``timeout_s`` > 0
    converts an alive-but-hung incarnation (e.g. a deadlocked
    collective no process dies from) into the restart this supervisor
    exists to provide."""
    # this supervisor never imports JAX: a parent that has initialised a
    # backend holds the chip(s) its workers are about to need
    port = _free_port()
    t_start = time.monotonic()
    procs = []
    for pid in range(nprocs):
        env = dict(os.environ)
        env.update(extra_env)
        env["ZOO_COORDINATOR"] = f"localhost:{port}"
        env["ZOO_NUM_PROCESSES"] = str(nprocs)
        env["ZOO_PROCESS_ID"] = str(pid)
        env["ZOO_INCARNATION"] = str(incarnation)
        procs.append(subprocess.Popen(cmd, env=env))
    rcs = [None] * nprocs
    try:
        while any(rc is None for rc in rcs):
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    rcs[i] = p.poll()
            bad = [i for i, rc in enumerate(rcs)
                   if rc is not None and rc != 0]
            if not bad and timeout_s > 0 and \
                    time.monotonic() - t_start > timeout_s:
                print(f"[run_elastic] incarnation timed out after "
                      f"{timeout_s:.0f}s (hung collective?) — killing "
                      f"the group", file=sys.stderr)
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                for p in procs:
                    p.wait()
                return [p.poll() if p.poll() != 0 else -1 for p in procs]
            if bad:
                # give the coordination service a moment to abort the
                # survivors on its own (clean diagnostics beat SIGTERM),
                # then terminate whatever is left
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline and \
                        any(p.poll() is None for p in procs):
                    time.sleep(0.5)
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=15)
                    except subprocess.TimeoutExpired:
                        p.kill()
                return [p.poll() for p in procs]
            time.sleep(0.5)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return rcs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="restart-on-failure supervisor for multihost training")
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restarts AFTER the first attempt")
    ap.add_argument("--incarnation-timeout", type=float, default=0,
                    help="seconds before an alive-but-hung incarnation "
                         "is killed and counted as a failure (0 = no "
                         "timeout)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- training command (python train.py ...)")
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd and args.cmd[0] == "--" else args.cmd
    if not cmd:
        ap.error("no training command given (append: -- python train.py)")
    for incarnation in range(args.max_restarts + 1):
        t0 = time.monotonic()
        rcs = run_group(cmd, args.nprocs, incarnation, {},
                        timeout_s=args.incarnation_timeout)
        if all(rc == 0 for rc in rcs):
            print(f"[run_elastic] incarnation {incarnation} succeeded "
                  f"({time.monotonic() - t0:.0f}s)")
            return 0
        print(f"[run_elastic] incarnation {incarnation} failed "
              f"(rcs={rcs}, {time.monotonic() - t0:.0f}s)"
              + ("; restarting from last checkpoint"
                 if incarnation < args.max_restarts else ""),
              file=sys.stderr)
    print(f"[run_elastic] giving up after {args.max_restarts + 1} "
          f"incarnations", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
