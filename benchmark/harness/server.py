"""The system under test, started the way a user starts it.

With each family's ``model.py``, the only modules of the benchmark that
import the package.  The configuration's family builds the model object and
places the benchmark's own seeded weights (harness/weights.py) in its tree
(families/<family>/model.py); this module checks the tree against
``model.init`` and starts ``InferenceModel.load_flax_generator`` ->
``ClusterServing(embedded_broker=True)`` -> ``HttpFrontend``.
"""

from __future__ import annotations

import gc
import http.client
import json
import time

import jax
import numpy as np

from . import cells
from . import weights as W


def build_variables(model, cfg: dict, seed: int) -> dict:
    """The benchmark's seeded bf16 leaves, placed by the configuration's
    family in the served model's own tree; the tree and every shape are
    checked against ``model.init``."""
    params = cells.family(cfg).model.place(
        cfg, W.top(cfg, seed), lambda i: W.layer(cfg, seed, i))
    variables = {"params": params}
    want = jax.eval_shape(model.init, jax.random.key(0),
                          np.zeros((1, 8), np.int32))
    got_s = jax.tree.map(lambda x: x.shape, variables)
    want_s = jax.tree.map(lambda x: x.shape, want)
    if got_s != want_s:
        raise RuntimeError(
            "the served model's parameter tree is not the one the "
            "benchmark fills: the program changed its layout; "
            f"benchmark {got_s} vs model {want_s}")
    return variables


class Stack:
    """ClusterServing + HttpFrontend over one engine."""

    def __init__(self, cfg: dict, seed: int, diag_dir: str):
        from analytics_zoo_tpu.learn.inference_model import InferenceModel
        from analytics_zoo_tpu.serving import (ClusterServing,
                                               HttpFrontend, ServingConfig)

        gen, eng = cfg["generator"], dict(cfg["engine"])
        fam = cells.family(cfg)
        self.model = fam.model.build(cfg)
        self.vocab = fam.leaves.vocab(cfg)
        variables = build_variables(self.model, cfg, seed)
        jax.block_until_ready(variables)
        im = InferenceModel(batch_buckets=(1, eng["engine_slots"]))
        im.load_flax_generator(self.model, variables,
                               max_new_tokens=gen["max_new_tokens"],
                               prompt_buckets=tuple(gen["prompt_buckets"]))
        del variables
        sc = ServingConfig(prompt_col="tokens", continuous_batching=True,
                           diag_dir=diag_dir, **eng)
        self.serving = ClusterServing(im, sc, embedded_broker=True).start()
        self.frontend = HttpFrontend(redis_port=self.serving.port,
                                     timeout=cfg["frontend_timeout_s"],
                                     serving=self.serving).start()
        self.engine = self.serving.engines[0]
        self.port = self.frontend.port

    def warm(self, lengths: list, max_chunk_rows=None) -> int:
        """Compile every program the cell's traffic can reach: the chunked
        scheduler's whole shape grid (the engine's own
        ``precompile_chunked``), then real requests through the front door
        at the given ``(prompt_len, max_new)`` so the decode-only step and
        the admission path have run.  ``max_chunk_rows`` (the
        configuration's ``warm`` section) bounds the grid's chunk-row
        axis where the whole grid would not fit a run's time.  Returns the
        grid's size."""
        t0 = time.monotonic()
        n = self.engine.precompile_chunked(max_chunk_rows=max_chunk_rows)
        self.grid_s = time.monotonic() - t0
        rng = np.random.default_rng(0)
        for plen, max_new in lengths:
            post_generate(self.port, rng.integers(
                1, self.vocab, plen).tolist(), max_new)
        return n

    def stop(self) -> None:
        self.frontend.stop()
        self.serving.stop()

    def free(self) -> None:
        """Drop every reference to the engine, its pool and the weights so
        the reference has the chip to itself."""
        self.engine = self.serving = self.frontend = self.model = None
        gc.collect()


def post_generate(port: int, tokens: list, max_new: int,
                  timeout: float = 900.0) -> list:
    """One non-streamed ``POST /v1/generate`` (warm-up only)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(
            {"tokens": tokens, "max_new": max_new}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"warm-up /v1/generate -> {resp.status}: "
                               f"{raw[:300]}")
        return json.loads(raw)["tokens"]
    finally:
        conn.close()


class CompileCounter:
    """Counts backend compiles of the whole process (what JAX itself
    reports through jax.monitoring), and loads from the persistent cache:
    the window must see neither."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0          # programs compiled
        self.seconds = 0.0
        self.loaded = 0     # programs read back from the persistent cache
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.loaded += 1
