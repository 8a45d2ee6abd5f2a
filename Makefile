# Developer entry points (ref: the reference's pyzoo/dev run scripts +
# make-dist.sh packaging glue).

PY ?= python

.PHONY: test verify examples chip-smoke native serve-smoke \
	chaos-smoke overload-smoke sim-gate lint clean

# full suite on the 8-virtual-device CPU mesh (tests/conftest.py forces it)
test:
	$(PY) -m pytest tests/ -q

# quick smoke: native build + fast test subset + every example vertical.
# The tests force the CPU; the examples run on whatever platform JAX is
# given (JAX_PLATFORMS=cpu for a CPU run) — this target proves nothing
# about the chip, `make chip-smoke` does.
verify: native
	$(PY) -m pytest tests/test_context.py tests/test_data.py \
	    tests/test_estimator.py -q
	$(PY) examples/train_ncf.py
	$(PY) examples/forecast_taxi.py
	$(PY) examples/serve_model.py

examples:
	$(PY) examples/train_ncf.py
	$(PY) examples/forecast_taxi.py
	$(PY) examples/serve_model.py
	$(PY) examples/multihost_fit.py
	$(PY) examples/train_moe_pipeline.py --devices 8 --epochs 2
	$(PY) examples/lm_generate.py --devices 8

# compile the C++ data plane in place (csv parser, zrec store, ring
# buffer, image decode)
native:
	$(PY) -c "from analytics_zoo_tpu import native; native.load_lib(); print('native data plane:', native.available())"

# JAX staging/tracing lint (TZ001..TZ008) + concurrency lock-discipline
# pass (TZ101..TZ108), docs/lint.md; exits non-zero on any finding not
# recorded in tpulint_baseline.json, or on stale baseline entries.
# Pass --no-concurrency to run the staging family alone.
lint:
	$(PY) -m analytics_zoo_tpu.lint analytics_zoo_tpu/ \
	    --baseline tpulint_baseline.json

# the quickest proof that serving and training still start on the chip:
# Qwen2.5-1.5B-width serving through ClusterServing + HttpFrontend
# (default, then paged+chunked+fused in bf16 and int8), every Pallas
# kernel compiled and compared with its reference, BERT-base and the
# 111M LM through Estimator.fit.  Needs the TPU (one process holds it);
# `$(PY) chip_smoke.py --chips 4` runs the multi-chip legs on a
# four-chip host, `--tiny` is the CPU dry run of the same code.
chip-smoke:
	$(PY) chip_smoke.py

# serving smoke, all on the host CPU: the paged KV-cache, chunked-prefill,
# telemetry, QoS front-door and router test files; the composed-mode
# (speculative over blocks and chunks), flight-recorder and fused-kernel
# files without the marker filter (they keep their live-stack cases in
# the slow lane); then the end-to-end wire-protocol tests of a live fleet
# (tests/test_serve_smoke.py: paged + chunked shared-prefix run, the
# scrape of a speculative stack, anomaly bundle, replicas,
# disaggregation, host tier, fused kernel under tp=2, chaos, overload).
# Counts and correctness only: nothing here reads a time.
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_paged_cache.py \
	    tests/test_chunked_prefill.py tests/test_telemetry.py \
	    tests/test_frontdoor.py tests/test_router.py -q -m "not slow"
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_spec_composed.py \
	    tests/test_flight.py tests/test_paged_fused.py -q
	# LockGuard leg: live paged+chunked engine ticks (speculative and
	# host-tier spill->readmit churn) with every lock instrumented and
	# jax.device_get/device_put patched — zero order inversions, zero
	# device transfers under a lock (docs/lint.md, TZ1xx runtime twin)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_lockguard.py -q
	# fresh-bundle -> replay round trip + engine/sim decision equivalence
	# (slow-marked classes in test_sim.py run unfiltered here, like
	# test_flight.py above; docs/simulation.md)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_sim.py -q
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve_smoke.py -q

# crash-tolerance chaos test, standalone (serve-smoke runs it too): a
# live prefill + two-decode fleet under a fixed fault schedule — one
# decode pump crashes and one KV handoff is dropped; every request must
# reach a terminal result with its `attempts` recorded, and /metrics must
# show the death, the redispatch and the handoff's ack-timeout retry
# (docs/debugging.md "Crash recovery runbook").
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve_smoke.py -q -k chaos

# graceful-degradation overload test, standalone (serve-smoke runs it
# too): a live 2-replica fleet under a saturating mixed-class burst with
# a tiny brownout ladder — the ladder must ascend AND fully unwind on
# /metrics, requests past their deadline must be shed at admission as
# terminal deadline_exceeded errors, and every other request must finish
# (docs/serving_qos.md "Overload & brownout").
overload-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve_smoke.py -q -k overload

# CI gate for scheduler regressions: run the pinned golden scenario
# (tests/golden/sim_golden.json) through the offline discrete-event
# simulator and assert its envelopes (docs/simulation.md).  jax-free:
# also part of tier-1 via tests/test_sim.py::TestGoldenGate.
sim-gate:
	$(PY) -m analytics_zoo_tpu.serving.sim gate tests/golden/sim_golden.json

clean:
	rm -rf build dist *.egg-info analytics_zoo_tpu/native/*.so
