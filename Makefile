# Build/test orchestration (role of the reference's setup.py Extension
# build + tox targets).  The C++ solver is also auto-built at runtime by
# pybitmessage_tpu/pow/native.py when missing or stale.

.PHONY: all native test bench bench-smoke chaos perfguard lint \
	roles-smoke clients-smoke profile-smoke device-smoke doctor \
	chip-smoke clean

all: native

native:
	$(MAKE) -C native/pow
	$(MAKE) -C native/secp256k1

test: native
	python -m pytest tests/ -q

# bmlint static-analysis gate (docs/static_analysis.md): AST checkers
# proving the standing conventions — crypto/SQL off the event loop,
# no RMW across awaits without a lock, no silent broad excepts,
# REGISTRY-only metrics with bounded labels, full chaos-site coverage.
# New findings and stale baseline entries both fail; the committed
# baseline (tools/bmlint/baseline.json) only ever shrinks.  Also runs
# inside tier-1 via tests/test_bmlint.py.
lint:
	python -m tools.bmlint

bench: native
	python bench.py

# seeded chaos suite on the CPU mesh (docs/resilience.md): fault
# injection at pow.device_launch / pow.readback / db.write / net.send
# plus the role fabric (role.ipc / role.handoff / role.replica —
# relay kill/restart and mid-drain handoff receiver kill/restart)
# proving no-object-loss + checkpoint resume; stays in the tier-1
# "not slow" budget
chaos: native
	JAX_PLATFORMS=cpu BMTPU_CHAOS_SEED=1234 python -m pytest \
		tests/test_resilience.py tests/test_resilience_chaos.py \
		tests/test_pow_farm.py tests/test_crypto_tpu.py \
		-q -m 'not slow'

# tiny CPU-only bench for CI: reduced slabs, reference test-mode
# difficulty, XLA impl (docs/pow_pipeline.md), plus the ingest_storm
# and sync_storm smoke sections — the sync mesh must converge with
# zero object loss (docs/sync.md) or the run fails
bench-smoke:
	JAX_PLATFORMS=cpu python bench.py --smoke

# perf guard (docs/observability.md): run bench-smoke and diff the
# guarded metrics against the committed baseline with per-metric
# tolerance bands — exits non-zero on regression.  Re-baseline after
# an intentional perf change with:
#   python tools/bench_compare.py --run --update
perfguard:
	python tools/bench_compare.py --run

# continuous-profiling smoke (docs/observability.md "Continuous
# profiling"): the sampler must classify threads/subsystems correctly,
# cost <2% on the ingest smoke path (same harness shape as the PR 1
# tracing-overhead gate), attribute loop-lag culprits, and serve
# profileDump/costStatus — plus the profile_merge / flightrec_merge
# profile-block tests.  CI-runnable, no TPU.
profile-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_profiling.py \
		-q -m 'not slow'

# device-telemetry smoke (docs/observability.md "Device telemetry"):
# the per-program compile/launch/transfer attribution must populate on
# the CPU backend — compile-vs-cache split, double-buffer busy union,
# deviceStatus / costStatus.device / GET /debug/device end to end,
# doctor diagnosis golden, <2% overhead.  CI-runnable, no TPU.
device-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_devicetelemetry.py \
		-q -m 'not slow'

# TPU preflight doctor (docs/observability.md): fingerprint the
# jax/jaxlib/libtpu stack, enumerate devices, compile-probe every
# program in the device-telemetry catalog, and map known failure
# signatures (no TPU, device busy, OOM) to named diagnoses.  Nonzero
# exit blocks a multi-chip rendezvous (ROADMAP item 3).  Off a TPU the
# probes run in interpret mode and the report says "interpret": true.
doctor:
	python tools/tpu_doctor.py

# the quickest proof that the node still starts on the chip: two nodes
# send and receive at network difficulty on ONE directly attached TPU
# chip, with every hidden fallback turned into a failure.  Fails at
# its first check without a TPU; one process per chip (docs/roles.md).
# `python chip_smoke.py --chips 4` runs the pod path on four chips.
chip-smoke:
	python chip_smoke.py

# role-split smoke (docs/roles.md): spawn edge+relay as REAL daemon
# subprocesses, deliver one message end to end over TCP through the
# role IPC hand-off, assert the federation pane merges both roles and
# that SIGTERM shuts both down cleanly.  CI-runnable, no TPU.
roles-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_roles_smoke.py \
		tests/test_roles.py -q

# Light-client tier regression (docs/roles.md): subscription wire
# codecs, inverted-index bounds/rebucket, DIGEST_DELTA+FETCH repair
# under churn, chaos reconnect-convergence, farm-delegated PoW tenant
# attribution and client-side trial decryption.  CI-runnable, no TPU.
clients-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_roles_clients.py -q

clean:
	$(MAKE) -C native/pow clean
	$(MAKE) -C native/secp256k1 clean
	find . -name __pycache__ -type d -exec rm -rf {} +
