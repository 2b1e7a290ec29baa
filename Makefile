# kube-scheduler-simulator_tpu build/test entry points.
#
# The framework is pure Python + JAX except the native annotation codec
# (kube_scheduler_simulator_tpu/native/annotation_codec.cpp), which the
# loader also auto-builds on first use; `make codec` is the explicit
# recipe.

PY ?= python

.PHONY: codec native-asan native-tsan test test-asan test-tsan analyze \
        bench-soak blackbox-smoke obs-smoke \
        chip-smoke chip-smoke-rehearsal chaos \
        clean \
        parity-fullscale parity-fullscale-device

# full-scale byte-parity of BASELINE configs 4 and 5 against the
# sequential oracle (PARITY.md "The parity protocol"; ~20 min on the
# CPU); the verdict lands in chiprun_out/, which git ignores
parity-fullscale:
	JAX_PLATFORMS=cpu $(PY) tools/parity_fullscale.py

# the same ON the device backend; needs a chip
# (the chip tool: chiprun -- make parity-fullscale-device)
parity-fullscale-device:
	$(PY) tools/parity_fullscale.py \
	    chiprun_out/parity_fullscale_device.json --device

# CI-enforceable autopilot soak gate (docs/autopilot.md): sustained
# multi-session churn + overload against a live server with the
# controller ON — the standard tenant's p99 stays inside the SLO
# target, every shed response carries Retry-After, the shed lifts when
# the overload stops, and the degradation ladder recovers to rung 0
bench-soak:
	JAX_PLATFORMS=cpu $(PY) -m tools.soak /tmp/bench_soak.json
	$(PY) -c "import json; d = json.load(open('/tmp/bench_soak.json')); \
	    assert d['ok'], d['failures']; \
	    assert d['soak_p99_wave_seconds'] <= d['slo_target_p99_s'], \
	        'std p99 %.3fs over target' % d['soak_p99_wave_seconds']; \
	    assert d['all_shed_had_retry_after'], 'shed without Retry-After'; \
	    assert d['soak_recovered_to_rung0'], 'ladder pinned degraded'; \
	    assert d['history_breach_before_shed'] and d['history_shed_lift_recorded'], \
	        'breach->shed->recovery not reconstructible from the history ring'; \
	    assert d['shed_evidence_checked'] >= 1, 'no shed evidence checked against the ring'; \
	    print('bench-soak: ok=true (p99 %.3fs, shed rate %.2f, %d decisions, %d evidence rows ring-checked)' \
	        % (d['soak_p99_wave_seconds'], d['soak_shed_rate'], \
	           d['autopilot']['decisions'], d['shed_evidence_checked']))"

codec:
	$(PY) -c "from kube_scheduler_simulator_tpu.native import build_codec; print(build_codec())"

# sanitizer build of the codec (address+undefined); the slow test in
# tests/test_native_asan.py runs the codec suite against it via
# KSS_TPU_NATIVE_SO + LD_PRELOAD of the ASan runtime
native-asan:
	$(PY) -c "from kube_scheduler_simulator_tpu.native import build_codec, ASAN_FLAGS; print(build_codec('kube_scheduler_simulator_tpu/native/_annotation_codec_asan.so', extra_flags=ASAN_FLAGS))"

test-asan:
	$(PY) -m pytest tests/test_native_asan.py -q -m slow

# ThreadSanitizer build of the codec; the slow test in
# tests/test_native_tsan.py runs the 4-thread concurrent chunk-decode
# soak against it (suppressions scope TSan to the codec's own threads —
# see native/tsan_suppressions.txt and docs/static-analysis.md)
native-tsan:
	$(PY) -c "from kube_scheduler_simulator_tpu.native import build_codec, TSAN_FLAGS; print(build_codec('kube_scheduler_simulator_tpu/native/_annotation_codec_tsan.so', extra_flags=TSAN_FLAGS))"

test-tsan:
	$(PY) -m pytest tests/test_native_tsan.py -q -m slow

# the kss-analyze static suite (docs/static-analysis.md): lock
# discipline, device purity, observability conformance.  Pure AST — no
# JAX import, no device; exits nonzero on any finding not suppressed
# in-source or grandfathered in tools/analysis/baseline.json
analyze:
	$(PY) -m tools.analysis

# wave black-box smoke gate (docs/metrics.md post-mortem dumps): arm a
# one-rule fault plan via KSS_TPU_FAULT_PLAN, run a wave with the retry
# budget at 0, and assert a schema-valid post-mortem dump lands in
# KSS_TPU_BLACKBOX_DIR (fault trip + protocol action + counter
# deltas + device fingerprint) — a crashed wave must ship its
# own evidence
blackbox-smoke:
	JAX_PLATFORMS=cpu $(PY) -m tools.blackbox_smoke

# causal-telemetry smoke gate (docs/metrics.md "History & correlation"):
# run one faulted wave under an explicit trace id and assert the id
# threads the tracer spans, the post-mortem dump's events, and the
# Perfetto export (spans + black-box instants), and that the dump's
# embedded history window validates — one trace id, every surface
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) -m tools.obs_smoke

# the quickest proof that the served path starts on the chip
# (README "On the chip"): needs a TPU and fails without one — run it
# through the chip tool, `chiprun -- make chip-smoke`.  One process per
# chip; output in chiprun_out/chip_smoke/
chip-smoke:
	$(PY) chip_smoke.py

# the same script's explicit CPU rehearsal at toy size (every phase, one
# wave per profile) — slow-marked, so `make test` runs it here beside
# the other smokes instead of inside tier-1
chip-smoke-rehearsal:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chip_smoke.py -q -m slow

test: analyze blackbox-smoke obs-smoke chip-smoke-rehearsal
	$(PY) -m pytest tests/ -q -m "not slow"

# chaos gate (docs/fault-injection.md): concurrent multi-session waves
# under seeded fault plans at every seam, asserting completion via
# retry/degradation, bit-identical annotations vs the fault-free run,
# gang atomicity, per-session isolation, and no lock-order cycles under
# the runtime witness.  Deterministic: a failure prints the seed and
# the exact reproducing command.  Also runs as the slow-marked tier-2
# suite tests/test_chaos.py.
chaos:
	KSS_TPU_LOCK_WITNESS=1 JAX_PLATFORMS=cpu $(PY) -m tools.chaos --seeds 3

clean:
	rm -f kube_scheduler_simulator_tpu/native/_annotation_codec.so \
	    kube_scheduler_simulator_tpu/native/_annotation_codec_asan.so \
	    kube_scheduler_simulator_tpu/native/_annotation_codec_tsan.so
	find . -name __pycache__ -type d -exec rm -rf {} +
