# One-command build + test entry point (the reference's CI does the same
# four steps: build all targets, test, fmt, lint — .github/workflows/rust.yml).
# .github/workflows/check.yml runs `make native lint test-ci` on every push.
#
#   make check     build the native data plane, lint, then run the test suite
#   make lint      syntax-compile every source tree (+ flake8 when installed)
#   make native    build native/libnarwhal_dp.so only
#   make bench     one driver benchmark run (prints the JSON line)
#   make clean     remove build products and bench scratch

PYTHON ?= python

.PHONY: check native lint lint-invariants test test-ci metrics-smoke \
	trace-smoke fault-smoke fault-fuzz-smoke trajectory race-explore \
	sim-smoke wire-ab-smoke crypto-ab-smoke commit-rule-smoke \
	cert-scheme-smoke knee-matrix knee-smoke sanitize bench clean

check: native lint test

native:
	$(MAKE) -C native

lint:
	$(PYTHON) -m compileall -q narwhal_tpu benchmark tests bench.py \
		bench_cadence.py bench_crypto.py \
		__graft_entry__.py
	@if $(PYTHON) -c "import flake8" 2>/dev/null; then \
		$(PYTHON) -m flake8 --select=F,E9 --extend-ignore=F401 \
			narwhal_tpu benchmark tests; \
	else \
		echo "flake8 not installed; syntax compile check only"; \
	fi
	$(PYTHON) -m narwhal_tpu.analysis

# Invariant linter alone, with the JSON findings report for the CI
# artifact upload (the `lint-invariants` job): AST rules over
# narwhal_tpu/ + benchmark/ — no-blocking-in-async, task-retention,
# wire-type coverage, metric-name drift, env-var registry + README
# env-table drift.  Nonzero exit on any non-pragma'd finding.
lint-invariants:
	mkdir -p .ci-artifacts
	$(PYTHON) -m narwhal_tpu.analysis \
		--report .ci-artifacts/lint-invariants.json

test:
	$(PYTHON) -m pytest tests/ -x -q

# CI variant: CPU backend pinned, tier-1 subset, no -x so one flaky test
# doesn't mask the rest of the report.
test-ci:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors

# Standalone in-process pipeline metrics test (4-node committee in one
# process; asserts sealed==committed+dropped and monotonic stage stamps),
# then a live-node /healthz probe: boots a real `node run` process with
# --metrics-port and fails on anything but 200 with zero firing rules.
# Dumps the final registry snapshot to .ci-artifacts/metrics-smoke.json,
# which CI uploads as a workflow artifact.
metrics-smoke: native
	JAX_PLATFORMS=cpu NARWHAL_METRICS_DUMP=.ci-artifacts \
		$(PYTHON) -m pytest tests/test_metrics_pipeline.py -x -q
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/health_smoke.py

# Committee flight-recorder + trace-export smoke (ISSUE 11): drive the
# health-bench clean run (4-node local_bench with --trace-out) and drop
# the exported Perfetto trace, the quiesce flight rings, the scraped
# timeline, and the critical-path/straggler/clock artifact into
# .ci-artifacts/ for the workflow upload.  The test itself round-trips
# the trace (8 process rows, ≥1 cross-process digest flow, sampled-CPU
# track, committee critical-path row), asserts every node's flight ring
# is populated, and gates a non-empty critical_path whose per-leg sums
# telescope to the e2e span within 10%.
trace-smoke:
	JAX_PLATFORMS=cpu NARWHAL_METRICS_DUMP=.ci-artifacts \
		$(PYTHON) -m pytest tests/test_health_bench.py -x -q

# Fault-injection smoke: the two CI scenarios (one Byzantine, one
# crash/restart) through the scenario runner, each gated on the three
# machine-checked verdicts (safety/liveness/detection) plus the
# zero-false-positive control arm.  Artifacts in .ci-artifacts/.
fault-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/fault_bench.py \
		--scenario benchmark/scenarios/byz_wrong_key.json \
		--scenario benchmark/scenarios/crash_restart.json \
		--artifact '.ci-artifacts/fault-{name}.json'

# Worker-plane + fuzz smoke: one worker-plane Byzantine scenario, one
# multi-fault composition, and a bounded fuzz run (three fixed seeds
# through narwhal_tpu/faults/fuzz.py — each generated scenario is dumped
# as a replayable .spec.json beside its artifact), all three-verdict
# gated with clean-control arms.  Artifacts in .ci-artifacts/.
fault-fuzz-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/fault_bench.py \
		--scenario benchmark/scenarios/byz_sync_flood.json \
		--scenario benchmark/scenarios/compose_equivocate_wan_lossy.json \
		--fuzz-seed 101 --fuzz-seed 202 --fuzz-seed 303 \
		--artifact '.ci-artifacts/fault-{name}.json'

# Cross-revision perf-trajectory gate (benchmark/trajectory.py): reads
# every BENCH_r*.json + recognizable artifacts/ bench capture, renders
# the revision series, and exits nonzero on any regression beyond the
# tolerances pinned in benchmark/trajectory_gate.json that no waiver
# names.  The rendered report lands in .ci-artifacts/ for upload.
trajectory:
	mkdir -p .ci-artifacts
	$(PYTHON) benchmark/trajectory.py \
		--report .ci-artifacts/trajectory.json

# narwhal-race schedule explorer (ISSUE 10): 16 seeded schedules of the
# reference pipeline scenario must commit byte-identically to the golden
# walk (plus a same-seed reproducibility pin), the socketed 4-node
# committee arm must pass its golden-replay + cross-node-prefix safety
# verdicts per seed, and the planted RacyConsensus race must be caught
# by BOTH the static interleave rule and a divergent schedule (the
# non-vacuity gate).  Divergent seeds dump `*.repro-<seed>.json` repros
# next to the artifact; replay one with
# `python benchmark/race_explore.py --repro <seed> [--mutated]`.
race-explore:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/race_explore.py \
		--seeds 16 --committee-seeds 4 \
		--artifact .ci-artifacts/race-explore.json

# Deterministic committee-at-scale simulation sweep (ISSUE 12): ≥200
# fuzzed (seed × fault × committee-size) points — sizes 4/7/10/20, at
# least one N=20 — run single-process on the virtual clock and judged
# by the three-verdict engine (golden-replay safety, virtual-time
# liveness, health-rule detection), plus per-size clean controls (zero
# firings), a same-seed bit-reproducibility pin, the planted-mutation
# honesty arms (RacyConsensus + stripped-expectation Byzantine), and
# the N=20/60-virtual-second acceptance arm whose wall-clock
# compression ratio is measured and gated.  Failing points dump
# replayable (seed, spec) repro files beside the artifact; replay one
# with `python benchmark/sim_bench.py --replay <file>`.
sim-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/sim_bench.py \
		--points 200 --artifact .ci-artifacts/sim-smoke.json --quiet

# Paired interleaved wire-format A/B (ISSUE 13): legacy
# (NARWHAL_WIRE_V2=0) vs v2 arms on a short 4-node local_bench,
# ledger-read gates — v2 goodput_ratio >= 0.45 at committed TPS no
# worse than the legacy arm (within the shared-host noise floor),
# sender_coverage ≈ 1.0 and protocol_check within 5% on BOTH arms.
# The before/after artifact is uploaded by the workflow.
wire-ab-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/wire_ab.py \
		--pairs 2 --duration 8 \
		--artifact .ci-artifacts/wire-ab.json

# Paired interleaved crypto A/B (ISSUE 14): serial per-burst verify
# (cpu backend, window off) vs the batched arm (verify-batch window on;
# --batched-backend cpu on deviceless CI runners — the window deepening
# is backend-independent, and the jax kernel's verdicts are covered by
# tests/test_backend_differential.py).  Ledger-read gates: zero errors
# + protocol_check within 5% on BOTH arms, and the batched arm's
# crypto.verify.batch_size.batch_burst mean >= the serial arm's at
# committed TPS no worse than the noise floor.
crypto-ab-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/crypto_ab.py \
		--pairs 2 --duration 8 --batched-backend cpu \
		--min-batch-mean 0 \
		--artifact .ci-artifacts/crypto-ab.json

# Commit-rule smoke (ISSUE 15; ISSUE 19 adds the multileader arm): the
# non-classic rules' full validation ladder in CI-affordable sizes —
# (a) the equivalence + flag-plumbing suites (each live rule
# byte-identical to ITS frozen oracle, classic byte-identical to
# GoldenTusk, cross-rule checkpoint refusal in all six directions,
# audit rule markers); (b) one race-explore run per non-classic rule:
# 16 seeded schedules byte-identical to that rule's oracle + the
# socketed committee replay verdicts + the planted race caught; (c) a
# sim flag-flip mini-sweep (--commit-rule all): every fuzzed point,
# control, mutation and acceptance arm under EACH of the three rules,
# three verdicts per arm, per-arm virtual-time cert→commit means in
# the artifact.  The full-size flag-flip sweep (200 points) is the
# release gate run manually; this keeps every arm of it exercised per
# push.
commit-rule-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest \
		tests/test_lowdepth_equivalence.py \
		tests/test_multileader_equivalence.py -x -q
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/race_explore.py \
		--seeds 16 --committee-seeds 2 --commit-rule lowdepth \
		--workdir .race_explore_lowdepth \
		--artifact .ci-artifacts/race-explore-lowdepth.json
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/race_explore.py \
		--seeds 16 --committee-seeds 2 --commit-rule multileader \
		--workdir .race_explore_multileader \
		--artifact .ci-artifacts/race-explore-multileader.json
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/sim_bench.py \
		--points 20 --commit-rule all --mutation-seeds 8 \
		--workdir .sim_commit_rule \
		--artifact .ci-artifacts/sim-commit-rule-flip.json --quiet

# Certificate-signature-scheme smoke (ISSUE 20): the frozen
# differential/refusal suite (halfagg must never accept what
# individual rejects; cross-scheme frames and checkpoints refuse
# loudly), then the paired per-scheme N=20 sim wire captures gated on
# the half-aggregation floor — exactly 1 verify op/cert, sig fraction
# <= 0.5, cert bytes/frame < 0.75x individual.  The gate driver's
# docstring explains why the thresholds are NOT the ISSUE's 0.25/0.6
# (those price a pairing aggregate; no pairing library in-container).
cert-scheme-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_cert_scheme.py -x -q
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/cert_scheme_gate.py \
		--nodes 20 \
		--artifact .ci-artifacts/cert_scheme_gate_n20.json

# Saturation-knee matrix (ISSUE 17): sweep offered load across
# committee sizes (socketed N=4, sim N=10/20), locate each config's
# TPS/latency knee, and name the first-saturating inter-task channel
# at the knee from the InstrumentedQueue accounting.  The full matrix
# is a release artifact (artifacts/knee_matrix_<rev>.json); knee-smoke
# is the 2-point N=4 CI arm, gated on a non-empty queue attribution.
knee-matrix: native
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/knee_matrix.py

knee-smoke:
	mkdir -p .ci-artifacts
	JAX_PLATFORMS=cpu $(PYTHON) benchmark/knee_matrix.py \
		--smoke --duration 8 \
		--out .ci-artifacts/knee-smoke.json

# Asyncio sanitizer tier (ISSUE 10): the fast concurrency-sensitive
# tier-1 subset under `python -X dev` — asyncio debug mode with the
# slow-callback threshold aligned to the PR 9 watchdog default
# (NARWHAL_LOOP_WATCHDOG_MS=100 arms it on node-booting tests, and
# loop.slow_callback_duration follows it), plus ResourceWarning
# escalated to an error: an unclosed socket/file surfacing at GC is a
# task-teardown bug, not noise.
sanitize:
	JAX_PLATFORMS=cpu NARWHAL_LOOP_WATCHDOG_MS=100 \
		$(PYTHON) -X dev -W error::ResourceWarning -m pytest \
		tests/test_store.py tests/test_tasks.py \
		tests/test_sync_timeouts.py \
		tests/test_checkpoint_under_load.py tests/test_schedule.py \
		tests/test_interleave.py -q

# The crypto differential suite under the float32 lane dtype (the default
# run covers int32 + a narrow f32 subprocess check; run this after any
# change to narwhal_tpu/ops/field25519.py or ed25519.py).
test-f32:
	NARWHAL_FIELD_DTYPE=float32 $(PYTHON) -m pytest \
		tests/test_field25519.py tests/test_ed25519.py -x -q

bench: native
	$(PYTHON) bench.py

clean:
	$(MAKE) -C native clean
	rm -rf .bench .bench_remote .bench_wire_ab .bench_crypto_ab \
		.bench_commit_rule_ab .race_explore_lowdepth \
		.race_explore_multileader .sim_commit_rule \
		.sim_crypto_ab .sim_wire_capture .pytest_cache .ci-artifacts
