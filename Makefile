# Tier-1 tests + quick allocator smoke — run `make ci` per PR so
# regressions (correctness or allocation optimality) are caught early.
# `ci-sharded` replays the tier-1 suite under 8 fake XLA host devices,
# exercising the camera-mesh shard_map fleet paths.
# `ci-episode` is the whole-trace lane: episode runs execute under
# jax.transfer_guard("disallow") in BOTH directions with NO scoped per-slot
# exemptions (the guard wraps the entire timed episode inside
# fleet_episode), on the 8-device mesh plus the 4-device subprocess
# harness; the fetch counters must show zero 'keep'/'control' and exactly
# TWO harvest fetches per run (the stacked F1/size pack + the stacked
# control pack, slot-count independent).
# `ci-scenarios` replays the scenario matrix (cross-mode differential
# harness, trace-length bucketing, golden logs) on the 8-device mesh in the
# harness's quick mode (reduced family set).
# `ci-faults` is the fault-tolerance lane: traced camera-churn/link-fault
# episodes (the dead==absent differential across all methods, runner
# modes and the stream runner), the checkify-guarded diagnostics runs
# (SystemConfig.checked on, invariant violations must raise), and the
# watchdog unit tests.  Runs WITHOUT fake devices: the checked lane forces
# shard off.
# `ci-serve` is the continuous-serving lane: the windowed stream runner's
# kill-and-resume differential (SIGTERM + injected exception, all methods,
# zero recompiles after restore), the SLO watchdog ladder, checkpoint
# crash-atomicity, and the bounded-queue/drain-budget regressions.
# `ci-audit` is the STATIC lane (<2 min, nothing executes an episode): the
# traced-scope source lint, the jaxpr invariant audit (no host callbacks in
# timed scopes, slot-step donation, two-harvest episode outputs, fleet-size-
# independent PRNG fold-in, method x bucket executable count), and the full
# compiled-manifest golden check (signatures + static flops/bytes/peak
# memory vs tests/golden/executable_manifest.json).  Runs WITHOUT fake
# devices: the manifest pins single-device lowerings.
# `ci-chaos` is the seeded chaos lane: the deterministic fault-injection
# soak (ft/chaos.py) replays a scheduled storm — checkpoint bit-flips,
# truncation, torn manifests, save latency, source stalls/timeouts,
# mid-window exceptions, SIGTERM, duplicate/out-of-order/gap delivery and
# poisoned bandwidth records — against the windowed stream runner behind
# the hardened ingest path (serve/ingest.py).  Asserts the recovered logs
# match the fault-free run to <= 1e-5 for all four methods with ZERO
# episode recompiles after recovery, exact quarantine/gap-fill accounting,
# and that restore demonstrably falls back past corrupted newest
# generations to the newest valid one.  REPRO_CHAOS_HEADLINE_SLOTS=1000
# additionally enables the 1000-slot headline soak (retention-bounded
# checkpoint store + peak-RSS ceiling).  Runs WITHOUT fake devices, like
# ci-serve.
# `ci-pipeline` is the episode fast-path lane: the software-pipelined scan
# body's differential vs the straight-line reference body (all methods,
# with and without camera-churn faults, <= 1e-5), the zero-recompile /
# two-fetch harvest contracts on the pipelined path, the manifest
# cost_analysis dead-compute proofs (padded tail slots and the dropped
# reuse arm cost zero static flops), and the full kernel parity suite
# (edge_motion, flash_decode, knapsack_dp, tx_codec ops-vs-ref-vs-
# interpret).  Runs under 8 fake host devices like ci-episode.
# Lane pytest selections live ONCE, in tests/harness.py (LANES) — the lanes
# shell out to it instead of duplicating test lists here.
PY := PYTHONPATH=src python

.PHONY: test bench-quick ci ci-sharded ci-episode ci-scenarios \
	ci-faults ci-serve ci-audit ci-chaos ci-pipeline

test:
	$(PY) -m pytest -q

bench-quick:
	$(PY) -m benchmarks.run --quick --only bench_allocation

ci-sharded:
	REPRO_FAKE_DEVICES=8 $(PY) -m pytest -q

ci-episode:
	REPRO_FAKE_DEVICES=8 $(PY) tests/harness.py --lane episode

ci-scenarios:
	REPRO_FAKE_DEVICES=8 REPRO_SCENARIO_QUICK=1 $(PY) tests/harness.py \
		--lane scenarios

ci-pipeline:
	REPRO_FAKE_DEVICES=8 $(PY) tests/harness.py --lane pipeline

ci-faults:
	$(PY) tests/harness.py --lane faults

ci-serve:
	$(PY) tests/harness.py --lane serve

ci-audit:
	$(PY) -m repro.analysis.lint
	$(PY) -m repro.analysis.jaxpr_audit --quiet
	REPRO_AUDIT_FULL=1 $(PY) tests/harness.py --lane audit

ci-chaos:
	REPRO_CHAOS_HEADLINE_SLOTS=1000 $(PY) tests/harness.py --lane chaos

ci: test bench-quick ci-sharded ci-episode ci-scenarios ci-faults \
	ci-serve ci-audit ci-chaos ci-pipeline
