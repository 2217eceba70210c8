# Repo-level targets. The native services build via native/Makefile.

PY ?= python
export JAX_PLATFORMS ?= cpu

.PHONY: lint test chaos bench-input bench-train bench-serve bench-serve-fleet bench-lifecycle bench-capacity bench-elastic bench-trace bench-compile bench-master-load native native-test clean

# The dogfood gate (docs/preflight.md + docs/static-analysis.md): one
# aggregate. The Python pass runs the DTL tree lint over the platform's
# own code, metric_lint (metric/span registry drift), and native_lint
# (native locking conventions, fault-point registry ↔ docs/chaos.md,
# REST routes ↔ OpenAPI). The native pass is the clang -Wthread-safety
# compile gate — `make -C native tsa` detects the compiler and skips
# with a notice when no thread-safety-capable clang is installed.
# Fails on any unsuppressed DTL finding; suppressions are in-line
# `# det: noqa[DTLnnn]` comments so they stay reviewable.
lint:
	$(PY) -m determined_tpu.analysis determined_tpu examples
	$(MAKE) -C native tsa

test:
	$(PY) -m pytest tests/ -q -m 'not slow'

# The -m slow chaos/recovery suite (docs/chaos.md, docs/checkpointing.md,
# docs/cluster-ops.md "Preemption & drain"): SIGKILL-mid-save lineage
# fallback, watchdog-driven restarts, master/agent kills, 5xx storms, and
# the spot-preemption drain → emergency checkpoint → reschedule e2e.
# Bounded so a wedged recovery path fails the target instead of hanging CI.
CHAOS_TIMEOUT ?= 1800
chaos:
	timeout -k 30 $(CHAOS_TIMEOUT) $(PY) -m pytest \
		tests/test_chaos.py tests/test_selfheal.py tests/test_preemption.py \
		tests/test_serving.py tests/test_deployments.py tests/test_elastic.py \
		tests/test_observability.py tests/test_compile_farm.py \
		tests/test_fencing.py tests/test_overload.py \
		-q -m slow

# Async input pipeline A/B: prefetch on/off step time + input_wait_ms
# (docs/trial-api.md "Data loading and the async input pipeline").
bench-input:
	$(PY) bench.py --only input

# Training-attention A/B (docs/training-perf.md): dense -> flash(f32) ->
# flash(bf16) -> flash+overlap, interleaved on this machine's mesh
# (numerics gates; off a TPU the flash legs run interpreted).
bench-train:
	$(PY) bench.py --only train_attn

# Serving throughput/latency: continuous batching vs the sequential
# one-request-at-a-time baseline on the same checkpoint
# (docs/serving.md "Latency tuning"). Emits serve_tokens_per_s,
# serve_p50_ms, serve_p99_ms.
bench-serve:
	$(PY) bench.py --only serve

# Fleet serving (docs/serving.md "Deployments & autoscaling"): a
# 2-replica deployment behind the master router vs a single replica on
# the same checkpoint — gates routed throughput >= 1.8x single-replica —
# plus a rolling drain under load proving zero dropped accepted requests.
# Emits serve_fleet_tokens_per_s, serve_fleet_drain_dropped.
bench-serve-fleet:
	$(PY) bench.py --only serve_fleet

# Model lifecycle (docs/serving.md "Model lifecycle"): a rolling
# blue-green weight swap under sustained load (spawn-at-new before
# drain-at-old; gate: ZERO dropped accepted requests) and a 10% canary
# split whose OBSERVED traffic fraction must land within ±5 points of
# the configured fraction, with canary-vs-stable p50/p99 reported from
# the per-version latency aggregation. Emits lifecycle_swap_dropped,
# lifecycle_canary_observed_fraction.
bench-lifecycle:
	$(PY) bench.py --only lifecycle

# Closed capacity loop (docs/cluster-ops.md "Capacity loop"): a diurnal
# traffic replay against the fake TPU API — the fleet grows nodes from
# composed demand, loses every spot agent mid-plateau (drained inside
# the notice deadline), shrinks back to ZERO nodes, then cold-starts
# from zero within cold_start_budget_s on the warm-AOT path. Gates:
# node count rises and falls, spot drains in deadline, cold start in
# budget with engine_source=deserialize, dropped accepted requests == 0.
bench-capacity:
	$(PY) bench.py --only capacity

# Elastic re-meshing: resize downtime (signal -> first post-resize step)
# vs the restart-from-checkpoint requeue baseline for the same drain
# (docs/elasticity.md). Emits elastic_resize_downtime_s.
bench-elastic:
	$(PY) bench.py --only elastic

# Compile farm A/B (docs/compile-farm.md): nocache vs persistent-cache vs
# farm arms of compile-bound trials on a devcluster. Gates the headline
# metric cached_median_compile_s <= 0.5s (ROADMAP item 5: recompilation
# eliminated as a per-trial cost) and reports the farm on/off trials/hour
# delta.
bench-compile:
	$(PY) bench.py --only compile

# Observability overhead + throughput (docs/observability.md): step_ms
# with lifecycle tracing on vs off (the <1% always-on gate) and span-
# ingest throughput on the real master under concurrent batched POSTs.
bench-trace:
	$(PY) bench.py --only trace

# Master overload bench (docs/cluster-ops.md "Overload, quotas & fair use"):
# thousands of short-trial writers + concurrent list/read pollers + one
# adversarial tenant against the real master. Gates: group-commit cuts
# hot-path DB transactions >= 5x (COUNTED via det_master_db_tx_total, not
# timed), write p99 stays under gate at 1k+ trials with readers attached,
# db.tx.stall loses and duplicates ZERO metric reports (idempotent retry
# through the batch queue), and a tenant at 10x its fair share cannot move
# a well-behaved tenant's p99 past the solo gate while trial-critical
# routes never shed (det_master_shed_total for that family stays 0).
bench-master-load:
	$(PY) bench_asha.py --master-load

native:
	$(MAKE) -C native

native-test:
	$(MAKE) -C native test

clean:
	$(MAKE) -C native clean
