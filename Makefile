# Developer entry points. The test targets run on the virtual 8-device CPU
# mesh (tests/conftest.py pins JAX_PLATFORMS=cpu); no TPU required. The
# chip is reached with `python chip_smoke.py` through the chip tool (README
# "Running").

PY ?= python
PYTEST_FLAGS ?= -q -m 'not slow' -p no:cacheprovider

# Multi-process suites: real server subprocesses (cluster boot, SPMD mesh,
# network faults, golden cluster runs). Slower and noisier than the core
# in-process suites, so they get their own target.
DISTRIBUTED = tests/test_clusterproc.py tests/test_spmd.py \
	tests/test_netfault.py tests/test_join.py \
	tests/test_golden_cluster.py tests/test_fuzz_cluster.py \
	tests/test_shardwidth_matrix.py tests/test_tls.py \
	tests/test_chip_smoke.py tests/test_crashmatrix.py

.PHONY: test test-core test-distributed test-observability test-parallel \
	test-flightrec test-devhealth test-explain test-durability \
	test-workload test-batching test-containers test-adaptive \
	test-ingest test-admission test-fusion test-incident \
	test-spmd-mesh test-meshobs lint

test: test-core test-distributed test-flightrec test-devhealth \
	test-explain test-durability test-workload test-batching \
	test-containers test-adaptive test-ingest test-admission \
	test-fusion test-incident test-spmd-mesh test-meshobs

test-core:
	$(PY) -m pytest tests/ $(PYTEST_FLAGS) \
		$(foreach f,$(DISTRIBUTED),--ignore=$(f))

test-distributed:
	$(PY) -m pytest $(DISTRIBUTED) $(PYTEST_FLAGS)

# Black-box surface: flight recorder ring, stall watchdog, HBM ledger
# exactness, kernel attribution, and the /debug endpoints serving them.
test-flightrec:
	$(PY) -m pytest tests/test_flightrec.py $(PYTEST_FLAGS)

# Device-link health surface: canary prober state machine, readiness
# gating (/readyz + query fail-fast 503), and the dispatch-phase RTT
# decomposition behind /debug/dispatch and ANALYZE actuals.
test-devhealth:
	$(PY) -m pytest tests/test_devhealth.py $(PYTEST_FLAGS)

# EXPLAIN/ANALYZE surface: plan trees, the cost model, misestimate
# flagging + the /debug/plans ring, and cluster sub-plan aggregation.
test-explain:
	$(PY) -m pytest tests/test_explain.py $(PYTEST_FLAGS)

# Durability surface: oplog unit tests (torn tails, checkpoints, fsync
# policy), the fault-injection framework, and the crash-matrix — real
# server subprocesses killed at armed fault points and restarted.
test-durability:
	$(PY) -m pytest tests/test_oplog.py tests/test_faultpoints.py \
		tests/test_crashmatrix.py $(PYTEST_FLAGS)

# Workload observatory surface: query fingerprinting + the per-shape
# stats table, the fragment heat ledger joined against HBM residency,
# and SLO error-budget burn tracking (/debug/workload|heat|slo).
test-workload:
	$(PY) -m pytest tests/test_workload.py $(PYTEST_FLAGS)

# Batching surface: GroupCommit (concurrent Counts sharing launches,
# solo-equal answers, leader failure, unbuilt buckets), the query-batch
# route, and batch= attribution in SLOW QUERY lines.
test-batching:
	$(PY) -m pytest tests/test_group_commit.py tests/test_query_batch.py \
		$(PYTEST_FLAGS)

# Query observability surface: per-query profiles, histograms, the
# slow-query log, trace retention, and the exposition formats.
test-observability:
	$(PY) -m pytest tests/test_observability.py tests/test_stats.py \
		tests/test_tracing.py $(PYTEST_FLAGS)

# Worker-pool surface: pool unit tests, the workers=1 vs workers=8
# differential corpus, and the concurrent-serving wedge guard.
test-parallel:
	$(PY) -m pytest tests/test_workpool.py \
		tests/test_workpool_differential.py \
		tests/test_workpool_serving.py $(PYTEST_FLAGS)

# Compressed container surface: representation builders/kernels, the
# per-fragment chooser, the differential corpus (compressed == dense
# bit-identity across densities, reprs, and concurrent batches), and the
# /debug compression surfaces.
test-containers:
	$(PY) -m pytest tests/test_containers.py $(PYTEST_FLAGS)

# Streaming ingest surface: the delta buffer + interval merge engine
# (flush == legacy differential across reprs, overflow back-pressure,
# crash-window replay, idle-window merge exclusion, serve-stale
# accounting) and /debug/ingest.
test-ingest:
	$(PY) -m pytest tests/test_ingest.py $(PYTEST_FLAGS)

# Adaptive execution surface: cost-model strategy/tile decisions, the
# heat×cost cache policy, proactive admission, shadow-mode A/B, the
# on==off differential corpus, and /debug/optimizer.
test-adaptive:
	$(PY) -m pytest tests/test_adaptive.py $(PYTEST_FLAGS)

# Overload-safe serving surface: request classing + deadline parsing,
# priced admission (token buckets, bounded queues), the degradation
# ladder, unified shed rejection (Retry-After + X-Pilosa-Shed), peer
# overload-vs-unready handling on fan-out, and /debug/admission.
test-admission:
	$(PY) -m pytest tests/test_admission.py $(PYTEST_FLAGS)

# Whole-plan fusion surface: the fused==interpreted differential corpus,
# single-dispatch warm queries, cold-fingerprint compile admission,
# program-cache LRU eviction, shadow A/B, and /debug/fusion.
test-fusion:
	$(PY) -m pytest tests/test_fusion.py $(PYTEST_FLAGS)

# Incident autopsy surface: cross-node trace assembly (skew-corrected
# merged span trees), anomaly-triggered postmortem bundles, /metrics
# exemplars, and the /debug/traces//incidents/threads endpoints.
test-incident:
	$(PY) -m pytest tests/test_incident.py $(PYTEST_FLAGS)

# Mesh-resident SPMD serving surface: the fast in-process units plus the
# 2-process gloo CPU mesh (marked slow, so deliberately NOT filtered by
# -m 'not slow' here): on==off==http bit-exactness over the query mix,
# warm fused queries with
# zero HTTP result bytes, step-stream lifecycle counters, and ?explain
# mesh plans.
test-spmd-mesh:
	$(PY) -m pytest tests/test_spmd_mesh.py tests/test_spmd_serve.py \
		-q -p no:cacheprovider

# Mesh observatory surface: the step-clock residual-fold invariant
# (phase sum == step wall, exactly), the bounded step ring, envelope
# clock-skew correction, the straggler-attribution oracle under
# synthetic skew, stream-gap onset events + stall accounting, and the
# collective_stall incident trigger. All fast in-process units; the
# live 2-process merged-timeline case rides in test-spmd-mesh.
test-meshobs:
	$(PY) -m pytest tests/test_meshobs.py $(PYTEST_FLAGS)

# ruff when available; otherwise fall back to a bytecode-compile pass so
# the target still catches syntax errors on a bare container (the image
# has no linters baked in and installs are not allowed).
lint:
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
		$(PY) -m ruff check pilosa_tpu tests; \
	else \
		echo "ruff not installed; falling back to compileall"; \
		$(PY) -m compileall -q pilosa_tpu tests; \
	fi
