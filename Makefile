# Native targets for the shared-memory object store.
#
# Reference: the reference wires TSAN/ASAN as first-class build configs
# (.bazelrc:92-111) run in CI (ci/ci.sh:356); here the sanitizer
# workload is src/shm_store_stress.cc (8 threads of mixed
# alloc/seal/abort/get/release/delete/evict against one arena).
#
#   make store           # the production .so (also built lazily at import)
#   make store-tsan      # ThreadSanitizer stress run
#   make store-asan      # AddressSanitizer+UBSan stress run
#   make sanitize        # both

CXX ?= g++
CXXFLAGS ?= -std=c++17 -O2
BUILD := build
PY ?= python
# verify's recipe uses pipefail, which POSIX sh (dash) rejects.
SHELL := /bin/bash

.PHONY: store store-tsan store-asan sanitize clean lint \
	lint-concurrency-strict verify check \
	bench-quick bench-llm-quick bench-llm-tier-quick bench-transfer \
	bench-collective \
	bench-collective-quick bench-control bench-control-quick \
	bench-serve-scale bench-serve-scale-quick bench-data \
	bench-data-quick bench-trace bench-trace-quick bench-train \
	bench-train-quick bench-autopilot bench-autopilot-quick \
	chaos chaos-smoke

# --- static + dynamic correctness gates -------------------------------
# lint: the AST-based distributed-correctness self-check (RTL001-008
# API misuse + RTC101-104 concurrency: lock discipline, package-wide
# lock-order cycles, blocking-under-lock, thread escape) over our own
# tree; fails on any finding NOT in .rtlint-baseline.json.
# verify: the tier-1 test command from ROADMAP.md.
# bench-quick: <60 s hot-path probe — ray_perf --quick on the RPC
# hot-path metrics + the serve overhead probe — so a submission/dispatch
# regression surfaces before a full bench round.  bench-llm-quick: the
# serve.llm twin (paged vs slot smoke).  check: all of them.

lint:
	$(PY) -m ray_tpu.lint ray_tpu examples tests \
		--baseline .rtlint-baseline.json

# Nightly strict concurrency leg: RTC baseline entries count ONLY when
# they carry a justification string in the baseline's "reasons" map
# (an unjustified count bump fails), and the ThreadSanitizer store
# stress runs in the same leg — the static analyzer and the dynamic
# race detector cover each other's blind spots.
lint-concurrency-strict: $(BUILD)/store_stress_tsan
	$(PY) -m ray_tpu.lint ray_tpu examples tests --jobs 4 \
		--select RTC101,RTC102,RTC103,RTC104 \
		--baseline .rtlint-baseline.json --strict-reasons
	$(BUILD)/store_stress_tsan

verify:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
		-m 'not slow' --continue-on-collection-errors \
		-p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
		| tee /tmp/_t1.log

bench-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) -m ray_tpu._private.ray_perf --quick \
		--only single_client_tasks_sync,actor_calls_1_1,put_small_1kb
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) -m ray_tpu._private.serve_perf --probe

# <60 s paged-vs-slot serve.llm smoke (smoke sizing; HEADLINE line
# last): catches a paged-attention / prefix-cache / speculation
# regression in the serving hot path before a full bench round.  Does
# NOT touch the checked-in BENCH_serve_llm.json.
bench-llm-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite serve_llm --quick

# <60 s KV-tiering smoke (smoke sizing; HEADLINE last): sessions held
# per GB of decode-pool memory with tiering on vs off at equal pool
# bytes, plus store-resurrect vs re-prefill resume latency with the
# greedy-parity check in-bench.  Does NOT touch BENCH_serve_llm.json.
bench-llm-tier-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite serve_llm_tier --quick

# Object transfer plane GB/s (pull/push, striped, vs stop-and-wait
# baseline); refreshes the checked-in BENCH_transfer.json artifact.
bench-transfer:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite transfer --json-out BENCH_transfer.json

# Host collectives on the transfer plane: world-4 allreduce bus GB/s
# per data plane (one-sided/scratch/wire vs the legacy put/get store
# ring baseline), bucket fusion, small-tensor latency, cross-plane
# bit-parity.  Refreshes the checked-in BENCH_collective.json.
bench-collective:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite collective \
		--json-out BENCH_collective.json

# <60 s collective smoke (small sizes, fast vs store only; HEADLINE
# last): catches a collective fast-path regression before a full bench
# round.  Does NOT touch the checked-in BENCH_collective.json.
bench-collective-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite collective --quick

# Control-plane scaling curves: coalesced-vs-legacy pubsub broadcast
# throughput over subscriber counts, indexed-vs-rescan scheduling
# decisions over simulated node counts, actor creations/sec + lease
# grant latency at queue depth, node-view convergence after churn.
# Refreshes the checked-in BENCH_control_plane.json.
bench-control:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite control_plane \
		--json-out BENCH_control_plane.json

# <60 s control-plane smoke (smaller sub/node counts; HEADLINE last):
# catches a pubsub-coalescing or scheduling-index regression before a
# full bench round.  Does NOT touch the checked-in artifact.
bench-control-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite control_plane --quick

# Multi-replica serving chaos-soak: concurrent greedy streams across N
# real replicas, then the same soak with CHAOS ARMED (replica kill
# mid-stream, slow/faulted stream RPCs, GCS black-hole window) and a
# per-tenant QoS leg (hot tenant floods, cold tenant stays fast).
# Asserts zero hung streams, greedy parity across failovers, exact shed
# accounting, and cold-tenant p99 TTFT within 2x of chaos-off.
# Refreshes the checked-in BENCH_serve_scale.json.
bench-serve-scale:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite serve_scale \
		--json-out BENCH_serve_scale.json

# <90 s serve-scale smoke (2 replicas, smaller soak; HEADLINE last):
# the same hung-stream / failover-parity / shed-accounting assertions
# as the full soak plus the prefix-affinity and KV-migration legs
# (quick gates on affinity-hit coverage + prefill collapse; the TTFT
# magnitude gate runs in the full suite).  Does NOT touch the
# checked-in artifact.
bench-serve-scale-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite serve_scale --quick

# Streaming data plane: transfer-plane shuffle GB/s vs the legacy
# push-round baseline (asserts >= 2x at 64MiB partitions), streaming
# iteration rows/s + O(block) driver heap vs bulk's O(dataset), map
# locality on/off, train-ingest overlap win.  Refreshes the checked-in
# BENCH_data.json.
bench-data:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite data --json-out BENCH_data.json

# <60 s data-plane smoke (small blocks; HEADLINE last): exercises the
# streaming executor, the exchange, the memory/row-count invariants and
# the ingest wrapper before a full bench round.  Does NOT touch the
# checked-in artifact.
bench-data-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite data --quick

# Always-on tracing overhead A/B (record() ns, RPC hot path, serve
# streaming soak; paired on/off windows, median statistic).  ASSERTS
# overhead <= 5% on both system legs.  Refreshes BENCH_trace.json.
bench-trace:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite trace --json-out BENCH_trace.json

# <60 s tracing-overhead gate for make check: same paired A/B at smoke
# sizing, same <= 5% assertion.  Does NOT touch the checked-in artifact.
bench-trace-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite trace --quick

# End-to-end train plane: gradient-hook overlap (GradientSynchronizer
# vs post-backward allreduce vs compute-only at 64MiB fp32 gradients;
# asserts the overlapped step <= 1.15x compute-only) and elastic
# member-death recovery wall time vs the cold checkpoint-restart
# baseline, with the metric-series continuity record.  Refreshes the
# checked-in BENCH_train_e2e.json.
bench-train:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite train_e2e \
		--json-out BENCH_train_e2e.json

# <60 s train-plane smoke (16MiB gradients, shorter chaos leg; same
# overlap and never-reset-to-zero assertions at smoke bounds): catches
# a gradient-overlap or elastic-recovery regression before a full
# bench round.  Does NOT touch the checked-in artifact.
bench-train-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 120 \
		$(PY) bench.py --suite train_e2e --quick

# Cluster autopilot soak: serve + elastic train gang + data soak share
# one fixed-capacity cluster under the SLO arbiter while a traffic
# spike replays.  Asserts the gang shrinks elastically (zero cold
# restarts, loss series continuous), serve p99 TTFT returns within SLO
# late in the spike, the data lease revokes within grace and re-soaks
# only after the gang is whole, and mean utilization stays > 80%.
# Refreshes the checked-in BENCH_autopilot.json.
bench-autopilot:
	env JAX_PLATFORMS=cpu timeout -k 10 600 \
		$(PY) bench.py --suite autopilot \
		--json-out BENCH_autopilot.json

# <60 s autopilot smoke (shorter phases, same gates): catches an
# arbitration-policy or lease-backpressure regression before a full
# soak.  Does NOT touch the checked-in artifact.
bench-autopilot-quick:
	env JAX_PLATFORMS=cpu timeout -k 10 150 \
		$(PY) bench.py --suite autopilot --quick

# --- chaos battery ----------------------------------------------------
# Seeded, deterministic message-level fault injection
# (tests/test_failpoints.py + the dup-dedup satellites).  Every run
# prints its seed up front and again on failure, so any red run
# replays EXACTLY with:  make chaos CHAOS_SEED=<printed seed>
# Simply-expanded (:=) behind an origin guard: `?=` stays recursive,
# so every recipe line would re-roll $RANDOM and the banner seed
# would not be the seed the tests actually ran with.
ifeq ($(origin CHAOS_SEED),undefined)
CHAOS_SEED := $(shell bash -c 'echo $$RANDOM')
endif

# ('not nightly', not 'not slow': the collective member-kill/destroy
# scenarios are slow-marked to keep tier-1 inside its budget, but they
# ARE the chaos battery's collective coverage.)
# RT_LOCK_SANITIZER=1: every locksan-wrapped lock records acquisition
# order during the battery; tests/conftest.py fails any test that
# records a lock-order violation (the dynamic half of RTC102).
chaos:
	@echo "== chaos battery: RT_CHAOS_SEED=$(CHAOS_SEED) =="
	env JAX_PLATFORMS=cpu RT_CHAOS_SEED=$(CHAOS_SEED) \
		RT_LOCK_SANITIZER=1 timeout -k 10 600 \
		$(PY) -m pytest -q -m 'not nightly' -p no:cacheprovider \
		tests/test_failpoints.py \
		tests/test_rpc_fastpath.py::test_duplicated_actor_task_frames_deduped_by_seq \
		tests/test_transfer_plane.py::test_duplicated_push_chunks_deduped_by_offset \
		tests/test_collective.py::test_member_death_mid_allreduce_fails_survivors_fast \
		tests/test_collective.py::test_destroy_mid_op_fails_blocked_members_fast \
		tests/test_control_plane.py::test_sigkill_gcs_restart_from_snapshot_mid_churn \
		tests/test_control_plane.py::test_gcs_restart_mid_churn_recovers_from_snapshot \
		tests/test_serve_scale.py::test_replica_kill_mid_stream_failover_token_identical \
		tests/test_serve_scale.py::test_stream_interrupted_structured_when_failover_disabled \
		tests/test_serve_scale.py::test_gcs_faults_during_serve_streams \
		tests/test_data_streaming.py::test_node_death_mid_shuffle_reissues_only_lost_partitions \
		tests/test_tracing.py::test_serve_failover_stream_keeps_one_trace_id \
		tests/test_tracing.py::test_http_sse_trace_header_links_client_proxy_replica \
		tests/test_train_elastic.py::test_elastic_sigkill_resumes_in_place \
		tests/test_train_elastic.py::test_reshard_death_falls_back_to_checkpoint \
		tests/test_autopilot.py::test_chaos_node_sigkill_mid_revocation \
		tests/test_autopilot.py::test_chaos_gcs_sigkill_mid_arbitration_no_stale_grants \
		tests/test_serve_kv_affinity.py::test_sse_resume_header_lands_through_proxy \
		tests/test_serve_llm_tier.py::test_kill_replica_with_demoted_sessions_resurrects_elsewhere \
	|| { echo "CHAOS BATTERY FAILED — replay with:" \
	     "make chaos CHAOS_SEED=$(CHAOS_SEED)"; exit 1; }
	@echo "== kill-origin-mid-migration x3 (locksan over kv_transfer) =="
	for i in 1 2 3; do \
		env JAX_PLATFORMS=cpu RT_CHAOS_SEED=$(CHAOS_SEED) \
			RT_LOCK_SANITIZER=1 timeout -k 10 300 \
			$(PY) -m pytest -q -p no:cacheprovider \
			tests/test_serve_kv_affinity.py::test_kill_origin_mid_migration_reprefills_with_parity \
		|| { echo "CHAOS kv-migration FAILED (iter $$i) — replay with:" \
		     "make chaos CHAOS_SEED=$(CHAOS_SEED)"; exit 1; }; \
	done

# <30 s smoke slice for make check: registry determinism + one fault
# path per runtime layer (protocol keepalive, transfer partition, GCS
# reconnect).
chaos-smoke:
	@echo "== chaos smoke: RT_CHAOS_SEED=$(CHAOS_SEED) =="
	env JAX_PLATFORMS=cpu RT_CHAOS_SEED=$(CHAOS_SEED) \
		RT_LOCK_SANITIZER=1 timeout -k 10 300 \
		$(PY) -m pytest -q -p no:cacheprovider \
		tests/test_failpoints.py::test_same_seed_identical_schedule \
		tests/test_failpoints.py::test_half_open_detected_by_keepalive \
		tests/test_failpoints.py::test_one_way_partition_multi_source_pull \
		tests/test_failpoints.py::test_gcs_reconnect_bounded_with_terminal_error \
	|| { echo "CHAOS SMOKE FAILED — replay with:" \
	     "make chaos-smoke CHAOS_SEED=$(CHAOS_SEED)"; exit 1; }

check: lint verify chaos-smoke bench-quick bench-llm-quick \
	bench-llm-tier-quick bench-collective-quick bench-control-quick \
	bench-serve-scale-quick \
	bench-data-quick bench-trace-quick bench-train-quick \
	bench-autopilot-quick

# One builder: the library's name carries a digest of its source
# (shm_store._build_lib), which make's timestamps cannot express.
store:
	python -c "from ray_tpu._private.shm_store import _build_lib; print(_build_lib())"

$(BUILD):
	mkdir -p $(BUILD)

$(BUILD)/store_stress_tsan: src/shm_store_stress.cc src/shm_store.cc | $(BUILD)
	$(CXX) -std=c++17 -g -O1 -fsanitize=thread -o $@ $< -lpthread

$(BUILD)/store_stress_asan: src/shm_store_stress.cc src/shm_store.cc | $(BUILD)
	$(CXX) -std=c++17 -g -O1 -fsanitize=address,undefined -o $@ $< -lpthread

store-tsan: $(BUILD)/store_stress_tsan
	$(BUILD)/store_stress_tsan

store-asan: $(BUILD)/store_stress_asan
	$(BUILD)/store_stress_asan

sanitize: store-tsan store-asan

clean:
	rm -rf $(BUILD) ray_tpu/_private/_shm_store*.so
