"""Central runtime-tunable table, overridable by environment variables.

TPU-native equivalent of the reference's single macro table of flags
(reference: src/ray/common/ray_config_def.h:18-22 — RAY_CONFIG(type, name,
default), env-overridable per process, distributed cluster-wide).  Here the
table is a plain dataclass-like registry; every entry can be overridden with
``RT_<NAME>`` in the environment, and ``ray_tpu.init(_system_config=...)``
overrides are forwarded to spawned processes through the environment.
"""

from __future__ import annotations

import json
import os

_DEFS: dict[str, tuple[type, object]] = {}


def _def(name: str, typ: type, default):
    _DEFS[name] = (typ, default)
    return default


class _Config:
    # --- timing / liveness ---
    heartbeat_period_ms = _def("heartbeat_period_ms", int, 1000)
    heartbeat_timeout_ms = _def("heartbeat_timeout_ms", int, 30000)
    resource_report_period_ms = _def("resource_report_period_ms", int, 100)
    worker_register_timeout_s = _def("worker_register_timeout_s", float, 60.0)
    connect_timeout_s = _def("connect_timeout_s", float, 30.0)
    # Default deadline for Connection.request() when the caller gives
    # none: no RPC may wait unbounded by accident (a hung peer must
    # surface as an error, not a wedged future).  Call sites that WANT
    # an unbounded wait (push_task on a long task, infeasible lease
    # requests parked as autoscaler demand) pass timeout=None
    # explicitly.  <= 0 disables the default.
    rpc_request_timeout_s = _def("rpc_request_timeout_s", float, 300.0)
    # Idle keepalive on the RPC plane: a connection with in-flight
    # requests but no inbound traffic for idle_s sends a PING; no
    # traffic for another timeout_s after that fails the connection
    # (half-open links — one direction dead — otherwise hang their
    # futures forever).  idle_s <= 0 disables.
    rpc_keepalive_idle_s = _def("rpc_keepalive_idle_s", float, 20.0)
    rpc_keepalive_timeout_s = _def("rpc_keepalive_timeout_s", float, 20.0)
    # Core-worker GCS reconnect: bounded attempts with full-jitter
    # backoff (was: reconnect exactly once per connection loss).
    gcs_reconnect_attempts = _def("gcs_reconnect_attempts", int, 8)
    gcs_reconnect_base_s = _def("gcs_reconnect_base_s", float, 0.25)
    gcs_reconnect_cap_s = _def("gcs_reconnect_cap_s", float, 5.0)
    # When a raylet's GCS connection drops WITHOUT a drain announcement,
    # the GCS probes the raylet's server before declaring it dead:
    # connection refused proves the process is gone (fast crash
    # detection), while an unreachable-but-maybe-alive node (partition,
    # suspect half-open link the raylet failed on purpose) keeps its
    # heartbeat-timeout grace window.
    node_probe_timeout_s = _def("node_probe_timeout_s", float, 2.0)

    # --- object store ---
    object_store_memory_bytes = _def("object_store_memory_bytes", int, 2 * 1024**3)
    # Below this size objects are inlined in the owner's memory store and on
    # the wire instead of going through shared memory (reference:
    # ray_config_def.h max_direct_call_object_size = 100KiB).
    max_direct_call_object_size = _def("max_direct_call_object_size", int, 100 * 1024)
    fetch_chunk_bytes = _def("fetch_chunk_bytes", int, 8 * 1024**2)
    # How long an object creation may wait for transiently-pinned memory
    # to free before reporting OOM (reference: plasma's create-request
    # queue + object_store_full_delay semantics).
    create_retry_timeout_s = _def("create_retry_timeout_s", float, 120.0)

    # --- object transfer plane (node-to-node pulls/pushes) ---
    # Sliding window of in-flight chunks per transfer in BOTH directions
    # (reference: pull_manager.h keeps several chunk requests outstanding
    # so throughput is wire-bound, not RTT-bound).
    transfer_window_chunks = _def("transfer_window_chunks", int, 4)
    # Admission cap on bytes in flight to/from any single peer across
    # ALL transfers, so many concurrent pulls can't buffer-bloat or OOM
    # a receiver.
    transfer_inflight_bytes_per_peer = _def(
        "transfer_inflight_bytes_per_peer", int, 64 * 1024**2)
    # Objects at least this large stripe chunk ranges across multiple
    # sealed locations when the GCS object directory knows of 2+.
    transfer_stripe_min_bytes = _def("transfer_stripe_min_bytes",
                                     int, 32 * 1024**2)
    # Most peers one striped pull will read from.
    transfer_max_sources = _def("transfer_max_sources", int, 4)
    # Same-host zero-copy fast path: when a source raylet's arena file
    # is reachable on this host, pin the object remotely and memcpy
    # straight out of a read-only mmap of the peer arena instead of
    # chunking it through the socket (the plasma model — one shared
    # store per node — recovered across co-located raylets).
    transfer_same_host_mmap = _def("transfer_same_host_mmap", bool, True)
    # Push-receive transfers with no chunk activity for this long are
    # swept (sender died mid-stream); also bounds the idle lifetime of
    # cached spill-file read fds.
    push_stale_sweep_s = _def("push_stale_sweep_s", float, 120.0)

    # --- data plane (ray_tpu.data streaming executor) ---
    # Use the operator-graph streaming executor for Dataset consumption
    # and all-to-all ops (random_shuffle/repartition): fused map
    # operators with per-operator output budgets + pull-based
    # backpressure, and a windowed shuffle whose partition movement
    # rides the TransferManager instead of round-accumulated store
    # hops.  Set false to restore the legacy bounded-window map loop +
    # push-based round shuffle (kept as the bench baseline).
    data_streaming = _def("data_streaming", bool, True)
    # Per-operator output budget: an operator stops admitting new input
    # blocks while its submitted-but-unconsumed output bytes exceed
    # this, so a slow consumer throttles the whole chain and peak
    # memory is O(sum of budgets), not O(dataset).
    data_op_budget_bytes = _def("data_op_budget_bytes", int, 128 * 1024**2)
    # Concurrent map/reduce tasks per shuffle phase (and the map
    # operator's in-flight task window).  <= 0 means auto (the block
    # count, capped at 16).
    data_shuffle_parallelism = _def("data_shuffle_parallelism", int, 0)
    # One deadline for every data-layer ray_tpu.get/wait (block fetch,
    # materialize, row counts) — was a hardcoded 600 s module constant
    # in data/streaming.py + data/dataset.py.
    data_get_timeout_s = _def("data_get_timeout_s", float, 600.0)

    # --- host collectives (util/collective) ---
    # One deadline for EVERY collective wait: coordinator rounds,
    # mailbox send/recv, group creation, and data-plane chunk waits
    # (was: collect honored RT_COLLECTIVE_TIMEOUT_S while send/recv and
    # create_collective_group hardcoded 300 s).
    collective_timeout_s = _def("collective_timeout_s", float, 3600.0)
    # Tensors at/above this ride the peer-to-peer transfer-plane path
    # (direct reduce-scatter/allgather chunks as raw blob frames /
    # same-host scratch memcpys); below it the coordinator reduces in
    # one round trip, which is cheaper for small tensors.
    collective_fastpath_min_bytes = _def("collective_fastpath_min_bytes",
                                         int, 256 * 1024)
    # Wire-path chunk size and scratch arena capacity for the
    # collective data plane.  The scratch file is sparse (/dev/shm);
    # pages materialize only when written.
    collective_chunk_bytes = _def("collective_chunk_bytes", int, 8 * 1024**2)
    collective_scratch_bytes = _def("collective_scratch_bytes", int, 1 << 30)
    # Bucket-fusion target: fuse_buckets coalesces small tensors into
    # flat buffers of about this many bytes so many tiny gradients ride
    # one rendezvous + one chunk exchange.
    collective_bucket_bytes = _def("collective_bucket_bytes",
                                   int, 32 * 1024**2)
    # Data-plane selection: "auto" (same-host one-sided reads /
    # scratch memcpy when the peer is reachable, raw blob frames
    # otherwise), "wire" (force blob frames even same-host), "store"
    # (the legacy object-store put/get ring — kept as the bench
    # baseline), "coord" (everything through the coordinator actor).
    collective_data_plane = _def("collective_data_plane", str, "auto")
    # Same-host one-sided reads (process_vm_readv straight out of the
    # sender's buffer — zero staging).  Probed at rendezvous and
    # auto-disabled where the kernel forbids it; set false to force
    # the scratch-arena memcpy path.
    collective_pvm_reads = _def("collective_pvm_reads", bool, True)

    # --- train (gang lifecycle + elastic recovery) ---
    # Gang RPC deadline: the start_training fan-out and
    # WorkerGroup.execute/execute_single (was hardcoded 600 s in
    # train/_internal/worker_group.py).
    train_start_timeout_s = _def("train_start_timeout_s", float, 600.0)
    # One report round: how long the driver waits for every rank's
    # next_result before declaring the round lost (was hardcoded
    # 3600 s in backend_executor.get_next_results).
    train_result_timeout_s = _def("train_result_timeout_s", float, 3600.0)
    # shutdown_training's join on the user loop thread (was hardcoded
    # 5 s).  The thread is a daemon; the join only bounds how long a
    # graceful stop waits for an unresponsive loop.
    train_worker_join_s = _def("train_worker_join_s", float, 5.0)
    # Elastic re-formation deadline: survivors (and joiners) must
    # report to the elastic coordinator AND finish the re-shard within
    # this bound or the driver falls back to a cold checkpoint
    # restart.  Jitter is added per recovery so many gangs recovering
    # at once don't stampede the control plane in lockstep.
    train_reform_timeout_s = _def("train_reform_timeout_s", float, 30.0)
    train_reform_jitter_s = _def("train_reform_jitter_s", float, 2.0)
    # Quorum: an elastic gang re-forms only while at least this many
    # members survive; below it the driver cold-restarts from the last
    # checkpoint (ScalingConfig.elastic_min_workers overrides).
    train_elastic_min_workers = _def("train_elastic_min_workers", int, 1)

    # --- control plane (GCS pubsub / snapshots / events) ---
    # Coalesced pubsub: every subscriber gets a bounded outbound queue
    # drained by a pump that batches same-channel messages into one
    # frame (KIND_BATCH), so an event burst costs O(events) enqueues
    # instead of O(events x subscribers) serialized awaits, and one
    # stalled subscriber can never head-of-line-block the broadcast.
    # Set false to restore the legacy per-event serialized push path
    # (kept as the bench baseline).
    gcs_pubsub_coalesce = _def("gcs_pubsub_coalesce", bool, True)
    # Per-subscriber outbound queue bound.  A subscriber that falls
    # this far behind starts losing its OLDEST queued events (drops are
    # counted and exported); pubsub is a best-effort notification
    # plane, so consumers must tolerate gaps (node views re-seed on
    # reconnect, actor waiters re-poll).
    gcs_pubsub_queue_max = _def("gcs_pubsub_queue_max", int, 10000)
    # Most messages one pump drain folds into a single batch frame.
    gcs_pubsub_batch_max = _def("gcs_pubsub_batch_max", int, 512)
    # Publish per-node resource/load deltas on the "nodes" channel when
    # a heartbeat payload changes them (raylets keep their spillback /
    # spread / hybrid views fresh instead of frozen at registration).
    gcs_publish_resource_updates = _def("gcs_publish_resource_updates",
                                        bool, True)
    # Durable-state snapshot cadence (when a persist path is set) and
    # how many trailing cluster events ride each snapshot, so a
    # restarted GCS keeps recent history instead of replaying the world.
    gcs_snapshot_period_s = _def("gcs_snapshot_period_s", float, 0.5)
    gcs_snapshot_events_tail = _def("gcs_snapshot_events_tail", int, 256)
    # Bounded cluster-event ring (drops are counted and exported).
    gcs_events_max = _def("gcs_events_max", int, 1000)

    # --- scheduling ---
    max_workers_per_node = _def("max_workers_per_node", int, 64)
    # Indexed cluster view for spillback/spread/hybrid picks: per-shape
    # candidate sets + score heaps updated incrementally from node
    # deltas, so a lease decision costs O(candidates-inspected) instead
    # of a full rescan of every node view.  Set false to force the
    # plain full-scan policy path (parity/debug escape hatch).
    sched_indexed_view = _def("sched_indexed_view", bool, True)
    # Fork-server worker spawn (zygote.py): pay the interpreter+import cost
    # once per node, fork workers in ~10ms after that.
    worker_zygote_enabled = _def("worker_zygote_enabled", bool, True)
    idle_worker_keep_s = _def("idle_worker_keep_s", float, 300.0)
    lease_spillback_threshold = _def("lease_spillback_threshold", float, 1.0)

    # --- tasks / actors ---
    max_task_retries_default = _def("max_task_retries_default", int, 3)
    # Lineage reconstruction attempts per lost object (reference:
    # ray_config_def.h task_max_retries semantics for object recovery).
    max_object_reconstructions = _def("max_object_reconstructions", int, 3)
    actor_max_restarts_default = _def("actor_max_restarts_default", int, 0)
    # How long a caller waits for a restarting actor to come back ALIVE
    # before treating it as dead (reference: the direct actor submitter
    # holds queued tasks while the GCS reports RESTARTING).  Generous on
    # purpose: a restart on a loaded 1-CPU host can take minutes.
    actor_restart_wait_s = _def("actor_restart_wait_s", float, 300.0)
    task_queue_warn_len = _def("task_queue_warn_len", int, 100000)

    # --- serve control plane (controller reconcile / autoscale ticks) ---
    # Reconcile-loop period (was the CONTROL_LOOP_PERIOD_S module
    # constant in serve/_private/controller.py) and the poll cadence of
    # the controller's wait loops (deployment-health wait, graceful
    # shutdown drain) — every controller tick interval now rides the
    # config table instead of hardcoded literals.
    serve_control_loop_period_s = _def("serve_control_loop_period_s",
                                       float, 0.1)
    serve_health_poll_period_s = _def("serve_health_poll_period_s",
                                      float, 0.1)

    # --- KV-aware serving (prefix-affinity routing + page migration) ---
    # Master switch for prefix-affinity routing: replicas publish radix
    # prefix digests through their autoscale gauges and the router
    # scores candidates by expected prefix-hit depth.  Off restores the
    # pure power-of-two-choices pick (kept as the bench baseline).
    serve_affinity = _def("serve_affinity", bool, True)
    # Most prefix fingerprints one replica publishes per digest (top-K
    # by recency) and the deepest page a fingerprint may describe.
    # Both bound digest size: a digest rides every autoscale poll and
    # every replica broadcast, so it must stay control-plane-sized.
    serve_affinity_digest_top_k = _def("serve_affinity_digest_top_k",
                                       int, 32)
    serve_affinity_digest_depth = _def("serve_affinity_digest_depth",
                                       int, 8)
    # Router score = blend * hit_depth_norm - (1 - blend) * load_norm:
    # 1.0 routes on affinity alone, 0.0 degenerates to load-only.
    serve_affinity_blend = _def("serve_affinity_blend", float, 0.7)
    # Hotspot bound: a replica whose occupancy (in-flight /
    # max_concurrent_queries) is at or past this fraction loses its
    # affinity claim — a viral prefix must not starve one replica, so
    # affinity always loses to overload.
    serve_affinity_hotspot_bound = _def("serve_affinity_hotspot_bound",
                                        float, 0.75)
    # How often a replica's digest may retrigger the controller's
    # replica broadcast (membership changes still broadcast at once);
    # bounds long-poll churn under hot caches.
    serve_affinity_refresh_s = _def("serve_affinity_refresh_s",
                                    float, 1.0)
    # --- KV page migration (serve/llm/kv_transfer.py) ---
    # Sliding window of in-flight page frames per migration pull (the
    # transfer plane's windowed-pump discipline).
    serve_kv_migration_window_chunks = _def(
        "serve_kv_migration_window_chunks", int, 4)
    # Below this many committed full pages, migration is skipped and
    # the destination re-prefills.  Crossover rationale: one migrated
    # page moves page_size * 2 * layers * kv_heads * head_dim * 4 bytes
    # over a ~GB/s link plus a fixed ~2 RPC rendezvous cost, while
    # re-prefilling the same page costs one chunked-prefill pass that
    # is amortized across the whole batch — for 1-page prefixes the
    # rendezvous alone usually exceeds the prefill FLOPs, so shipping
    # only wins once a few pages of K/V ride one rendezvous (measured
    # by bench.py --suite serve_scale's migration-vs-reprefill leg).
    serve_kv_min_migrate_pages = _def("serve_kv_min_migrate_pages",
                                      int, 2)
    # Same-host fast path: the origin stages export pages in a /dev/shm
    # file the destination mmap-reads (one memcpy, no socket); falls
    # back to wire frames when the file is not reachable.
    serve_kv_samehost = _def("serve_kv_samehost", bool, True)
    # An export a destination never sealed (puller died mid-pull) is
    # released after this TTL so its page refs cannot leak forever.
    serve_kv_export_ttl_s = _def("serve_kv_export_ttl_s", float, 60.0)
    # How long a router keeps trusting the pull address (kv_rdv) of a
    # replica that LEFT the membership broadcast.  Client-replayed
    # resume cursors name a kv_origin to migrate pages from; the router
    # only honors addresses it has itself observed in the broadcast —
    # never a client-invented endpoint (SSRF / cache poisoning) — and
    # the grace window covers the dead-replica resume case, where the
    # origin is gone from membership by the time the client retries.
    serve_kv_rdv_grace_s = _def("serve_kv_rdv_grace_s", float, 120.0)
    # --- KV memory hierarchy (cold-page tiering + durable sessions) ---
    # Master switch for the three-tier hierarchy: T0 decode pool, T1
    # host shared-memory arena, T2 file-backed page store.  Off keeps
    # the pure pool-bound behavior (the bench's tiering-off baseline).
    serve_kv_tiering = _def("serve_kv_tiering", bool, True)
    # A tree-only T0 page with no decode tick for this long is demoted
    # to the host arena by the engine's sweeper.  Short enough that an
    # idle conversation releases its pool pages well before a typical
    # human reply; long enough that an actively streaming request's
    # shared prefix never thrashes.
    serve_kv_demote_idle_s = _def("serve_kv_demote_idle_s", float, 30.0)
    # A T1 page idle this long past its demotion moves on to the store
    # tier (T2) — where it survives replica death and is pullable from
    # any replica on the host.
    serve_kv_t2_idle_s = _def("serve_kv_t2_idle_s", float, 120.0)
    # Sweeper cadence.  Also the retry hint submit() sends when the
    # demotable cold-page headroom could cover a rejected reservation:
    # one sweep from now the pages will be free.
    serve_kv_tier_sweep_s = _def("serve_kv_tier_sweep_s", float, 2.0)
    # Host-arena (T1) byte budget per engine.  Overflow demotes the
    # arena's coldest pages straight to the store tier, so T1 is a
    # cache over T2, never a second hard ceiling.
    serve_kv_t1_budget_bytes = _def("serve_kv_t1_budget_bytes",
                                    int, 256 * 1024**2)
    # Store-tier (T2) directory, shared by every replica on the host
    # (the spill-directory pattern); empty means
    # <tempdir>/rt_kv_store-<uid>.  Pages are content-addressed by
    # chained prefix fingerprint, so two replicas that never exchanged
    # state agree on the key of a shared prefix.
    serve_kv_store_dir = _def("serve_kv_store_dir", str, "")
    # Store entries (pages and session manifests) older than this are
    # garbage-collected by the sweeper; bounds disk growth at the cost
    # of how long a dormant session stays resurrectable.
    serve_kv_store_ttl_s = _def("serve_kv_store_ttl_s", float, 3600.0)
    # Retry-After for kv_exhausted rejections when no demotion headroom
    # applies (a KV pool drains at generation speed).  Sub-second values
    # are honored: the HTTP surface sends float seconds on the wire.
    serve_kv_retry_after_s = _def("serve_kv_retry_after_s", float, 5.0)
    # Router affinity: a digest hit whose deepest node sits in T1/T2 is
    # discounted by this factor versus a T0 hit — promoted pages cost a
    # host->device splice the decode-pool hit does not.
    serve_affinity_tier_discount = _def("serve_affinity_tier_discount",
                                        float, 0.5)

    # --- cluster autopilot (SLO-driven arbiter, _private/arbiter.py) ---
    # The GCS broker's arbitration tick: how often registered workload
    # declarations + smoothed signals are re-evaluated into grant /
    # revoke decisions.
    autopilot_period_s = _def("autopilot_period_s", float, 0.25)
    # Client-side report cadence (serve controller SLO attainment,
    # train gang agent, data soak lease) — each report doubles as the
    # grant fetch, so one RPC per period per workload.
    autopilot_report_period_s = _def("autopilot_report_period_s",
                                     float, 0.25)
    # A serve SLO breach must be SUSTAINED this long before the arbiter
    # reclaims capacity from lower-priority workloads (and the
    # recovery must be sustained equally long before capacity returns)
    # — the arbiter's half of the flap suppression.
    autopilot_slo_breach_window_s = _def("autopilot_slo_breach_window_s",
                                         float, 1.0)
    # Post-decision cooldown per workload: two budget changes for the
    # same workload are always at least this far apart.
    autopilot_cooldown_s = _def("autopilot_cooldown_s", float, 2.0)
    # EWMA smoothing over reported signals (TTFT p99) — 1.0 disables.
    autopilot_ewma_alpha = _def("autopilot_ewma_alpha", float, 0.5)
    # A revoked data soak lease stops admitting new tasks immediately;
    # in-flight tasks get this grace window to drain before the bench /
    # chaos harness calls the revocation late.
    autopilot_data_revoke_grace_s = _def("autopilot_data_revoke_grace_s",
                                         float, 2.0)
    # Nodes reserved for a reclaim beneficiary (so revoked capacity
    # drains instead of accepting new low-priority leases) un-reserve
    # after this TTL even if the arbiter never clears them.
    autopilot_reserve_ttl_s = _def("autopilot_reserve_ttl_s", float, 15.0)
    # A workload whose client stopped reporting (driver died without
    # unregistering) is dropped from arbitration after this long — its
    # budget returns to the pool instead of leaking forever.
    autopilot_stale_report_s = _def("autopilot_stale_report_s",
                                    float, 15.0)

    # --- tracing (the cross-plane span runtime, _private/tracing.py) ---
    # Always-on per-process span ring; set false to hard-disable every
    # record (the fast path is one bool check — measured by
    # `bench.py --suite trace` and gated <=5% in make bench-trace-quick).
    trace_enabled = _def("trace_enabled", bool, True)
    # Bounded ring capacity (drop-oldest; drops counted and exported as
    # tracing_events_dropped_total).
    trace_ring_capacity = _def("trace_ring_capacity", int, 8192)
    # Complete events WITHOUT span linkage shorter than this are not
    # recorded (perf-only noise gate); linked spans always record —
    # dropping them would hole the request tree.
    trace_min_dur_us = _def("trace_min_dur_us", float, 0.0)
    # RPC handlers slower than this record an rpc.slow span (0 disables).
    trace_rpc_slow_ms = _def("trace_rpc_slow_ms", float, 50.0)
    # Byte cap on the pickled telemetry KV push (the stale convenience
    # view).  The push must stay control-plane-sized: anything
    # chunk-sized belongs on raw transfer frames, and the authoritative
    # trace path is the dump_trace pull, which has no such cap.
    trace_kv_push_budget = _def("trace_kv_push_budget", int, 48 * 1024)

    # --- logging ---
    log_to_driver = _def("log_to_driver", bool, True)

    def __init__(self, overrides: dict | None = None):
        for name, (typ, default) in _DEFS.items():
            env = os.environ.get(f"RT_{name.upper()}")
            if env is not None:
                if typ is bool:
                    val = env.lower() in ("1", "true", "yes")
                elif typ is int:
                    val = int(env)
                elif typ is float:
                    val = float(env)
                else:
                    val = env
                setattr(self, name, val)
            else:
                setattr(self, name, default)
        if overrides:
            for k, v in overrides.items():
                if k not in _DEFS:
                    raise ValueError(f"Unknown system config: {k}")
                setattr(self, k, v)

    def to_env(self) -> dict[str, str]:
        """Serialize current values as env vars for child processes."""
        out = {}
        for name in _DEFS:
            v = getattr(self, name)
            out[f"RT_{name.upper()}"] = json.dumps(v) if not isinstance(v, str) else v
        return out


GLOBAL_CONFIG = _Config()


def apply_system_config(overrides: dict):
    global GLOBAL_CONFIG
    GLOBAL_CONFIG = _Config(overrides)
    return GLOBAL_CONFIG
