"""CoreWorker: the in-process runtime of every driver and worker.

TPU-native re-design of the reference core worker (reference:
src/ray/core_worker/core_worker.h:63 — SubmitTask core_worker.cc:1567, Put
:892, Get :1095, ExecuteTask :2181, HandlePushTask :2543;
CoreWorkerDirectTaskSubmitter transport/direct_task_transport.h:57 with
per-SchedulingKey lease pools; CoreWorkerDirectActorTaskSubmitter
direct_actor_task_submitter.h:67 with per-caller sequence numbers;
TaskManager task_manager.h:86 for retries; ReferenceCounter
reference_count.h:61 for ownership; memory store
store_provider/memory_store/memory_store.h:43).

Each process runs one CoreWorker: it owns the objects it creates (the owner
resolves status/location queries from borrowers), submits tasks via
raylet-granted worker leases and pushes them directly worker-to-worker, and
— in worker processes — executes pushed tasks/actor methods on an executor
pool while the asyncio loop stays responsive for the data plane.
"""

from __future__ import annotations

import asyncio
import functools
import heapq
import logging
import os
import threading
import time
import traceback
from collections import deque
from concurrent.futures import Future as CFuture, ThreadPoolExecutor
from concurrent.futures import TimeoutError as CFTimeoutError

from ray_tpu import exceptions as rexc
from ray_tpu._private import failpoints, protocol, retry, serialization
from ray_tpu._private.config import GLOBAL_CONFIG as cfg
from ray_tpu._private.jax_utils import bind_tpu_chips
from ray_tpu._private.ids import (ActorID, FunctionID, JobID, NodeID, ObjectID,
                                  TaskID, WorkerID)
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.shm_store import StoreMapping
from ray_tpu._private.task_spec import (ActorCreationSpec, ActorTaskSpec,
                                        TaskSpec)
from ray_tpu._private import tracing as _tracing

logger = logging.getLogger(__name__)

global_worker: "CoreWorker | None" = None

# Distributed trace context, propagated inside task specs (reference:
# util/tracing/tracing_helper.py — otel context rides the TaskSpec).
# The contextvar, id minting, and the per-process span ring all live in
# _private/tracing.py now that every plane records spans, not just
# task/actor execution here.
_TRACE = _tracing._TRACE
_trace_for_submit = _tracing.trace_for_submit


# Serializes cross-thread attachment of concurrent.futures waiters to
# owned entries against the loop-side ready flip (OwnedObject.set_ready):
# a sync get() attaches its waiter directly under this lock — no
# call_soon_threadsafe hop (and thus no self-pipe syscall) per get.
_CF_LOCK = threading.Lock()


class _Latch:
    """Countdown waiter attached (via per-entry _LatchRef wrappers) to
    SEVERAL owned entries' cf_waiters: trips a threading.Event when
    every entry has fired — or IMMEDIATELY when any entry completes
    ERRORED, preserving the fail-fast semantics of the asyncio.gather
    path this replaces.  Backs the list-get fast path: one wake for N
    objects."""

    __slots__ = ("_n", "event", "errored")

    def __init__(self, n: int):
        self._n = n
        self.event = threading.Event()
        self.errored = False


class _LatchRef:
    """One entry's stake in a _Latch; duck-types the CFuture surface
    set_ready() touches (done / set_result)."""

    __slots__ = ("latch", "entry")

    def __init__(self, latch: _Latch, entry: "OwnedObject"):
        self.latch = latch
        self.entry = entry

    def done(self) -> bool:
        return self.latch.event.is_set()

    def set_result(self, _value):  # loop thread only (set_ready)
        latch = self.latch
        if self.entry.state == ERRORED:
            latch.errored = True
            latch.event.set()  # fail fast: don't wait for the rest
            return
        latch._n -= 1
        if latch._n <= 0:
            latch.event.set()

MODE_DRIVER = "driver"
MODE_WORKER = "worker"

# Owned-object states.
PENDING = "PENDING"
INLINE = "INLINE"
IN_STORE = "IN_STORE"
ERRORED = "ERRORED"


class _PinView:
    """Buffer wrapper tying a raylet read-pin to the lifetime of the
    zero-copy views handed to user code (PEP 688 __buffer__): when the
    last derived memoryview/ndarray dies, the pin is released and the
    object becomes evictable/spillable again (reference: plasma client
    Release on buffer destruction)."""

    __slots__ = ("_mv", "_cb")

    def __init__(self, mv: memoryview, release_cb):
        self._mv = mv
        self._cb = release_cb

    def __buffer__(self, flags):
        return memoryview(self._mv)

    def __del__(self):
        cb, self._cb = self._cb, None
        if cb is not None:
            try:
                cb()
            except Exception:
                pass


class _RefArg:
    """Marker for a top-level ObjectRef argument: the executor substitutes
    the fetched value (nested refs are passed through as refs — reference
    semantics)."""
    __slots__ = ("ref",)

    def __init__(self, ref: ObjectRef):
        self.ref = ref


class OwnedObject:
    __slots__ = ("state", "blob", "location", "size", "event", "local_refs",
                 "submitted_task", "reconstructions", "cf_waiters",
                 "dynamic_children")

    def __init__(self):
        self.state = PENDING
        self.blob = None
        self.location: NodeID | None = None
        self.size = 0
        self.event = asyncio.Event()
        self.local_refs = 0
        # The submitting task's spec, kept for lineage reconstruction
        # (reference: TaskManager lineage, task_manager.h:86; recovery via
        # ObjectRecoveryManager::RecoverObject object_recovery_manager.h:90).
        self.submitted_task = None
        self.reconstructions = 0
        # Sub-object ids of a num_returns="dynamic" task's yields; freed
        # when this (main) entry is released.
        self.dynamic_children = None
        # concurrent.futures waiters from sync get() fast paths on other
        # threads; fired (on the loop thread) the moment the entry lands.
        self.cf_waiters = None

    def ready(self):
        return self.state != PENDING

    def set_ready(self):
        """Mark ready: wake loop-side awaiters and cross-thread waiters.
        Loop-thread only.  The waiter list is taken under _CF_LOCK so
        sync get()s on other threads can attach directly (lock-ordered
        against the ready flip) instead of paying a loop hop."""
        self.event.set()
        with _CF_LOCK:
            waiters = self.cf_waiters
            self.cf_waiters = None
        if waiters:
            for f in waiters:
                if not f.done():
                    f.set_result(None)


class LeasePool:
    """Per-SchedulingKey lease pool (reference: direct_task_transport.h:57 —
    worker_to_lease_entry / pipelining per scheduling key)."""

    def __init__(self):
        self.queue: list = []
        self.idle: list = []
        self.all: dict[bytes, dict] = {}
        self.requests_inflight = 0
        self.return_timers: dict[bytes, asyncio.TimerHandle] = {}
        # request_id -> raylet conn the request is queued at (for cancel)
        self.outstanding: dict[bytes, object] = {}


class _ActorSendQueue:
    """Per-actor submission queue drained by ONE long-lived pump task
    (reference: the direct actor submitter's per-actor send queue,
    direct_actor_task_submitter.h:67).  A submission costs one loop hop
    (the cross-thread enqueue); sequence numbers are assigned at
    DEQUEUE, on the loop, so the unacked-window/reconnect-replay
    semantics are identical to the per-call submitter this replaces —
    and bursts to one actor coalesce into a single KIND_BATCH frame."""

    __slots__ = ("pending", "waiter", "pump", "addr_hint")

    def __init__(self):
        self.pending: deque = deque()
        self.waiter: asyncio.Future | None = None
        self.pump: asyncio.Task | None = None
        self.addr_hint: tuple | None = None


class ExecutionContext(threading.local):
    def __init__(self):
        self.task_id = None
        self.actor_id = None
        self.lease_id = None
        self.blocked_depth = 0
        self.tpu_ids: list = []  # chip indices granted to this lease


class CoreWorker:
    def __init__(self, mode, gcs_addr, raylet_addr=None, store_path=None,
                 store_cap=None, worker_id=None, job_id=None,
                 host="127.0.0.1"):
        self.mode = mode
        self.host = host
        self.worker_id = worker_id or WorkerID.from_random()
        self.job_id = job_id or JobID.from_random()
        self.gcs_addr = gcs_addr
        self.raylet_addr = raylet_addr
        self.node_id: NodeID | None = None
        self.store_path = store_path
        self.store_cap = store_cap
        self.mapping: StoreMapping | None = None
        # Pluggable worker-to-worker RPC surface: subsystems living in
        # the worker process (the collective transport) register async
        # handlers and per-method blob sinks here instead of growing
        # rpc_* methods on CoreWorker.  blob_providers lets an inbound
        # KIND_BLOB body land straight in a subsystem-owned buffer.
        self.ext_rpc: dict[str, object] = {}
        self.blob_providers: dict[str, object] = {}
        self._collective_transport = None
        self.server = protocol.RpcServer(self._handle, host=host,
                                         name=f"cw-{mode}",
                                         blob_provider=self._blob_provider)
        self.addr: tuple[str, int] | None = None
        self.gcs: protocol.Connection | None = None
        self.raylet: protocol.Connection | None = None
        self.loop: asyncio.AbstractEventLoop | None = None
        self._loop_thread: threading.Thread | None = None
        self._loop_ready = threading.Event()
        # ownership tables
        self.owned: dict[ObjectID, OwnedObject] = {}
        self._pinned: set[bytes] = set()
        self._borrow_cache: dict[ObjectID, bytes] = {}
        # Argument ObjectRefs of in-flight tasks, pinned so the owner keeps
        # serving them until the dependent task finishes (reference:
        # TaskManager lineage pinning of task dependencies).  Keyed by the
        # task's first return ObjectID.
        self._arg_pins: dict[ObjectID, list] = {}
        # Lineage for reconstruction: task_id -> spec while any of the
        # task's returns is still owned; arg refs move to _lineage_pins on
        # completion so re-execution can still resolve them.
        self._lineage: dict[TaskID, dict] = {}
        self._lineage_pins: dict[TaskID, list] = {}
        self._recovering: dict[TaskID, asyncio.Future] = {}
        # task_id -> (lease, spec) while pushed to a worker (for cancel)
        self._inflight_tasks: dict[TaskID, tuple] = {}
        # submission state
        self.lease_pools: dict[tuple, LeasePool] = {}
        self._worker_conns: dict[tuple, protocol.Connection] = {}
        self._owner_conns: dict[tuple, protocol.Connection] = {}
        self._exported_fns: set[bytes] = set()
        self._fn_cache: dict[bytes, object] = {}
        # actor-caller state
        self._actor_seq: dict[ActorID, int] = {}
        self._actor_conns: dict[ActorID, protocol.Connection] = {}
        self._actor_addr_cache: dict[ActorID, tuple] = {}
        self._actor_locks: dict[ActorID, asyncio.Lock] = {}
        # Unacked submission window per actor: seq -> entry.  Held across
        # incarnations and resent IN ORDER on restart (reference:
        # direct_actor_task_submitter.h:67 resend of the unacked window).
        self._actor_unacked: dict[ActorID, dict[int, dict]] = {}
        self._actor_recovering: dict[ActorID, asyncio.Future] = {}
        # Pipelined submission state: one send queue + pump per actor,
        # return-oid -> queued entry (for cancel of unsent calls), and
        # the per-(actor, method) spec templates of the zero-alloc
        # dispatch fast path.
        self._actor_queues: dict[ActorID, _ActorSendQueue] = {}
        self._actor_queued_refs: dict[ObjectID, dict] = {}
        self._actor_spec_templates: dict[tuple, dict] = {}
        # actor-executor state
        self.actor_instance = None
        self.actor_id: ActorID | None = None
        self._actor_is_async = False
        self._actor_pools: dict[str, ThreadPoolExecutor] = {}
        self._actor_async_sems: dict[str, asyncio.Semaphore] = {}
        self._caller_seq: dict[bytes, int] = {}
        self._caller_buffer: dict[bytes, list] = {}
        # Wire-duplicate defense (chaos dup action / retransmits): seqs
        # whose dispatch is still running, and reply waiters parked by
        # duplicate frames of those seqs (see rpc_push_actor_task).
        self._caller_running: dict[bytes, set] = {}
        self._dup_waiters: dict = {}
        self._task_pool = ThreadPoolExecutor(max_workers=1,
                                             thread_name_prefix="exec")
        # Drain-batched dispatch state for single-thread executor pools
        # (see _exec_on_serial_pool), keyed by id(pool).
        self._exec_states: dict[int, dict] = {}
        self.exec_ctx = ExecutionContext()
        self.connected = False
        self._shutdown = False
        # MPSC thread->loop post queue (see _post).
        self._post_q: deque = deque()
        self._post_armed = False
        self._loop_ident: int | None = None
        self._pubsub_handlers: dict[str, object] = {}
        self._gcs_reconnect_lock: asyncio.Lock | None = None
        # Chrome-trace profile events for ray_tpu.timeline(): the
        # process-wide span ring (_private/tracing.py) — bounded,
        # drop-oldest, drained authoritatively by the dump_trace RPC.
        self._trace_ring = _tracing.ring()

    @property
    def _profile_events(self) -> list:
        """Snapshot view of this process's span ring (compat surface
        for ray_tpu.timeline()'s driver-side merge)."""
        return self._trace_ring.snapshot()

    # ------------------------------------------------------------ lifecycle
    def start_driver(self):
        """Driver mode: run the loop in a background thread."""
        self._loop_thread = threading.Thread(target=self._loop_main,
                                             name="ray_tpu-io", daemon=True)
        self._loop_thread.start()
        self._loop_ready.wait(30)
        self._call(self._connect()).result(cfg.connect_timeout_s)
        self.connected = True

    def _loop_main(self):
        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)
        protocol.enable_eager_tasks(self.loop)
        self._loop_ident = threading.get_ident()
        self._loop_ready.set()
        self.loop.run_forever()

    async def start_worker_async(self):
        """Worker mode: called from the worker process's own loop."""
        self.loop = asyncio.get_running_loop()
        protocol.enable_eager_tasks(self.loop)
        self._loop_ident = threading.get_ident()
        await self._connect()
        self.connected = True

    async def _gcs_request(self, method, body,
                           timeout=protocol._DEFAULT_TIMEOUT):
        """GCS RPC surviving a GCS restart/partition: bounded reconnect
        attempts with full-jitter backoff (reference: workers re-resolve
        the GCS after failover, NotifyGCSRestart node_manager.proto:343;
        was reconnect-exactly-once, which one flaky reconnect turned
        into a caller-visible error while the GCS was still coming
        back).  Reconnects are serialized so concurrent failures share
        one new connection rather than stampeding (and leaking the
        losers); when every attempt is exhausted the terminal error
        names the GCS address so the operator knows what was
        unreachable."""
        inject = None
        if failpoints.ACTIVE:
            act = failpoints.check("worker.gcs_request", peer=method)
            if act is not None:
                if act.kind == "delay":
                    await asyncio.sleep(act.delay_s)
                elif act.kind in ("error", "drop", "disconnect"):
                    # Raised INSIDE the try: an injected request fault
                    # must exercise the reconnect machinery, exactly
                    # like a real conn loss would.
                    inject = protocol.ConnectionLost(
                        f"failpoint: injected gcs_request {act.kind} "
                        f"({method})")
        attempts = max(1, cfg.gcs_reconnect_attempts)
        backoff = retry.ExpBackoff(cfg.gcs_reconnect_base_s,
                                   cfg.gcs_reconnect_cap_s)
        last_error: Exception | None = None
        failed = None
        # Attempt 0 is the request on the existing connection; attempts
        # 1..N reconnect first.  One loop, one classification of what
        # retries vs what surfaces.
        for attempt in range(attempts + 1):
            try:
                if attempt > 0:
                    if self._gcs_reconnect_lock is None:
                        self._gcs_reconnect_lock = asyncio.Lock()
                    async with self._gcs_reconnect_lock:
                        if self.gcs is failed or self.gcs.closed:
                            if failpoints.ACTIVE:
                                act = failpoints.check(
                                    "worker.gcs_reconnect")
                                if act is not None:
                                    if act.kind == "delay":
                                        await asyncio.sleep(act.delay_s)
                                    elif act.kind != "off":
                                        raise protocol.ConnectionLost(
                                            "failpoint: injected "
                                            f"gcs_reconnect {act.kind}")
                            old = self.gcs
                            try:
                                self.gcs = (
                                    await protocol.Connection.connect(
                                        self.gcs_addr[0],
                                        self.gcs_addr[1],
                                        handler=self._handle,
                                        name="cw->gcs",
                                        timeout=cfg.connect_timeout_s))
                            except asyncio.TimeoutError as e:
                                # Connect timeout = failed reconnect
                                # ATTEMPT (SYN black-holed partition) —
                                # classify as conn failure so the
                                # bounded retry keeps going.
                                raise ConnectionError(
                                    "connect timed out after "
                                    f"{cfg.connect_timeout_s}s") from e
                            if old is not None and not old.closed:
                                try:
                                    await old.close()
                                except Exception:
                                    pass
                if inject is not None:
                    e, inject = inject, None
                    raise e
                return await self.gcs.request(method, body,
                                              timeout=timeout)
            except asyncio.TimeoutError:
                # Request deadline with the connection still healthy
                # (the keepalive would have failed it otherwise): the
                # GCS may already be executing this RPC, so neither
                # tear down the shared connection nor re-send — surface
                # the deadline.  Caught before the conn-loss clause: on
                # py3.11+ TimeoutError is an OSError subclass.
                raise
            except (protocol.ConnectionLost, ConnectionError,
                    OSError) as e:
                if self._shutdown:
                    raise
                last_error = e
                failed = self.gcs
                if attempt < attempts:
                    await asyncio.sleep(backoff.next())
        raise ConnectionError(
            f"GCS at {self.gcs_addr[0]}:{self.gcs_addr[1]} unreachable "
            f"after {attempts} reconnect attempt(s); last error: "
            f"{last_error}") from last_error

    async def _connect(self):
        self.addr = (self.host, await self.server.start(0))
        self.gcs = await protocol.Connection.connect(
            self.gcs_addr[0], self.gcs_addr[1], handler=self._handle,
            name="cw->gcs", timeout=cfg.connect_timeout_s)
        if self.mode == MODE_DRIVER:
            await self.gcs.request("register_driver", {
                "job_id": self.job_id, "pid": os.getpid(),
                "entrypoint": " ".join(os.sys.argv)})
            if cfg.log_to_driver:
                import sys

                def _echo_logs(msg):
                    for line in (msg or {}).get("lines", []):
                        print(f"(worker {msg['worker']}, "
                              f"node {msg['node'][:8]}) {line}",
                              file=sys.stderr)

                self._pubsub_handlers["logs"] = _echo_logs
                await self.gcs.request("subscribe", {"channels": ["logs"]})
        self.loop.create_task(self._telemetry_loop())
        if self.raylet_addr is not None:
            on_close = None
            if self.mode == MODE_WORKER:
                # A worker whose raylet died must exit, or it leaks forever
                # (reference: workers die when the raylet socket closes,
                # src/ray/common/client_connection.h).
                def on_close(_conn):
                    if not self._shutdown:
                        logger.warning("raylet connection lost; worker exiting")
                        os._exit(1)
            self.raylet = await protocol.Connection.connect(
                self.raylet_addr[0], self.raylet_addr[1], handler=self._handle,
                name="cw->raylet", timeout=cfg.connect_timeout_s,
                on_close=on_close)
            reply = await self.raylet.request("register_worker", {
                "worker_id": self.worker_id.hex(),
                "addr": self.addr,
                "pid": os.getpid(),
            })
            self.node_id = reply["node_id"]
            if self.store_path is None:
                # External-driver connect path: the raylet tells us where
                # its arena lives so we can mmap the data plane.
                self.store_path = reply.get("store_path")
                self.store_cap = reply.get("store_capacity")
        if self.store_path:
            self.mapping = StoreMapping(self.store_path, self.store_cap)

    def _call(self, coro) -> CFuture:
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    # Coalesced thread->loop posting: call_soon_threadsafe writes to the
    # loop's self-pipe on EVERY call, so a burst of N submissions costs
    # N syscalls.  This MPSC queue arms at most one wake per drain: a
    # burst rides one self-pipe write, and posts from the loop thread
    # itself never pay a syscall at all.
    def _post(self, fn, *args):
        self._post_q.append((fn, args))
        if not self._post_armed:
            self._post_armed = True
            if threading.get_ident() == self._loop_ident:
                self.loop.call_soon(self._drain_posts)
            else:
                self.loop.call_soon_threadsafe(self._drain_posts)

    def _drain_posts(self):
        # Reset the arm flag FIRST: a producer appending after the reset
        # re-arms (worst case an extra no-op wake, never a lost item).
        self._post_armed = False
        q = self._post_q
        while q:
            try:
                fn, args = q.popleft()
            except IndexError:
                break
            try:
                fn(*args)
            except Exception:
                logger.exception("posted callback %s failed", fn)

    def _run(self, coro, timeout=None):
        """Run coro on the loop from a non-loop thread and wait."""
        return self._call(coro).result(timeout)

    def gcs_call(self, method, body, timeout=None):
        """Synchronous GCS RPC from any non-loop thread (serve
        controller executor threads, train gang agents, the CLI) —
        the same bounded-reconnect path as _gcs_request."""
        return self._run(self._gcs_request(method, body), timeout)

    def shutdown(self):
        if self._shutdown:
            return
        self._shutdown = True
        try:
            self._call(self._shutdown_async()).result(5)
        except Exception:
            pass
        if self._loop_thread is not None:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._loop_thread.join(5)
        self.connected = False

    async def _shutdown_async(self):
        for q in self._actor_queues.values():
            if q.pump is not None:
                q.pump.cancel()
        if self._collective_transport is not None:
            try:
                self._collective_transport.close()
            except Exception:
                pass
        await self.server.stop()
        for conn in list(self._worker_conns.values()) + \
                list(self._owner_conns.values()) + \
                list(self._actor_conns.values()):
            await conn.close()
        if self.raylet is not None:
            await self.raylet.close()
        if self.gcs is not None:
            await self.gcs.close()
        if self.mapping is not None:
            self.mapping.close()

    # ----------------------------------------------------------- rpc server
    async def _handle(self, conn, method, body):
        fn = getattr(self, "rpc_" + method, None)
        if fn is None:
            ext = self.ext_rpc.get(method)
            if ext is not None:
                return await ext(conn, body)
            raise protocol.RpcError(f"core worker: no method {method}")
        return await fn(conn, body)

    def _blob_provider(self, conn, method, header, nraw):
        """Route an inbound raw-payload frame to the subsystem that
        registered the method (returns a writable sink or None)."""
        p = self.blob_providers.get(method)
        if p is None:
            return None
        return p(conn, header, nraw)

    async def _telemetry_loop(self):
        """Push metric snapshots + profile events to the GCS KV every few
        seconds (reference: the per-node metrics agent relay,
        _private/metrics_agent.py:63; consumed by the dashboard head and
        ray_tpu.timeline()).  Also measures this process's event-loop lag
        (reference: the instrumented asio event loop, event_stats.h) —
        sustained lag means a handler is blocking the IO plane."""
        lag_gauge = None
        try:
            from ray_tpu.util.metrics import Gauge
            lag_gauge = Gauge(
                "rt_event_loop_lag_ms",
                "scheduling delay of the CoreWorker IO loop",
                tag_keys=("mode",))
        except Exception:
            pass
        while not self._shutdown:
            t0 = time.monotonic()
            # Jittered: thousands of workers pushing telemetry must not
            # beat against the GCS KV in phase.
            tick = retry.jittered(2.0)
            await asyncio.sleep(tick)
            if lag_gauge is not None:
                lag = max(0.0, (time.monotonic() - t0 - tick) * 1000)
                try:
                    lag_gauge.set(round(lag, 2), tags={"mode": self.mode})
                except Exception:
                    pass
            try:
                from ray_tpu.util import metrics as metrics_mod
                # Ring health rides the metrics push: the drop counter
                # (tracing_events_dropped_total) reaches prometheus, so
                # an overflowing ring is visible without a trace pull.
                _tracing.export_metrics()
                snaps = metrics_mod.registry_snapshot()
                # STALE CONVENIENCE VIEW: the KV push truncates to the
                # freshest ring tail and lags by the push period.  The
                # authoritative path is the dump_trace RPC pull
                # (ray_tpu.cluster_trace / rt timeline --cluster),
                # which drains the whole ring on demand.
                payload = self._telemetry_payload(snaps)
                if payload is None:
                    continue
                await self._gcs_request("kv_put", {
                    "ns": "telemetry", "key": self.worker_id.binary(),
                    "value": payload})
            except Exception:
                if self._shutdown:
                    return

    def _telemetry_payload(self, snaps):
        """Build one telemetry KV push, capped at
        cfg.trace_kv_push_budget bytes (the profile tail halves until it
        fits).  The push must stay control-plane-sized: a full ring tail
        pickles to hundreds of KiB, which belongs on the dump_trace
        pull, not the heartbeat.  Returns None when there is nothing to
        push."""
        import pickle
        events = self._trace_ring.tail(2000)
        if not snaps and not events:
            return None

        def _dumps(evs):
            return pickle.dumps({
                "snapshots": snaps, "profile": evs,
                # Ring coverage + drop counts: timeline() synthesizes a
                # trace.ring_meta event per process, so a truncated
                # trace says WHAT it could not retain.
                "trace_stats": self._trace_ring.stats(),
                "rpc_handlers": protocol.handler_stats_snapshot(),
                "pid": os.getpid(), "mode": self.mode})

        payload = _dumps(events)
        budget = cfg.trace_kv_push_budget
        while len(payload) > budget and events:
            events = events[-(len(events) // 2):] if len(events) > 1 else []
            payload = _dumps(events)
        # Degenerate guard: high-cardinality metric snapshots (per-tenant
        # counters etc.) can pickle past the budget with NO events at
        # all.  The push must never ship a chunk-sized pickle onto the
        # control plane, so halve the snapshot list too — prometheus is
        # a best-effort view; the next push re-snapshots everything.
        while len(payload) > budget and len(snaps) > 1:
            snaps = snaps[:len(snaps) // 2]
            payload = _dumps(events)
        return payload

    async def rpc_pubsub(self, conn, body):
        """GCS pubsub push (driver-side: mirrored worker logs, error
        events — reference: the driver's log/error subscriber threads in
        python/ray/_private/worker.py listen_error_messages etc.)."""
        handler = self._pubsub_handlers.get(body.get("channel"))
        if handler is not None:
            try:
                handler(body.get("message"))
            except Exception:
                pass
        return None

    async def rpc_pubsub_gap(self, conn, body):
        """The GCS shed some of this subscriber's events (slow-consumer
        bound).  Driver-side channels (logs, actor events) are
        best-effort streams with their own backstops, so the gap is
        tolerated silently."""
        return None

    async def rpc_pubsub_batch(self, conn, body):
        """Coalesced GCS pubsub: one frame carrying a same-channel run
        of messages (publish order preserved) — fanned out to the same
        per-channel handler as single pushes."""
        handler = self._pubsub_handlers.get(body.get("channel"))
        if handler is not None:
            for message in protocol.pubsub_batch_messages(body):
                try:
                    handler(message)
                except Exception:
                    pass
        return None

    # ======================================================= OWNER-SIDE API
    def put(self, value, _owner_ref=None) -> ObjectRef:
        blob, _nested = serialization.serialize(value)
        return self._run(self._put_blob(blob))

    async def _put_blob(self, blob, object_id=None) -> ObjectRef:
        oid = object_id or ObjectID.for_put()
        entry = OwnedObject()
        entry.local_refs = 1
        self.owned[oid] = entry
        size = blob.total_size()
        # state is written LAST: the sync-get fast path reads ready()
        # lock-free from other threads, so blob/location/size must be
        # visible before the state flip (GIL gives the ordering).
        if size <= cfg.max_direct_call_object_size or self.raylet is None:
            entry.blob = blob.to_bytes()
            entry.size = size
            entry.state = INLINE
        else:
            offset = await self._store_create(oid.binary(), size)
            if offset is not None:
                blob.write_into(self.mapping.slice(offset, size))
                await self.raylet.request("os_seal", {"oid": oid.binary()})
            entry.location = self.node_id
            entry.size = size
            entry.state = IN_STORE
        entry.set_ready()
        return ObjectRef(oid, owner_addr=self.addr, _track=True)

    async def _store_create(self, oid_bin: bytes, size: int):
        """Allocate ``oid`` in the local store; returns the arena offset,
        or None when a copy already exists there (idempotent create —
        reconstruction re-ran the producing task on a node that never
        lost the object; the caller skips its write+seal)."""
        reply = await self.raylet.request("os_create",
                                          {"oid": oid_bin, "size": size})
        if "error" in reply:
            raise rexc.ObjectLostError(oid_bin.hex(), reply["error"])
        if reply.get("exists"):
            return None
        return reply["offset"]

    def get(self, refs, timeout=None):
        if isinstance(refs, ObjectRef):
            return self._get_sync_single(refs, timeout)
        return self._get_sync_list(refs, timeout)

    def object_meta(self, refs) -> dict:
        """Driver-side metadata for OWNED, READY refs without touching
        the bytes: {ref.id: (size_bytes, NodeID_or_None, errored)}.
        Pending / borrowed refs are simply absent.  The data layer's
        streaming executor uses this for budget accounting and
        locality-aware placement — blocks must not ride through the
        driver just to learn their size or location."""
        out = {}
        for r in refs:
            entry = self.owned.get(r.id)
            if entry is None or not entry.ready():
                continue
            out[r.id] = (entry.size, entry.location,
                         entry.state == ERRORED)
        return out

    def object_locations(self, refs, timeout: float = 5.0) -> dict:
        """{ref.id: [NodeID, ...]} of believed sealed-copy holders:
        the owner-recorded primary location plus whatever the GCS
        object directory (rpc_get_object_locations — populated for
        stripe-size objects) knows of.  Best-effort: a missing or
        unreachable directory degrades to the primary copy only."""
        out = {}
        lookups = []
        for r in refs:
            entry = self.owned.get(r.id)
            locs = []
            if entry is not None and entry.location is not None:
                locs.append(entry.location)
            out[r.id] = locs
            lookups.append(r.id)

        async def _dir(oid):
            try:
                reply = await self._gcs_request(
                    "get_object_locations", {"oid": oid.binary()},
                    timeout=timeout)
                return oid, reply.get("locations", [])
            except Exception:
                return oid, []

        async def _all():
            return await asyncio.gather(*[_dir(o) for o in lookups])

        try:
            for oid, extra in self._run(_all(), timeout=timeout + 5.0):
                for nid in extra:
                    if nid not in out[oid]:
                        out[oid].append(nid)
        except Exception:
            pass
        return out

    @staticmethod
    def _attach_waiter(entry, waiter) -> bool:
        """Attach `waiter` to a pending entry under _CF_LOCK; False if
        the entry is already ready (nothing attached)."""
        with _CF_LOCK:
            if entry.ready():
                return False
            if entry.cf_waiters is None:
                entry.cf_waiters = []
            entry.cf_waiters.append(waiter)
            return True

    @staticmethod
    def _detach_waiter(entry, waiter):
        with _CF_LOCK:
            if entry.cf_waiters is not None:
                try:
                    entry.cf_waiters.remove(waiter)
                except ValueError:
                    pass

    def _get_sync_single(self, ref, timeout):
        """Sync-get fast path for one OWNED ref: attach a plain
        concurrent future directly (lock-ordered against set_ready — no
        loop hop, no self-pipe syscall), wait, then deserialize on the
        calling thread; the loop never spends time deserializing.
        Borrowed refs, in-store objects, and recovery fall back to the
        full async path with whatever remains of the ONE timeout
        budget."""
        deadline = None if timeout is None else time.monotonic() + timeout
        entry = self.owned.get(ref.id)
        if entry is not None and not entry.ready():
            waiter = CFuture()
            if self._attach_waiter(entry, waiter):
                self._notify_blocked()
                try:
                    waiter.result(timeout)
                except (TimeoutError, CFTimeoutError):
                    # CFTimeoutError: on py<3.11 concurrent.futures
                    # raises its OWN TimeoutError, which is NOT the
                    # builtin — the builtin-only clause let the timeout
                    # escape as a raw futures error instead of
                    # GetTimeoutError.  Prune the dead waiter: a caller
                    # polling with short timeouts must not grow
                    # entry.cf_waiters unboundedly.
                    self._detach_waiter(entry, waiter)
                    raise rexc.GetTimeoutError(
                        f"timed out waiting for object {ref.id.hex()}")
                finally:
                    self._notify_unblocked()
        if (entry is not None
                and (entry.state == INLINE or entry.state == ERRORED)):
            value = serialization.deserialize(entry.blob)
            if isinstance(value, _SerializedError):
                raise value.to_exception()
            return value
        # Borrowed / in-store / recovery: async path, remaining budget.
        remaining = self._remain(deadline)
        self._notify_blocked()
        try:
            return self._run(self._get_async_list(
                [ref], remaining, trace=_tracing.current_dict()))[0]
        finally:
            self._notify_unblocked()

    def _get_sync_list(self, refs, timeout):
        """List-get fast path for OWNED refs: ONE countdown latch rides
        every pending entry's waiter list, so a burst of N replies costs
        one thread wake, and all deserialization happens on the calling
        thread.  Any borrowed ref sends the whole call to the async
        path; in-store values resolve through it afterwards with the
        remaining budget."""
        deadline = None if timeout is None else time.monotonic() + timeout
        entries = [self.owned.get(r.id) for r in refs]
        if any(e is None for e in entries):
            self._notify_blocked()
            try:
                return self._run(self._get_async_list(
                    refs, timeout, trace=_tracing.current_dict()))
            finally:
                self._notify_unblocked()
        # Fail fast on errors already in hand, like the gather path did.
        for e in entries:
            if e.ready() and e.state == ERRORED:
                value = serialization.deserialize(e.blob)
                if isinstance(value, _SerializedError):
                    raise value.to_exception()
        latch = _Latch(0)
        wrappers = []
        with _CF_LOCK:
            # One lock region for the whole attach: set_ready can only
            # observe the latch after we release, so the count is final
            # before the first fire.
            for e in entries:
                if not e.ready():
                    if e.cf_waiters is None:
                        e.cf_waiters = []
                    w = _LatchRef(latch, e)
                    e.cf_waiters.append(w)
                    wrappers.append(w)
            latch._n = len(wrappers)
        if wrappers:
            self._notify_blocked()
            try:
                if not latch.event.wait(timeout):
                    for w in wrappers:
                        self._detach_waiter(w.entry, w)
                    raise rexc.GetTimeoutError(
                        f"timed out waiting for {len(refs)} objects")
            finally:
                self._notify_unblocked()
            if latch.errored:
                # A task failed while others may still be running: raise
                # its error NOW (fail-fast), detaching our stakes from
                # the stragglers first.
                for w in wrappers:
                    self._detach_waiter(w.entry, w)
                for e in entries:
                    if e.ready() and e.state == ERRORED:
                        value = serialization.deserialize(e.blob)
                        if isinstance(value, _SerializedError):
                            raise value.to_exception()
        values = []
        slow_idx = []
        for i, e in enumerate(entries):
            if e.state == INLINE or e.state == ERRORED:
                value = serialization.deserialize(e.blob)
                if isinstance(value, _SerializedError):
                    raise value.to_exception()
                values.append(value)
            else:
                values.append(None)
                slow_idx.append(i)
        if slow_idx:
            # In-store (or recovering) objects: async path, shared
            # remaining budget.
            remaining = self._remain(deadline)
            self._notify_blocked()
            try:
                slow_values = self._run(self._get_async_list(
                    [refs[i] for i in slow_idx], remaining,
                    trace=_tracing.current_dict()))
            finally:
                self._notify_unblocked()
            for i, v in zip(slow_idx, slow_values):
                values[i] = v
        return values

    def get_future(self, ref: ObjectRef) -> CFuture:
        return self._call(self._get_one(ref))

    def ready_future(self, ref: ObjectRef) -> CFuture:
        """Thread-safe future firing (with None) when an OWNED ref's
        entry becomes ready; fires immediately for already-ready and
        borrowed refs.  Pairs with try_take_local_value for the serve
        router's unary fast path: no coroutine is spawned per call and
        the value is deserialized on the CALLER's thread, keeping the
        CoreWorker IO loop out of the reply data path."""
        fut = CFuture()
        entry = self.owned.get(ref.id)
        if entry is None or entry.ready() \
                or not self._attach_waiter(entry, fut):
            fut.set_result(None)
        return fut

    def try_take_local_value(self, ref: ObjectRef):
        """(True, value) for a ready owned INLINE entry — deserialized
        on the calling thread (the carried exception is raised for
        ERRORED entries); (False, None) when the full get() path is
        needed (borrowed refs or in-store objects)."""
        entry = self.owned.get(ref.id)
        if entry is None or not entry.ready():
            return False, None
        state = entry.state
        if state != INLINE and state != ERRORED:
            return False, None
        value = serialization.deserialize(entry.blob)
        if isinstance(value, _SerializedError):
            raise value.to_exception()
        return True, value

    async def get_async(self, ref: ObjectRef):
        return await self._get_one(ref)

    async def _get_async_list(self, refs, timeout=None, trace=None):
        """``trace`` is the CALLER THREAD's span context: the sync get
        paths capture it before hopping to the IO loop (contextvars do
        not cross run_coroutine_threadsafe), so a store fetch that
        escalates into a transfer-plane pull stays in the task's
        trace."""
        deadline = None if timeout is None else time.monotonic() + timeout
        coros = [self._get_one(r, deadline, trace) for r in refs]
        return list(await asyncio.gather(*coros))

    async def _get_one(self, ref: ObjectRef, deadline=None, trace=None):
        blob = await self._resolve_blob(ref, deadline, trace)
        value = serialization.deserialize(blob)
        if isinstance(value, _SerializedError):
            raise value.to_exception()
        return value

    async def _resolve_blob(self, ref: ObjectRef, deadline=None,
                            trace=None):
        entry = self.owned.get(ref.id)
        if entry is not None:
            if not entry.ready():
                await self._wait_event(entry.event, deadline,
                                       f"object {ref.id.hex()}")
            if entry.state == INLINE:
                return entry.blob
            if entry.state == ERRORED:
                return entry.blob
            try:
                return await self._fetch_from_store(ref.id, entry.location,
                                                    deadline, trace)
            except rexc.ObjectLostError:
                # The node holding the primary copy died: reconstruct by
                # re-executing the creating task, then re-resolve.
                await self._recover_object(ref.id, entry)
                if entry.state in (INLINE, ERRORED):
                    return entry.blob
                return await self._fetch_from_store(ref.id, entry.location,
                                                    deadline, trace)
        # Borrowed ref: ask the owner.
        cached = self._borrow_cache.get(ref.id)
        if cached is not None:
            return cached
        if ref.owner_addr is None:
            raise rexc.ObjectLostError(ref.id.hex(), "no owner address")
        owner = await self._owner_conn(tuple(ref.owner_addr))
        status = await owner.request("get_object_status", {"oid": ref.id},
                                     timeout=self._remain(deadline))
        if status.get("error") is not None:
            return status["error"]  # serialized error blob
        if "blob" in status:
            self._borrow_cache[ref.id] = status["blob"]
            return status["blob"]
        try:
            return await self._fetch_from_store(ref.id, status["location"],
                                                deadline, trace)
        except rexc.ObjectLostError:
            # Report the loss to the owner, who recovers via lineage and
            # tells us where the object lives now.
            status = await owner.request("recover_object", {"oid": ref.id},
                                         timeout=self._remain(deadline))
            if status.get("error") is not None:
                return status["error"]
            if "blob" in status:
                self._borrow_cache[ref.id] = status["blob"]
                return status["blob"]
            return await self._fetch_from_store(
                ref.id, status["location"], deadline, trace)

    async def _fetch_from_store(self, oid: ObjectID, location,
                                deadline=None, trace=None):
        if self.raylet is None:
            raise rexc.ObjectLostError(oid.hex(), "no raylet (local mode)")
        # The remaining budget travels as ONE deadline: the raylet
        # charges every wait and every pulled chunk against it (a
        # stop-and-wait transfer used to re-grant the full timeout per
        # chunk).  The RPC timeout is slightly larger so the raylet's
        # own deadline error wins the race and keeps its detail.
        budget = self._remain(deadline) or 60.0
        body = {"oid": oid.binary(), "location": location,
                "timeout": budget}
        if trace is None:
            # Async callers (actor coroutines) still carry the context
            # in THIS task; sync callers captured it pre-hop.
            trace = _tracing.current_dict()
        if trace is not None and location is not None:
            # The trace crosses into the raylet only when a remote pull
            # may run (a local sealed copy records nothing): flow-start
            # here, flow-finish inside TransferManager.pull.
            trace = dict(trace, flow=_tracing.fresh_id())
            _tracing.flow_start(trace["flow"], "transfer")
            body["trace"] = trace
        reply = await self.raylet.request("os_get", body,
                                          timeout=budget + 5.0)
        if "error" in reply:
            if reply.get("timeout"):
                # The resolution ran out of the caller's budget — that
                # is a timeout, not a lost object: reconstruction would
                # re-execute the producing task for an object that still
                # exists on its node.
                raise rexc.GetTimeoutError(
                    f"object {oid.hex()}: {reply['error']}")
            raise rexc.ObjectLostError(oid.hex(), reply["error"])
        binary = oid.binary()
        self._pinned.add(binary)
        mv = self.mapping.slice(reply["offset"], reply["size"])

        def _release():
            if self._shutdown or self.loop is None or self.raylet is None:
                return
            self._pinned.discard(binary)
            try:
                asyncio.run_coroutine_threadsafe(
                    self.raylet.request("os_release", {"oid": binary}),
                    self.loop)
            except Exception:
                pass

        pv = _PinView(mv, _release)
        try:
            # Zero-copy: the returned view keeps the read-pin alive via
            # _PinView.__buffer__ (PEP 688, Python >= 3.12).
            return memoryview(pv)
        except TypeError:
            # Python < 3.12 ignores __buffer__ — memoryview() refuses
            # the wrapper.  Disarm pv FIRST (its __del__ must not
            # release the pin out from under the copy), copy under the
            # pin, then release exactly once; one copy per store fetch
            # beats every remote get() crashing.
            pv._cb = None
            data = bytes(mv)
            _release()
            return data

    @staticmethod
    def _remain(deadline):
        if deadline is None:
            return None
        return max(0.001, deadline - time.monotonic())

    async def _wait_event(self, event, deadline, what):
        if deadline is None:
            await event.wait()
        else:
            try:
                await asyncio.wait_for(event.wait(), self._remain(deadline))
            except asyncio.TimeoutError:
                raise rexc.GetTimeoutError(f"timed out waiting for {what}")

    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        self._notify_blocked()
        try:
            return self._run(self._wait_async(refs, num_returns, timeout,
                                              fetch_local))
        finally:
            self._notify_unblocked()

    async def _wait_async(self, refs, num_returns, timeout,
                          fetch_local=True):
        pending = list(refs)
        ready: list = []
        deadline = None if timeout is None else time.monotonic() + timeout

        async def _ready_one(r):
            if not fetch_local:
                # Readiness only, no byte movement: an OWNED ref is
                # ready when its entry lands (task finished / put
                # sealed) — resolving the blob here would PULL the
                # store copy to this node, which is exactly what the
                # streaming executor's handle plumbing must avoid
                # (fetch_local=True used to be silently forced).
                # Borrowed refs still resolve (the owner round trip is
                # what determines readiness for them).
                entry = self.owned.get(r.id)
                if entry is not None:
                    if not entry.ready():
                        await entry.event.wait()
                    return r
            await self._resolve_blob(r)
            return r

        tasks = {asyncio.ensure_future(_ready_one(r)): r for r in pending}
        try:
            while len(ready) < num_returns and tasks:
                done, _ = await asyncio.wait(
                    tasks.keys(), timeout=self._remain(deadline),
                    return_when=asyncio.FIRST_COMPLETED)
                if not done:
                    break
                for t in done:
                    r = tasks.pop(t)
                    if t.exception() is None:
                        ready.append(r)
                    else:
                        ready.append(r)  # errored objects count as ready
            not_ready = [tasks[t] for t in tasks]
        finally:
            for t in tasks:
                t.cancel()
        order = {id(r): i for i, r in enumerate(refs)}
        ready.sort(key=lambda r: order.get(id(r), 0))
        return ready, not_ready

    async def _owner_conn(self, addr: tuple) -> protocol.Connection:
        conn = self._owner_conns.get(addr)
        if conn is None or conn.closed:
            conn = await protocol.Connection.connect(
                addr[0], addr[1], handler=self._handle, name="cw->owner",
                timeout=cfg.connect_timeout_s)
            self._owner_conns[addr] = conn
        return conn

    async def rpc_get_object_status(self, conn, body):
        """Serve borrowers asking about an object we own (reference:
        CoreWorkerService GetObjectStatus)."""
        oid: ObjectID = body["oid"]
        entry = self.owned.get(oid)
        if entry is None:
            return {"error": _error_blob(
                rexc.ObjectLostError(oid.hex(), "owner has no record"))}
        if not entry.ready():
            await entry.event.wait()
        if entry.state == INLINE:
            return {"blob": entry.blob}
        if entry.state == ERRORED:
            return {"error": entry.blob}
        return {"location": entry.location, "size": entry.size}

    async def rpc_recover_object(self, conn, body):
        """A borrower failed to fetch an object we own: reconstruct it via
        lineage and reply with the fresh status (reference: owner-driven
        recovery, object_recovery_manager.h:41)."""
        oid: ObjectID = body["oid"]
        entry = self.owned.get(oid)
        if entry is None:
            return {"error": _error_blob(
                rexc.ObjectLostError(oid.hex(), "owner has no record"))}
        try:
            if entry.ready() and entry.state == IN_STORE:
                await self._recover_object(oid, entry)
        except rexc.ObjectLostError as e:
            return {"error": _error_blob(e)}
        if not entry.ready():
            await entry.event.wait()
        if entry.state == INLINE:
            return {"blob": entry.blob}
        if entry.state == ERRORED:
            return {"error": entry.blob}
        return {"location": entry.location, "size": entry.size}

    async def _recover_object(self, oid: ObjectID, entry: OwnedObject):
        """Re-execute the task that created `oid` (reference:
        TaskManager::ResubmitTask task_manager.h:135).  Deduped per task:
        concurrent losses of sibling returns re-execute once."""
        spec = entry.submitted_task
        if spec is None:
            raise rexc.ObjectLostError(
                oid.hex(), "object lost and not reconstructable "
                           "(ray_tpu.put objects have no lineage)")
        task_id = spec["task_id"]
        fut = self._recovering.get(task_id)
        if fut is not None:
            await asyncio.shield(fut)
            return
        fut = self._recovering[task_id] = self.loop.create_future()
        try:
            reexecutions = []
            for rid in spec["return_ids"]:
                e = self.owned.get(rid)
                if e is None:
                    continue
                if e.reconstructions >= cfg.max_object_reconstructions:
                    raise rexc.ObjectLostError(
                        oid.hex(),
                        f"exceeded {cfg.max_object_reconstructions} "
                        "reconstruction attempts")
                e.reconstructions += 1
                e.state = PENDING
                e.blob = None
                e.location = None
                e.event = asyncio.Event()
                reexecutions.append(rid)
            if oid not in spec["return_ids"]:
                # A dynamic-returns sub-object: not listed in the spec's
                # return ids, so reset it here — re-execution re-enters
                # the dynamic branch and fires THIS entry's fresh event.
                if entry.reconstructions >= \
                        cfg.max_object_reconstructions:
                    raise rexc.ObjectLostError(
                        oid.hex(),
                        f"exceeded {cfg.max_object_reconstructions} "
                        "reconstruction attempts")
                entry.reconstructions += 1
                entry.state = PENDING
                entry.blob = None
                entry.location = None
                entry.event = asyncio.Event()
                reexecutions.append(oid)
            logger.warning(
                "reconstructing %d object(s) by re-executing task %s",
                len(reexecutions), task_id.hex()[:8])
            self._pin_args_from_lineage(task_id)
            await self._submit(TaskSpec(spec))
            await entry.event.wait()
            if not fut.done():
                fut.set_result(True)
        except Exception as e:
            if not fut.done():
                fut.set_exception(e)
            raise
        finally:
            self._recovering.pop(task_id, None)
            # Consume fut's exception if nobody else awaited it.
            if fut.done() and fut.exception() is not None:
                fut.exception()

    def _pin_args_from_lineage(self, task_id):
        pins = self._lineage_pins.pop(task_id, None)
        if pins is not None:
            self._arg_pins[task_id] = pins

    # ----------------------------------------------------------- refcounting
    def add_local_ref(self, ref: ObjectRef):
        entry = self.owned.get(ref.id)
        if entry is not None:
            entry.local_refs += 1

    def remove_local_ref(self, ref: ObjectRef):
        if self._shutdown or not self.connected:
            return
        entry = self.owned.get(ref.id)
        if entry is None:
            return
        entry.local_refs -= 1
        if entry.local_refs <= 0 and entry.ready():
            self.owned.pop(ref.id, None)
            # A dynamic-returns main entry carries its yields' pins:
            # release them with it (their untracked refs in the
            # ObjectRefGenerator share the outer ref's lifetime).
            for child in entry.dynamic_children or ():
                self.remove_local_ref(ObjectRef(child,
                                                owner_addr=self.addr))
            if entry.state == IN_STORE and self.loop is not None:
                try:
                    self._call(self._delete_store_object(ref.id, entry))
                except Exception:
                    pass
            spec = entry.submitted_task
            if spec is not None and all(rid not in self.owned
                                        for rid in spec["return_ids"]):
                # Last live return gone: release the lineage + arg pins.
                self._lineage.pop(spec["task_id"], None)
                self._lineage_pins.pop(spec["task_id"], None)

    async def _delete_store_object(self, oid: ObjectID, entry):
        try:
            if entry.location == self.node_id and self.raylet is not None:
                await self.raylet.request("os_delete", {"oid": oid.binary()})
        except Exception:
            pass

    # ==================================================== TASK SUBMISSION
    def export_function(self, fn) -> bytes:
        blob = serialization.dumps_function(fn)
        import hashlib
        fn_id = hashlib.sha1(blob).digest()[:16]
        if fn_id not in self._exported_fns:
            self._run(self._gcs_request("kv_put", {
                "ns": "funcs", "key": fn_id, "value": blob}))
            self._exported_fns.add(fn_id)
            self._fn_cache[fn_id] = fn
        return fn_id

    def submit_task(self, fn_id: bytes, args, kwargs, opts: dict):
        task_id = TaskID.for_submit()
        num_returns = opts.get("num_returns", 1)
        # "dynamic": one visible return (the ObjectRefGenerator); the
        # per-yield objects get ids for_task_return(task_id, 1..N) on
        # the executing side and register with the owner on reply.
        dynamic = num_returns == "dynamic"
        if dynamic:
            num_returns = 1
        refs = []
        for i in range(num_returns):
            oid = ObjectID.for_task_return(task_id, i)
            entry = OwnedObject()
            entry.local_refs = 1
            self.owned[oid] = entry
            refs.append(ObjectRef(oid, owner_addr=self.addr, _track=True))
        args_blob = self._pack_args(args, kwargs)
        pg = opts.get("placement_group")
        trace = _trace_for_submit()
        # Submit-side flow start: the execution span (possibly another
        # process) closes the edge, connecting the waterfall.  No flow
        # id = un-spanned submit (nothing to connect from; keeps the
        # ambient per-call cost at one ring event).
        if "flow" in trace:
            _tracing.flow_start(trace["flow"])
        spec = TaskSpec.new(
            task_id=task_id,
            fn_id=fn_id,
            args_blob=args_blob,
            num_returns=-1 if dynamic else num_returns,
            owner_addr=self.addr,
            return_ids=[r.id for r in refs],
            resources=_normalize_resources(opts),
            strategy=_strategy_dict(opts.get("scheduling_strategy")),
            max_retries=opts.get("max_retries",
                                 cfg.max_task_retries_default),
            retry_exceptions=opts.get("retry_exceptions", False),
            name=opts.get("name", ""),
            trace=trace,
            runtime_env=(self._pack_runtime_env(opts["runtime_env"])
                         if opts.get("runtime_env") else None),
            pg_id=pg.id if pg is not None else None,
            bundle_index=opts.get("placement_group_bundle_index", -1),
        ).validate()
        # Lineage: keep the spec on every return so a lost object can be
        # reconstructed by re-executing the task (reference:
        # task_manager.h:86 lineage, object_recovery_manager.h:90).
        # num_returns=0 tasks have nothing to reconstruct — recording
        # lineage for them would leak specs+arg pins forever (cleanup runs
        # from remove_local_ref over return refs).
        if refs:
            for r in refs:
                self.owned[r.id].submitted_task = spec
            self._lineage[task_id] = spec
        self._pin_args(task_id, args, kwargs)
        if task_id in self._arg_pins:
            self._call(self._submit(spec))
        else:
            # No ObjectRef args -> nothing to await before dispatch; a
            # coalesced post skips run_coroutine_threadsafe's coroutine +
            # future-chaining overhead AND shares one loop wake across a
            # submission burst.
            self._post(self._enqueue_spec, spec)
        return refs

    def cancel_task(self, ref, force: bool = False) -> bool:
        """Cancel the task that produces `ref` (reference: ray.cancel,
        core_worker CancelTask): queued tasks are dequeued and their
        returns error with TaskCancelledError; running tasks are killed
        only with force=True (their worker is torn down)."""
        entry = self.owned.get(ref.id)
        spec = entry.submitted_task if entry is not None else None
        if spec is None:
            # Actor tasks: cancellable only while still queued in the
            # per-actor send queue (not yet on the wire).
            if self._run(self._cancel_queued_actor(ref.id)):
                return True
            raise ValueError(
                "ray_tpu.cancel only applies to normal-task returns "
                "and queued-but-unsent actor tasks: puts have no task, "
                "completed-and-released tasks are gone, and an actor "
                "task already on the wire cannot be cancelled (kill "
                "the actor instead)")
        return self._run(self._cancel(spec, force))

    async def _cancel(self, spec, force: bool) -> bool:
        task_id = spec["task_id"]
        key = self._scheduling_key(spec)
        pool = self.lease_pools.get(key)
        if pool is not None and any(s is spec for s in pool.queue):
            pool.queue[:] = [s for s in pool.queue if s is not spec]
            self._complete_with_error(spec, rexc.TaskCancelledError(
                f"task {task_id.hex()[:8]} cancelled before start"))
            # Re-pump: with the queue drained this cancels the stale
            # outstanding lease request, or a granted lease would park
            # in pool.idle forever holding its worker's resources.
            self._pump(key)
            return True
        inflight = self._inflight_tasks.get(task_id)
        if inflight is not None:
            lease, ispec = inflight
            if force:
                # Mark ONLY when actually stopping: a no-op cancel must
                # not poison later legitimate retries/reconstruction.
                ispec["cancelled"] = True
                self._drop_lease(key, lease)
                return True
            return False
        return False

    def _pack_runtime_env(self, runtime_env):
        from ray_tpu import runtime_env as renv

        def _kv_put(ns, key, value):
            self._run(self._gcs_request("kv_put", {
                "ns": ns, "key": key, "value": value}))

        return renv.pack(runtime_env, _kv_put)

    def _apply_runtime_env(self, runtime_env):
        """Executor side: materialize packages + env vars (reference:
        runtime-env creation before task execution).  Returns a restore
        callable: pooled workers are REUSED across tasks, so env vars /
        cwd / sys.path must not leak into the next task (the reference
        instead dedicates workers per runtime env)."""
        if not runtime_env:
            return None
        import sys
        from ray_tpu import runtime_env as renv

        def _kv_get(ns, key):
            return self._run(self._gcs_request(
                "kv_get", {"ns": ns, "key": key}))["value"]

        cache = os.path.join(
            os.environ.get("RT_SESSION_DIR", "/tmp/ray_tpu"),
            "runtime_envs")
        saved_env = dict(os.environ)
        saved_cwd = os.getcwd()
        saved_path = list(sys.path)

        def _restore():
            os.environ.clear()
            os.environ.update(saved_env)
            try:
                os.chdir(saved_cwd)
            except OSError:
                pass
            sys.path[:] = saved_path

        try:
            renv.apply(runtime_env, _kv_get, cache)
        except BaseException:
            # A half-applied env (vars set, package missing) must not
            # leak into the pooled worker.
            _restore()
            raise
        return _restore

    def _pin_args(self, task_id, args, kwargs):
        """Keep ObjectRef args alive until the task completes.  Keyed by
        task_id so num_returns=0 (fire-and-forget) tasks pin too."""
        pins = [a for a in args if isinstance(a, ObjectRef)]
        pins += [v for v in kwargs.values() if isinstance(v, ObjectRef)]
        if pins:
            self._arg_pins[task_id] = pins

    def _unpin_args(self, task_id):
        if task_id is None:
            return
        pins = self._arg_pins.pop(task_id, None)
        # While the task's lineage is retained (its returns may need
        # reconstruction), its args must stay fetchable: move the pins to
        # the lineage table instead of dropping them (reference: lineage
        # pinning of task dependencies, reference_count.h borrower docs).
        if pins is not None and task_id in self._lineage:
            self._lineage_pins[task_id] = pins

    _EMPTY_ARGS_BLOB: bytes | None = None

    def _pack_args(self, args, kwargs):
        if not args and not kwargs:
            blob = CoreWorker._EMPTY_ARGS_BLOB
            if blob is None:
                b, _ = serialization.serialize(([], {}))
                blob = CoreWorker._EMPTY_ARGS_BLOB = b.to_bytes()
            return blob
        new_args = [(_RefArg(a) if isinstance(a, ObjectRef) else a)
                    for a in args]
        new_kwargs = {k: (_RefArg(v) if isinstance(v, ObjectRef) else v)
                      for k, v in kwargs.items()}
        blob, _nested = serialization.serialize((new_args, new_kwargs))
        return blob.to_bytes()

    def _scheduling_key(self, spec):
        res = tuple(sorted(spec["resources"].items()))
        strat = spec.get("strategy")
        strat_key = tuple(sorted(strat.items())) if strat else None
        from ray_tpu.runtime_env import pip_env_key
        return (spec["fn_id"], res, strat_key, spec.get("pg_id"),
                spec.get("bundle_index"),
                pip_env_key(spec.get("runtime_env")))

    async def _submit(self, spec):
        await self._wait_args_ready(spec)
        self._enqueue_spec(spec)

    def _enqueue_spec(self, spec):
        key = self._scheduling_key(spec)
        pool = self.lease_pools.get(key)
        if pool is None:
            pool = self.lease_pools[key] = LeasePool()
        pool.queue.append(spec)
        self._pump(key)

    async def _wait_args_ready(self, spec):
        """Dependency resolution BEFORE dispatch (reference:
        DependencyResolver in direct_task_transport.h — a task is pushed
        only once its args exist).  Without this, dispatched tasks sit on
        workers blocking in the arg fetch; each blocked worker releases
        its CPU, the raylet admits yet another task, and an all-to-all
        under memory pressure amplifies into dozens of half-running tasks
        whose pinned args wedge the object store."""
        pins = self._arg_pins.get(spec["task_id"])
        if not pins:
            return
        for ref in pins:
            entry = self.owned.get(ref.id)
            if entry is not None and not entry.ready():
                await entry.event.wait()

    def _pump(self, key):
        pool = self.lease_pools[key]
        while pool.queue and pool.idle:
            lease = pool.idle.pop()
            timer = pool.return_timers.pop(lease["lease_id"], None)
            if timer is not None:
                timer.cancel()
            spec = pool.queue.pop(0)
            self.loop.create_task(self._push_on_lease(key, lease, spec))
        backlog = len(pool.queue)
        if backlog == 0 and pool.outstanding:
            self._cancel_outstanding(pool)
        # One lease wanted per queued task (capped): a busy lease must
        # NOT count as covering the backlog — its task may run for
        # hours, and parallelism must never depend on task duration.
        # (Regression: a lingering warm lease made the pool dispatch
        # task A onto it and then request nothing for task B, fully
        # serializing two same-key tasks — caught by the dask-on-ray
        # rendezvous test.)
        want = min(backlog, 8) - pool.requests_inflight
        for _ in range(max(0, want)):
            pool.requests_inflight += 1
            self.loop.create_task(self._request_lease(key))

    def _cancel_outstanding(self, pool):
        by_conn: dict[int, tuple] = {}
        for rid, conn in pool.outstanding.items():
            by_conn.setdefault(id(conn), (conn, []))[1].append(rid)
        pool.outstanding.clear()
        for conn, rids in by_conn.values():
            if not conn.closed:
                self.loop.create_task(self._send_cancel(conn, rids))

    async def _send_cancel(self, conn, rids):
        try:
            await conn.request("cancel_lease_requests", {"request_ids": rids})
        except Exception:
            pass

    async def _request_lease(self, key):
        pool = self.lease_pools[key]
        spec_probe = pool.queue[0] if pool.queue else None
        request_id = os.urandom(8)
        try:
            if spec_probe is None:
                return
            body = {
                "resources": spec_probe["resources"],
                "strategy": spec_probe.get("strategy"),
                "pg_id": spec_probe.get("pg_id"),
                "bundle_index": spec_probe.get("bundle_index"),
                "request_id": request_id,
            }
            renv = spec_probe.get("runtime_env") or {}
            from ray_tpu.runtime_env import env_spec, worker_env_key
            espec = env_spec(renv)
            if espec:
                body["env_key"] = worker_env_key(renv)
                body["env_spec"] = espec
            conn = self.raylet
            if spec_probe.get("pg_id") is not None:
                conn = await self._raylet_for_bundle(
                    spec_probe["pg_id"], spec_probe.get("bundle_index"))
            for _hop in range(4):
                pool.outstanding[request_id] = conn
                # Explicit timeout=None (NOT the config default
                # deadline): a cluster-wide-infeasible request stays
                # queued at the raylet as autoscaler demand (reference:
                # infeasible tasks wait for scale-up, they don't error).
                # Conn loss / keepalive / cancellation still wake this.
                reply = await conn.request("request_worker_lease", body,
                                           timeout=None)
                pool.outstanding.pop(request_id, None)
                if "spillback" in reply:
                    addr = tuple(reply["spillback"])
                    conn = await self._raylet_conn(addr)
                    body = dict(body)
                    body["strategy"] = None  # don't re-spread at the target
                    # A spilled request must not bounce again on the
                    # target's (possibly stale) view of us — it queues
                    # there instead (reference: spillback counts in the
                    # lease protocol prevent ping-pong).
                    body["hops"] = body.get("hops", 0) + 1
                    continue
                break
            if reply.get("cancelled"):
                # A task enqueued during the cancel round trip saw
                # requests_inflight > 0 and issued no request of its
                # own — re-pump so it gets one (this exit path must
                # behave like every other one).
                self.loop.call_soon(self._pump, key)
                return
            if "error" in reply:
                self._fail_queued(key, rexc.RayTpuError(reply["error"]))
                return
            if "worker_addr" not in reply:
                self._fail_queued(key, rexc.RayTpuError(
                    f"lease not granted after spillback hops: {reply}"))
                return
            worker_addr = tuple(reply["worker_addr"])
            wconn = await self._worker_conn(worker_addr)
            lease = {
                "lease_id": reply["lease_id"],
                "conn": wconn,
                "raylet": conn,
                "node_id": reply["node_id"],
                "worker_addr": worker_addr,
                "busy": False,
                "tpu_ids": reply.get("tpu_ids") or [],
            }
            pool.all[lease["lease_id"]] = lease
            pool.idle.append(lease)
        except Exception as e:
            logger.warning("lease request failed: %s", e)
            self._fail_queued(key, e)
            return
        finally:
            pool.requests_inflight -= 1
        self._pump(key)
        # Granted after the backlog drained (a finishing task absorbed
        # the queue): without a linger timer this lease would park its
        # worker forever.
        if (lease in pool.idle
                and lease["lease_id"] not in pool.return_timers):
            self._schedule_lease_return(key, lease)

    def _fail_queued(self, key, exc):
        pool = self.lease_pools.get(key)
        if pool is None:
            return
        while pool.queue:
            spec = pool.queue.pop(0)
            self._complete_with_error(spec, exc)

    def _complete_with_error(self, spec, exc):
        self._unpin_args(spec.get("task_id"))
        blob = _error_blob(exc if isinstance(exc, Exception)
                           else rexc.RayTpuError(str(exc)))
        for oid in spec["return_ids"]:
            entry = self.owned.get(oid)
            if entry is not None:
                entry.blob = blob
                entry.state = ERRORED  # last: lock-free readers order on it
                entry.set_ready()

    async def _raylet_for_bundle(self, pg_id, bundle_index):
        """Route a placement-group lease to the raylet holding the bundle
        (reference: PG-aware lease targeting via the bundle's node)."""
        view = await self._gcs_request(
            "wait_placement_group", {"pg_id": pg_id, "timeout": 60.0})
        if view is None or view.get("state") != "CREATED":
            raise rexc.RayTpuError(
                f"placement group {pg_id.hex()[:8]} not ready "
                f"(state={view and view.get('state')})")
        bundle_nodes = view["bundle_nodes"]
        if bundle_index is not None and bundle_index >= 0:
            node_ids = [bundle_nodes[bundle_index]]
        else:
            node_ids = list(dict.fromkeys(bundle_nodes))
        nodes = await self._gcs_request("get_nodes", {})
        by_id = {n["node_id"]: n for n in nodes}
        for nid in node_ids:
            nview = by_id.get(nid)
            if nview is not None and nview.get("alive"):
                if nid == self.node_id:
                    return self.raylet
                return await self._raylet_conn(tuple(nview["addr"]))
        raise rexc.RayTpuError(
            f"no alive node holds bundles of pg {pg_id.hex()[:8]}")

    async def _raylet_conn(self, addr):
        key = ("raylet",) + tuple(addr)
        conn = self._worker_conns.get(key)
        if conn is None or conn.closed:
            conn = await protocol.Connection.connect(
                addr[0], addr[1], handler=self._handle, name="cw->raylet2",
                timeout=cfg.connect_timeout_s)
            self._worker_conns[key] = conn
        return conn

    async def _worker_conn(self, addr):
        conn = self._worker_conns.get(tuple(addr))
        if conn is None or conn.closed:
            conn = await protocol.Connection.connect(
                addr[0], addr[1], handler=self._handle, name="cw->worker",
                timeout=cfg.connect_timeout_s)
            self._worker_conns[tuple(addr)] = conn
        return conn

    async def _push_on_lease(self, key, lease, spec):
        pool = self.lease_pools[key]
        lease["busy"] = True
        self._inflight_tasks[spec["task_id"]] = (lease, spec)
        try:
            reply = await lease["conn"].request("push_task", {
                "spec": spec, "lease_id": lease["lease_id"],
                "tpu_ids": lease.get("tpu_ids") or []}, timeout=None)
            self._record_results(spec, reply)
        except Exception as e:
            if spec.get("cancelled"):
                # _cancel already dropped this lease; don't double-kill.
                self._complete_with_error(spec, rexc.TaskCancelledError(
                    f"task {spec['task_id'].hex()[:8]} cancelled"))
                self._pump(key)
                return
            self._drop_lease(key, lease)
            retries = spec.get("max_retries", 0)
            if retries != 0 and _is_system_error(e):
                spec["max_retries"] = retries - 1 if retries > 0 else retries
                logger.info("retrying task %s after worker failure: %s",
                            spec["name"] or spec["task_id"].hex()[:8], e)
                pool.queue.append(spec)
            else:
                self._complete_with_error(spec, e)
            self._pump(key)
            return
        finally:
            self._inflight_tasks.pop(spec["task_id"], None)
        lease["busy"] = False
        if pool.queue:
            pool.idle.append(lease)
            self._pump(key)
        else:
            self._schedule_lease_return(key, lease)
            pool.idle.append(lease)

    def _schedule_lease_return(self, key, lease):
        """Linger briefly before returning the lease: a tight
        submit/get loop re-uses it without a fresh lease round trip."""
        pool = self.lease_pools[key]
        handle = self.loop.call_later(
            0.02, lambda: self.loop.create_task(
                self._return_lease(key, lease)))
        pool.return_timers[lease["lease_id"]] = handle

    async def _return_lease(self, key, lease):
        pool = self.lease_pools.get(key)
        if pool is None:
            return
        # The timer may have FIRED before _pump claimed the lease for a
        # new task (cancel() on a fired handle is a no-op).  _pump pops
        # return_timers when it claims — if our entry is gone, the lease
        # is busy again: returning it now would reclaim the worker
        # mid-push.
        if lease["lease_id"] not in pool.return_timers:
            return
        if lease in pool.idle:
            pool.idle.remove(lease)
        pool.all.pop(lease["lease_id"], None)
        pool.return_timers.pop(lease["lease_id"], None)
        try:
            await lease["raylet"].request("return_worker",
                                          {"lease_id": lease["lease_id"]})
        except Exception:
            pass

    def _drop_lease(self, key, lease):
        pool = self.lease_pools.get(key)
        if pool is None:
            return
        if lease in pool.idle:
            pool.idle.remove(lease)
        pool.all.pop(lease["lease_id"], None)
        try:
            self.loop.create_task(
                lease["raylet"].request("return_worker",
                                        {"lease_id": lease["lease_id"],
                                         "kill": True}))
        except Exception:
            pass

    def _record_results(self, spec, reply):
        self._unpin_args(spec.get("task_id"))
        if "error" in reply:
            blob = reply["error"]
            for oid in spec["return_ids"]:
                entry = self.owned.get(oid)
                if entry is not None:
                    entry.blob = blob
                    entry.state = ERRORED  # last: lock-free readers
                    entry.set_ready()
            return
        for oid, result in zip(spec["return_ids"], reply["results"]):
            entry = self.owned.get(oid)
            kind = result[0]
            if entry is None:
                if kind == "dynamic":
                    # The visible generator ref was released but
                    # deserialized sub-refs keep their own stakes: a
                    # reconstruction get() may be parked on one of
                    # them.  Refresh the surviving sub entries so those
                    # waiters unblock (skipping this was a permanent
                    # hang: the re-executed generator's results were
                    # dropped here and the PENDING subs never fired).
                    self._record_dynamic_children(result[1], entry=None)
                continue
            if kind == "inline":
                entry.blob = result[1]
                entry.size = len(result[1])
                entry.state = INLINE  # last: lock-free readers order on it
            elif kind == "dynamic":
                # Generator task: register each yielded object as owned
                # HERE (the caller is the owner, as for static returns),
                # then resolve the visible ref to an ObjectRefGenerator.
                # Lineage: subs carry the creating task's spec, so a
                # lost store-resident yield re-executes the generator
                # (recovery re-enters this branch and updates the SAME
                # entry objects in place — waiters' events fire).
                sub_refs, children = self._record_dynamic_children(
                    result[1], entry=entry)
                entry.dynamic_children = children
                from ray_tpu._private.object_ref import ObjectRefGenerator
                blob, _ = serialization.serialize(
                    ObjectRefGenerator(sub_refs))
                entry.blob = blob.to_bytes()
                entry.size = len(entry.blob)
                entry.state = INLINE
            else:  # ("store", node_id, size)
                entry.location = result[1]
                entry.size = result[2]
                entry.state = IN_STORE
            entry.set_ready()

    def _record_dynamic_children(self, records, entry):
        """Register/refresh the per-yield objects of a dynamic-returns
        task.  With `entry` (the task's main owned entry) present this
        is first registration: unknown subs are created and pinned for
        the main entry's lifetime.  With `entry=None` (re-execution
        after the outer ref was released) only subs somebody still owns
        are updated in place — their fresh events fire and parked
        recovery get()s resume."""
        sub_refs = []
        children = []
        for rec in records:
            sub_oid = ObjectID(rec[0])
            sub = self.owned.get(sub_oid)
            if sub is None:
                if entry is None:
                    continue  # released sub of a released generator
                sub = OwnedObject()
            if entry is not None:
                if sub.local_refs == 0:
                    # First registration: the pin lives until the
                    # MAIN entry is released (dynamic_children).
                    sub.local_refs = 1
                sub.submitted_task = entry.submitted_task
            if rec[1] == "inline":
                sub.blob = rec[2]
                sub.size = len(rec[2])
                sub.location = None
                sub.state = INLINE
            else:  # (oid, "store", node_id, size)
                sub.location = rec[2]
                sub.size = rec[3]
                sub.state = IN_STORE
            self.owned[sub_oid] = sub
            sub.set_ready()
            children.append(sub_oid)
            # _track=False: the pin above IS the ownership
            # stake — a tracked temp here would decrement it to
            # zero on GC and drop the entry.
            sub_refs.append(ObjectRef(sub_oid, owner_addr=self.addr))
        return sub_refs, children

    # ------------------------------------------------- blocked notifications
    def _notify_blocked(self):
        ctx = self.exec_ctx
        ctx.blocked_depth += 1
        if (self.mode == MODE_WORKER and ctx.blocked_depth == 1
                and ctx.lease_id is not None and self.raylet is not None):
            try:
                self._call(self.raylet.request("worker_blocked",
                                               {"lease_id": ctx.lease_id}))
            except Exception:
                pass

    def _notify_unblocked(self):
        ctx = self.exec_ctx
        ctx.blocked_depth -= 1
        if (self.mode == MODE_WORKER and ctx.blocked_depth == 0
                and ctx.lease_id is not None and self.raylet is not None):
            try:
                self._call(self.raylet.request("worker_unblocked",
                                               {"lease_id": ctx.lease_id}))
            except Exception:
                pass

    # ======================================================== EXECUTION SIDE
    async def _exec_on_serial_pool(self, pool, fn, *args):
        """run_in_executor replacement for SINGLE-thread pools: a burst
        of queued calls is drained by ONE pool submission (one futex
        wake instead of one per call), and results return to the loop
        through the coalesced _post queue (one self-pipe wake per
        drain).  Execution order on the pool thread == dispatch order —
        the property the serial pools exist for."""
        st = self._exec_states.get(id(pool))
        if st is None:
            st = self._exec_states[id(pool)] = {
                "q": deque(), "armed": False, "pool": pool}
        fut = self.loop.create_future()
        st["q"].append((fn, args, fut))
        if not st["armed"]:
            st["armed"] = True
            pool.submit(self._exec_drain, st)
        return await fut

    def _exec_drain(self, st):  # pool thread
        q = st["q"]
        while True:
            try:
                fn, args, fut = q.popleft()
            except IndexError:
                # Disarm FIRST, then re-check: an append racing the
                # disarm either sees armed and leaves the item to us, or
                # arms a fresh drain — never a stranded item.
                st["armed"] = False
                if q and not st["armed"]:
                    st["armed"] = True
                    continue
                return
            try:
                result, err = fn(*args), None
            except BaseException as e:
                # BaseException: SystemExit/_ActorExit must reach the
                # loop-side awaiter exactly as run_in_executor delivered
                # them (they terminate the worker there).
                result, err = None, e
            self._post(self._finish_serial_exec, fut, result, err)

    @staticmethod
    def _finish_serial_exec(fut, result, err):  # loop thread
        if fut.done():
            return
        if err is not None:
            fut.set_exception(err)
        else:
            fut.set_result(result)

    async def rpc_push_task(self, conn, body):
        spec = body["spec"]
        lease_id = body.get("lease_id")
        return await self._exec_on_serial_pool(
            self._task_pool, self._execute_task_sync, spec, lease_id,
            body.get("tpu_ids") or [])

    def _execute_task_sync(self, spec, lease_id, tpu_ids=()):
        ctx = self.exec_ctx
        ctx.task_id = spec["task_id"]
        ctx.lease_id = lease_id
        ctx.tpu_ids = list(tpu_ids)
        t0 = time.time()
        restore_env = None
        span = self._enter_span(spec.get("trace"))
        try:
            if tpu_ids:
                bind_tpu_chips(tpu_ids)
            restore_env = self._apply_runtime_env(spec.get("runtime_env"))
            fn = self._load_function(spec["fn_id"])
            args, kwargs = self._unpack_args(spec["args"])
            result = fn(*args, **kwargs)
            return self._pack_results(result, spec)
        except Exception as e:
            return {"error": _error_blob(e, traceback.format_exc())}
        finally:
            if restore_env is not None:
                restore_env()
            self._record_profile_event(
                "task", spec.get("name") or getattr(
                    self._fn_cache.get(spec["fn_id"]), "__name__", "task"),
                t0, trace=span)
            ctx.task_id = None
            ctx.lease_id = None
            ctx.tpu_ids = []

    @staticmethod
    def _enter_span(trace, cat: str = "task"):
        """Adopt the submitter's trace context with a fresh span id so
        tasks submitted from here link as children; closes the
        submit-side flow edge (chrome ph "s"/"f" pair)."""
        return _tracing.adopt(trace, cat)

    def _record_profile_event(self, cat: str, name: str, t0: float,
                              trace=None):
        """Chrome-trace complete event (reference: core worker profiling
        events, src/ray/core_worker/profiling.h) into the bounded
        process ring — drop-oldest with a counted, exported drop total
        (was: a bare list that silently deleted half its buffer at
        10k).  Trace args link spans across processes."""
        _tracing.record(cat, name, t0, time.time() - t0, trace=trace)

    async def rpc_dump_trace(self, conn, body):
        """Pull-path trace dump: drain (or stat) this process's span
        ring on demand — the authoritative source for rt timeline
        --cluster / rt trace (the telemetry KV push is a truncated,
        lagging convenience view)."""
        body = body or {}
        return _tracing.dump(stats_only=bool(body.get("stats_only")),
                             clear=bool(body.get("clear")))

    def _load_function(self, fn_id: bytes):
        fn = self._fn_cache.get(fn_id)
        if fn is None:
            reply = self._run(self._gcs_request(
                "kv_get", {"ns": "funcs", "key": fn_id}))
            if reply["value"] is None:
                raise rexc.RayTpuError(f"function {fn_id.hex()} not found")
            fn = serialization.loads_function(reply["value"])
            self._fn_cache[fn_id] = fn
        return fn

    def _get_arg(self, ref):
        """Fetch a task argument without IMMEDIATELY taking the
        blocked-worker CPU release.

        The CPU release exists so user code calling get() on a
        not-yet-scheduled task can't deadlock the pool — but releasing
        it for every arg fetch lets the raylet admit another task whose
        pinned args deepen the very memory pressure stalling the fetch
        (observed: 7 concurrent tasks on a 2-CPU node, the arena 100%
        pinned by their args, every create wedged).  Submitter-owned
        args are dispatch-gated on readiness (_wait_args_ready), so the
        short first attempt covers them; borrowed refs and actor-task
        args are NOT gated, so after the grace window this falls back to
        the releasing path — a fetch truly waiting on an unscheduled
        producer still frees its CPU and the pool keeps moving."""
        try:
            return self._run(self._get_async_list(
                [ref], 2.0, trace=_tracing.current_dict()))[0]
        except Exception:
            pass
        return self.get(ref)

    def _unpack_args(self, args_blob):
        args, kwargs = serialization.deserialize(args_blob)
        args = [self._get_arg(a.ref) if isinstance(a, _RefArg) else a
                for a in args]
        kwargs = {k: (self._get_arg(v.ref) if isinstance(v, _RefArg) else v)
                  for k, v in kwargs.items()}
        return args, kwargs

    def _pack_results(self, result, spec):
        num_returns = spec["num_returns"]
        if num_returns == 0:
            return {"results": []}
        if num_returns == -1:  # num_returns="dynamic": generator task
            import inspect as _inspect
            # Require an actual generator/iterator — a returned str or
            # ndarray is iterable but exploding it into per-element
            # refs is never what the caller meant.
            if not (_inspect.isgenerator(result)
                    or hasattr(result, "__next__")):
                raise TypeError(
                    'num_returns="dynamic" tasks must return a '
                    f"generator/iterator, got {type(result).__name__}")
            task_id = spec["task_id"]
            dyn = []
            for i, value in enumerate(result):
                oid = ObjectID.for_task_return(task_id, i + 1)
                dyn.append((oid.binary(),) + self._pack_one(oid, value))
            return {"results": [("dynamic", dyn)]}
        if num_returns == 1:
            values = [result]
        else:
            values = list(result)
            if len(values) != num_returns:
                raise ValueError(
                    f"task declared num_returns={num_returns} but returned "
                    f"{len(values)} values")
        out = []
        for oid, value in zip(spec["return_ids"], values):
            out.append(self._pack_one(oid, value))
        return {"results": out}

    def _pack_one(self, oid, value):
        """Serialize one return: inline for small values, sealed into
        the local store otherwise."""
        blob, _ = serialization.serialize(value)
        size = blob.total_size()
        if size <= cfg.max_direct_call_object_size or self.raylet is None:
            return ("inline", blob.to_bytes())
        offset = self._run(self._store_create(oid.binary(), size))
        if offset is not None:
            blob.write_into(self.mapping.slice(offset, size))
            self._run(self.raylet.request("os_seal",
                                          {"oid": oid.binary()}))
        return ("store", self.node_id, size)

    # --------------------------------------------------------------- actors
    async def rpc_create_actor(self, conn, body):
        spec = body["spec"]
        self.actor_id = body["actor_id"]
        # Actor-lifetime device grant: every method call of this actor
        # sees the same chip indices (reference: actors keep their GPU
        # ids for their whole lifetime).
        self._actor_tpu_ids = list(body.get("tpu_ids") or [])
        try:
            result = await self.loop.run_in_executor(
                self._task_pool, self._create_actor_sync, spec,
                body.get("worker_start"))
            return result
        except Exception as e:
            return {"ok": False, "error": repr(e),
                    "error_blob": _error_blob(e, traceback.format_exc())}

    def _create_actor_sync(self, spec, worker_start=None):
        # The creation task runs under its submitter's trace like any
        # task, so what the constructor records (a serve replica's
        # start) links under it; `worker_start` is what the raylet
        # measured while this lease waited for a worker.
        t0 = time.time()
        trace = spec.get("trace")
        _tracing.start_begin(trace, worker_start)
        outer = _TRACE.get()
        span = self._enter_span(trace)
        try:
            return self._construct_actor(spec)
        finally:
            _TRACE.set(outer)
            if span is not None:
                _tracing.record(
                    "task", "task.create_actor", t0, time.time() - t0,
                    trace=span,
                    args={"class": spec.get("class_name", "")})

    def _construct_actor(self, spec):
        try:
            if self._actor_tpu_ids:
                bind_tpu_chips(self._actor_tpu_ids)
            self._apply_runtime_env(spec.get("runtime_env"))
            cls = self._load_function(spec["class_id"])
            args, kwargs = self._unpack_args(spec["init_args"])
            import inspect
            self.actor_instance = cls(*args, **kwargs)
            self._actor_is_async = any(
                inspect.iscoroutinefunction(m)
                for _, m in inspect.getmembers(
                    cls, predicate=inspect.isfunction))
            self._max_concurrency = spec.get("max_concurrency") or (
                1000 if self._actor_is_async else 1)
            groups = dict(spec.get("concurrency_groups") or {})
            # Sync methods always need a thread pool — an "async" actor can
            # still define plain def methods (async sems are made lazily).
            self._actor_pools["_default"] = ThreadPoolExecutor(
                max_workers=(1 if self._actor_is_async
                             else self._max_concurrency),
                thread_name_prefix="actor")
            for name, n in groups.items():
                self._actor_pools[name] = ThreadPoolExecutor(
                    max_workers=n, thread_name_prefix=f"actor-{name}")
            self._concurrency_groups = groups
            return {"ok": True}
        except Exception as e:
            return {"ok": False, "error": repr(e),
                    "error_blob": _error_blob(e, traceback.format_exc())}

    async def rpc_push_actor_task(self, conn, body):
        """Ordered actor-task execution (reference: ActorSchedulingQueue —
        per-caller sequence numbers ensure submission order)."""
        caller = body["caller_id"]
        seq = body["seq"]
        expected = self._caller_seq.get(caller, 0)
        if seq < expected:
            # Wire-level duplicate of a frame this stream already
            # consumed (dup'd frame, retransmit): NEVER re-execute.
            # Replays after an actor restart are not this case —
            # recovery re-mints fresh seqs for the unacked window, so
            # they arrive in-stream and run normally.  If the original
            # dispatch is still running we must ride its result: both
            # replies share the duplicated frame's msg_id, so a bare
            # ack could reach the caller FIRST and the real reply
            # (carrying the task's results) would then be dropped as a
            # stale msg_id — the results would be lost, not just the
            # frame deduped.  Once the original has completed, its
            # reply is already on the wire ahead of ours (same conn,
            # FIFO), so a generic ack is safe.
            w = self._dup_waiter(caller, seq)
            if w is not None:
                return await w
            return {"ok": True, "duplicate": True}
        if seq != expected:
            fut = self.loop.create_future()
            heapq.heappush(self._caller_buffer.setdefault(caller, []),
                           (seq, id(fut), fut, body))
            return await fut
        return await self._run_actor_task_in_order(caller, body)

    def _dup_waiter(self, caller, seq):
        """A future riding the still-running original dispatch of
        ``seq``, or None when that dispatch already completed (its
        reply is then already ahead of any ack on the wire)."""
        running = self._caller_running.get(caller)
        if not running or seq not in running:
            return None
        w = self.loop.create_future()
        self._dup_waiters.setdefault((caller, seq), []).append(w)
        return w

    def _finish_caller_task(self, caller, seq, result, exc):
        """Retire a tracked dispatch and resolve any duplicate-frame
        waiters with the same outcome.  The hot path (no duplicates
        anywhere) pays one set.discard and one empty-dict truth test."""
        running = self._caller_running.get(caller)
        if running is not None:
            running.discard(seq)
            if not running:
                self._caller_running.pop(caller, None)
        if self._dup_waiters:
            for w in self._dup_waiters.pop((caller, seq), ()):
                if w.cancelled():
                    continue
                if exc is not None:
                    w.set_exception(exc)
                else:
                    w.set_result(result)

    async def _run_tracked(self, caller, body):
        """_dispatch_actor_task plus duplicate-frame bookkeeping (the
        seq must already be in _caller_running)."""
        seq = body["seq"]
        try:
            result = await self._dispatch_actor_task(body)
        except BaseException as e:
            self._finish_caller_task(caller, seq, None, e)
            raise
        self._finish_caller_task(caller, seq, result, None)
        return result

    async def _run_actor_task_in_order(self, caller, body):
        seq = body["seq"]
        self._caller_seq[caller] = seq + 1
        self._caller_running.setdefault(caller, set()).add(seq)
        # Release any buffered next-in-line tasks.
        buf = self._caller_buffer.get(caller)
        if not buf:
            # Nothing buffered (the overwhelmingly common case): await
            # the dispatch directly — no Task allocation.  A successor
            # arriving mid-dispatch sees the advanced seq and dispatches
            # itself; only out-of-order arrivals need the buffer path.
            # (Tracking is inlined too: no wrapper coroutine here.)
            try:
                result = await self._dispatch_actor_task(body)
            except BaseException as e:
                self._finish_caller_task(caller, seq, None, e)
                raise
            self._finish_caller_task(caller, seq, result, None)
            return result
        task = self.loop.create_task(self._run_tracked(caller, body))
        # ONE release loop for both cases, because they interleave: a
        # buffered duplicate of a seq released *by this very loop*
        # surfaces at the heap front between releases, and two split
        # loops would neither ack it nor reach the entries behind it
        # (stranding the caller's whole stream).  Duplicates (< seq)
        # are never dispatched: they ride the original's still-running
        # result or get a generic ack; next-in-line entries dispatch
        # and advance the stream.
        while buf:
            expected = self._caller_seq[caller]
            if buf[0][0] < expected:
                _seq, _tie, fut, _dup = heapq.heappop(buf)
                if fut.cancelled():
                    continue
                w = self._dup_waiter(caller, _seq)
                if w is None:
                    fut.set_result({"ok": True, "duplicate": True})
                else:
                    def _ride(t, f=fut):
                        if f.cancelled():
                            return
                        if t.exception() is not None:
                            f.set_exception(t.exception())
                        else:
                            f.set_result(t.result())
                    w.add_done_callback(_ride)
                continue
            if buf[0][0] != expected:
                break
            _seq, _tie, fut, nxt = heapq.heappop(buf)
            self._caller_seq[caller] = nxt["seq"] + 1
            self._caller_running.setdefault(caller, set()).add(nxt["seq"])
            nxt_task = self.loop.create_task(self._run_tracked(caller, nxt))

            def _transfer(t, f=fut):
                if f.cancelled():
                    return
                if t.exception() is not None:
                    f.set_exception(t.exception())
                else:
                    f.set_result(t.result())
            nxt_task.add_done_callback(_transfer)
        return await task

    async def _dispatch_actor_task(self, body):
        method_name = body["method"]
        group = body.get("concurrency_group") or "_default"
        if self.actor_instance is None:
            return {"error": _error_blob(
                rexc.ActorDiedError(self.actor_id, "actor not initialized"))}
        method = getattr(self.actor_instance, method_name, None)
        if method is None:
            return {"error": _error_blob(AttributeError(
                f"actor has no method {method_name}"))}
        import inspect
        spec = {"task_id": body["task_id"], "num_returns": body["num_returns"],
                "return_ids": body["return_ids"]}
        if inspect.iscoroutinefunction(method):
            sem = self._actor_async_sems.get(group)
            if sem is None:
                n = (self._concurrency_groups.get(group)
                     if group != "_default" else None) or self._max_concurrency
                sem = self._actor_async_sems[group] = asyncio.Semaphore(n)
            async with sem:
                # Async actor methods adopt the caller's trace context
                # too (was: only the sync-pool paths recorded spans, so
                # every async actor call — serve replicas included —
                # was a tracing hole and broke trace continuity).
                t0 = time.time()
                # Default "task" cat: the submit-side flow_start used it,
                # and chrome matches flow pairs by (cat, name, id).
                span = self._enter_span(body.get("trace"))
                try:
                    args, kwargs = await self.loop.run_in_executor(
                        None, self._unpack_args, body["args"])
                    result = await method(*args, **kwargs)
                    return await self.loop.run_in_executor(
                        None, self._pack_results, result, spec)
                except Exception as e:
                    return {"error": _error_blob(e, traceback.format_exc())}
                finally:
                    self._record_profile_event(
                        "actor_task", body["method"], t0, trace=span)
        pool = self._actor_pools.get(group) or self._actor_pools["_default"]
        if pool._max_workers == 1:
            # The common sync-actor shape: drain-batched serial dispatch
            # (order-preserving; see _exec_on_serial_pool).
            return await self._exec_on_serial_pool(
                pool, self._execute_actor_method_sync, method, body, spec)
        return await self.loop.run_in_executor(
            pool, self._execute_actor_method_sync, method, body, spec)

    def _execute_actor_method_sync(self, method, body, spec):
        t0 = time.time()
        span = self._enter_span(body.get("trace"))
        try:
            args, kwargs = self._unpack_args(body["args"])
            result = method(*args, **kwargs)
            return self._pack_results(result, spec)
        except Exception as e:
            if isinstance(e, SystemExit) or isinstance(e, _ActorExit):
                raise
            return {"error": _error_blob(e, traceback.format_exc())}
        finally:
            self._record_profile_event("actor_task", body["method"], t0,
                                       trace=span)

    # --------------------------------------------------- actor-caller side
    def submit_actor_task(self, actor_id: ActorID, actor_addr, method: str,
                          args, kwargs, num_returns=1, opts=None):
        """Hot path: build the spec from a cached per-(actor, method)
        template — only task id / args / return ids / trace / seq vary
        per call — and hand it to the actor's send queue with ONE loop
        hop.  Sequencing, wire writes, and reply handling all live on
        the loop side (_actor_pump / _on_actor_reply)."""
        opts = opts or {}
        task_id = TaskID.for_submit()
        refs = []
        return_ids = []
        for i in range(num_returns):
            oid = ObjectID.for_task_return(task_id, i)
            entry = OwnedObject()
            entry.local_refs = 1
            self.owned[oid] = entry
            return_ids.append(oid)
            refs.append(ObjectRef(oid, owner_addr=self.addr, _track=True))
        args_blob = self._pack_args(args, kwargs)
        self._pin_args(task_id, args, kwargs)
        tkey = (actor_id, method, num_returns, opts.get("concurrency_group"))
        tmpl = self._actor_spec_templates.get(tkey)
        if tmpl is None:
            tmpl = self._actor_spec_templates[tkey] = ActorTaskSpec.new(
                task_id=None,
                method=method,
                args_blob=None,
                trace=None,
                num_returns=num_returns,
                return_ids=None,
                caller_id=self.worker_id.binary(),
                concurrency_group=opts.get("concurrency_group"),
                owner_addr=self.addr,
            )
        body = ActorTaskSpec(tmpl)
        body["task_id"] = task_id
        body["args"] = args_blob
        body["return_ids"] = return_ids
        body["trace"] = _trace_for_submit()
        if "flow" in body["trace"]:
            _tracing.flow_start(body["trace"]["flow"])
        entry = {"body": body, "retries": opts.get("max_task_retries", 0),
                 "attempts": 0, "fut": None, "seq": None, "conn": None,
                 "failed": None, "cancelled": False, "driver": False}
        self._post(self._actor_enqueue, actor_id, actor_addr, entry)
        return refs

    def _actor_enqueue(self, actor_id, actor_addr, entry):
        """Loop side of submit_actor_task: append to the actor's send
        queue (creating queue + pump on first use) and wake the pump."""
        q = self._actor_queues.get(actor_id)
        if q is None:
            q = self._actor_queues[actor_id] = _ActorSendQueue()
            q.pump = self.loop.create_task(self._actor_pump(actor_id, q))
            q.pump.add_done_callback(lambda t: t.cancelled() or t.exception())
        if actor_addr is not None and q.addr_hint is None:
            q.addr_hint = actor_addr
        q.pending.append(entry)
        for oid in entry["body"]["return_ids"]:
            self._actor_queued_refs[oid] = entry
        w = q.waiter
        if w is not None and not w.done():
            w.set_result(None)

    _ACTOR_SEND_BURST = 32

    async def _actor_pump(self, actor_id, q: _ActorSendQueue):
        """The one sender for this actor: drains the queue FIFO, assigns
        sequence numbers at dequeue, and writes bursts as one KIND_BATCH
        frame.  Between the seq assignment and the wire write nothing
        yields, so wire order always equals sequence order — the
        per-call lock of the old submitter is unnecessary here."""
        while not self._shutdown:
            if not q.pending:
                q.waiter = self.loop.create_future()
                try:
                    await q.waiter
                finally:
                    q.waiter = None
                continue
            # Never interleave fresh sends with an in-flight window
            # replay: replayed entries were submitted first and must
            # keep their place in the sequence stream.
            rec = self._actor_recovering.get(actor_id)
            if rec is not None:
                try:
                    await asyncio.shield(rec)
                except Exception:
                    pass
                continue
            conn = self._actor_conns.get(actor_id)
            if conn is None or conn.closed:
                # A (re)connect means a possibly new incarnation: replay
                # the unacked window FIRST so newer queued calls keep
                # their place behind it (submission order across
                # restart).  Entries stay IN the queue — and therefore
                # cancellable — until a live connection is in hand.
                if self._actor_unacked.get(actor_id):
                    try:
                        await self._actor_recover(actor_id, conn)
                    except Exception:
                        pass
                try:
                    conn = await self._actor_conn(actor_id, q.addr_hint)
                except Exception as e:
                    # No reachable incarnation: hand every queued entry
                    # to the retry/recovery slow path (each applies its
                    # own budget and terminal-death handling).
                    while q.pending:
                        entry = q.pending.popleft()
                        for oid in entry["body"]["return_ids"]:
                            self._actor_queued_refs.pop(oid, None)
                        if not entry["cancelled"]:
                            self._spawn_actor_entry_driver(actor_id,
                                                           entry, e)
                    continue
                continue  # re-check recovery state before sending
            batch = []
            while q.pending and len(batch) < self._ACTOR_SEND_BURST:
                entry = q.pending.popleft()
                for oid in entry["body"]["return_ids"]:
                    self._actor_queued_refs.pop(oid, None)
                if entry["cancelled"]:
                    continue  # returns already completed by cancel
                batch.append(entry)
            if not batch:
                continue
            try:
                una = self._actor_unacked.setdefault(actor_id, {})
                base = self._actor_seq.get(actor_id, 0)
                if len(batch) == 1:
                    batch[0]["body"]["seq"] = base
                    futs = [conn.request_send_nowait("push_actor_task",
                                                     batch[0]["body"])]
                else:
                    for i, entry in enumerate(batch):
                        entry["body"]["seq"] = base + i
                    futs = conn.request_send_many_nowait(
                        "push_actor_task", [e["body"] for e in batch])
                self._actor_seq[actor_id] = base + len(batch)
                for entry, fut in zip(batch, futs):
                    entry["seq"] = entry["body"]["seq"]
                    entry["conn"] = conn
                    entry["fut"] = fut
                    una[entry["seq"]] = entry
                    fut.add_done_callback(functools.partial(
                        self._on_actor_reply, actor_id, entry))
            except Exception as e:
                # The write never hit the wire (the nowait senders are
                # all-or-nothing) and the seq stream was not committed:
                # run each entry through the retry/recovery slow path.
                for entry in batch:
                    entry["fut"] = None
                    entry["conn"] = None
                    entry["seq"] = None
                    entry["body"].pop("seq", None)
                    self._spawn_actor_entry_driver(actor_id, entry, e)
                continue
            try:
                # Throttle at the transport's high-water mark: a stalled
                # actor must not let this queue buffer frames unbounded.
                # (The batch is already on the wire/window — a failure
                # here surfaces through the reply futures, not by
                # re-driving the entries.)
                await conn.backpressure()
            except Exception:
                pass

    def _maybe_evict_actor_queue(self, actor_id):
        """Drop the actor's send machinery (parked pump task + queue +
        spec templates) once nothing is queued or unacked — an
        actor-churn workload (launch/kill loops) must not park one task
        per dead actor forever.  Safe for live actors: the next call
        recreates the queue, and the seq stream / unacked window live in
        their own tables, which this does NOT touch."""
        if self._shutdown:
            return
        if self._actor_unacked.get(actor_id):
            return
        q = self._actor_queues.get(actor_id)
        if q is not None:
            if q.pending:
                return
            self._actor_queues.pop(actor_id, None)
            if q.pump is not None:
                q.pump.cancel()
        for key in [k for k in self._actor_spec_templates
                    if k[0] == actor_id]:
            self._actor_spec_templates.pop(key, None)

    def _on_actor_conn_close(self, actor_id, conn):
        self._maybe_evict_actor_queue(actor_id)

    def _on_actor_reply(self, actor_id, entry, fut):
        """Reply-future callback for queue-sent actor tasks (loop
        thread).  Success is recorded inline — no per-call task ever
        existed; any failure hands the entry to a driver task that owns
        the retry/recovery loop."""
        if entry["driver"] or entry["fut"] is not fut:
            return  # a driver task or a recovery resend owns this entry
        if not fut.cancelled() and fut.exception() is None:
            self._actor_unacked.get(actor_id, {}).pop(entry["seq"], None)
            body = entry["body"]
            self._record_results({"task_id": body["task_id"],
                                  "return_ids": body["return_ids"]},
                                 fut.result())
            return
        self._spawn_actor_entry_driver(actor_id, entry, None)

    def _spawn_actor_entry_driver(self, actor_id, entry, pre_error):
        entry["driver"] = True
        t = self.loop.create_task(
            self._drive_actor_entry(actor_id, entry, pre_error))
        # Failures surface through the return entries; retrieve any stray
        # exception so task GC doesn't log it.
        t.add_done_callback(lambda t: t.cancelled() or t.exception())

    async def _cancel_queued_actor(self, oid) -> bool:
        """Cancel an actor task still waiting in its send queue: the
        entry is marked (the pump skips it at dequeue) and its returns
        complete with TaskCancelledError immediately.  Returns False if
        the call already reached the wire."""
        entry = self._actor_queued_refs.get(oid)
        if entry is None:
            return False
        if entry["cancelled"]:
            return True
        entry["cancelled"] = True
        body = entry["body"]
        self._unpin_args(body["task_id"])
        blob = _error_blob(rexc.TaskCancelledError(
            f"actor task {body['task_id'].hex()[:8]} cancelled before "
            "it was sent"))
        for roid in body["return_ids"]:
            self._actor_queued_refs.pop(roid, None)
            oentry = self.owned.get(roid)
            if oentry is not None:
                oentry.blob = blob
                oentry.state = ERRORED  # last: lock-free readers
                oentry.set_ready()
        return True

    async def _actor_send(self, actor_id, actor_addr, entry):
        """Connect (or reuse), assign the next sequence number, put the
        request on the wire, and register the entry in the actor's unacked
        window — all under the per-actor lock so wire order always matches
        sequence order (reference: the direct actor submitter's send queue
        preserves submission order per caller)."""
        lock = self._actor_locks.get(actor_id)
        if lock is None:
            lock = self._actor_locks[actor_id] = asyncio.Lock()
        async with lock:
            conn = await self._actor_conn(actor_id, actor_addr)
            seq = self._actor_seq.get(actor_id, 0)
            self._actor_seq[actor_id] = seq + 1
            body = entry["body"]
            body["seq"] = seq
            entry["seq"] = seq
            entry["conn"] = conn
            try:
                entry["fut"] = await conn.request_send("push_actor_task",
                                                       body)
            except Exception:
                # The send never hit the wire: roll the sequence number
                # back (we still hold the lock, so nobody interleaved) —
                # a burned seq would wedge the actor's in-order queue.
                self._actor_seq[actor_id] = seq
                raise
            self._actor_unacked.setdefault(actor_id, {})[seq] = entry

    async def _drive_actor_entry(self, actor_id, entry, pre_error=None):
        """Slow-path driver for one entry after a failure: retry through
        the per-actor unacked window.  On a connection loss the whole
        window is held, the next incarnation is awaited (patiently — a
        restart under load may take minutes), and every entry with retry
        budget left is resent IN ORIGINAL ORDER by one shared recovery
        pass; entries out of budget fail with ActorDiedError.  -1
        retries = unbounded while the actor keeps restarting.
        Reference: direct_actor_task_submitter.h:67.

        Entered with entry["fut"] set to the failed reply future (a sent
        call whose connection died), or None (the pump could not reach
        the actor at all, `pre_error` carries why)."""
        body = entry["body"]
        retries = entry["retries"]
        first_error = pre_error
        addr = None
        while True:
            if entry["fut"] is None and entry["failed"] is None:
                # Not on a wire (pump send failed, or a resend is due):
                # send on the current incarnation.
                if retries != -1 and entry["attempts"] > max(retries, 0):
                    break
                try:
                    await self._actor_send(actor_id, addr, entry)
                except Exception as e:
                    if first_error is None:
                        first_error = e
                    entry["attempts"] += 1
                    if retries != -1 and entry["attempts"] > max(retries, 0):
                        break
                    try:
                        await self._actor_recover(actor_id, None)
                    except rexc.ActorDiedError as e2:
                        if first_error is None:
                            first_error = e2
                        break
                    except Exception:
                        pass  # transient; the budget check above bounds us
                    addr = None  # re-resolve from the GCS on the resend
                    continue
            if entry["failed"] is not None:
                break  # recovery exhausted this entry's retry budget
            fut = entry["fut"]
            try:
                reply = await fut
                self._actor_unacked.get(actor_id, {}).pop(entry["seq"], None)
                self._record_results({"task_id": body["task_id"],
                                      "return_ids": body["return_ids"]},
                                     reply)
                return
            except Exception as e:
                if first_error is None:
                    first_error = e
            if entry["fut"] is not fut or entry["failed"] is not None:
                # A concurrent recovery already resent (or failed) this
                # entry while we were waking up: act on its decision.
                continue
            if retries != -1 and entry["attempts"] >= max(retries, 0):
                break
            try:
                await self._actor_recover(actor_id, entry.get("conn"))
            except rexc.ActorDiedError as e:
                # Terminal: the GCS reported DEAD (or gave up entirely).
                if first_error is None:
                    first_error = e
                break
            except Exception as e:
                # Transient: the next incarnation crashed between the GCS
                # reporting ALIVE and our reconnect.  Consume a retry and
                # go around (the wait inside recovery throttles the loop).
                if first_error is None:
                    first_error = e
                entry["attempts"] += 1
                continue
            if entry["fut"] is fut and entry["failed"] is None:
                # Recovery declined (live connection already in place —
                # e.g. an earlier recovery crashed mid-window and lost this
                # entry): resend it ourselves on the live connection.
                self._actor_unacked.get(actor_id, {}).pop(entry["seq"], None)
                entry["fut"] = None
                entry["attempts"] += 1
        await self._finalize_actor_entry(actor_id, entry, first_error)

    async def _finalize_actor_entry(self, actor_id, entry, first_error):
        """Terminal failure: complete the entry's returns with
        ActorDiedError carrying the best-known cause."""
        body = entry["body"]
        self._actor_unacked.get(actor_id, {}).pop(entry.get("seq"), None)
        view = await self._wait_actor_alive(actor_id, overall_timeout=1.0)
        cause = (entry["failed"]
                 or (_death_cause_from_view(view)
                     if isinstance(first_error, protocol.ConnectionLost)
                     else None)
                 or str(first_error))
        err = rexc.ActorDiedError(actor_id, cause)
        blob = _error_blob(err)
        self._unpin_args(body["task_id"])
        for oid in body["return_ids"]:
            oentry = self.owned.get(oid)
            if oentry is not None:
                oentry.blob = blob
                oentry.state = ERRORED  # last: lock-free readers
                oentry.set_ready()
        # Terminal failures usually mean a dead actor: reap its parked
        # send machinery once the last entry settles.
        self._maybe_evict_actor_queue(actor_id)

    async def _actor_recover(self, actor_id, failed_conn):
        """Single-flight per actor: wait for the next ALIVE incarnation,
        reconnect, and resend the entire unacked window in original-seq
        order.  Entries whose retry budget is exhausted are marked failed
        instead of resent.  Raises if the actor is terminally DEAD.

        `failed_conn` is the connection the caller observed failing; if
        the current connection is already a LIVE different one, another
        recovery has run and this call is a no-op (resending the window
        over a live connection would double-execute tasks)."""
        rec = self._actor_recovering.get(actor_id)
        if rec is not None:
            await asyncio.shield(rec)
            return
        cur = self._actor_conns.get(actor_id)
        if (cur is not None and not cur.closed
                and (failed_conn is None or cur is not failed_conn)):
            return
        rec = self.loop.create_future()
        self._actor_recovering[actor_id] = rec
        try:
            stale = self._actor_conns.get(actor_id)
            view = await self._wait_actor_alive(actor_id)
            if (view is None or view.get("state") != "ALIVE"
                    or view.get("addr") is None):
                raise rexc.ActorDiedError(
                    actor_id, _death_cause_from_view(view) or "not found")
            lock = self._actor_locks.get(actor_id)
            if lock is None:
                lock = self._actor_locks[actor_id] = asyncio.Lock()
            async with lock:
                conn = self._actor_conns.get(actor_id)
                if conn is stale or (conn is not None and conn.closed):
                    self._actor_conns.pop(actor_id, None)
                # _actor_conn resets the seq stream on address change.
                conn = await self._actor_conn(actor_id, tuple(view["addr"]))
                unacked = self._actor_unacked.get(actor_id) or {}
                entries = [unacked[s] for s in sorted(unacked)]
                unacked.clear()
                for ent in entries:
                    ent["attempts"] += 1
                    r = ent["retries"]
                    if r != -1 and ent["attempts"] > max(r, 0):
                        ent["failed"] = ("task was submitted to a previous "
                                         "incarnation and is out of retries")
                        ent["fut"] = None
                        if not ent.get("driver"):
                            # No driver task is watching this entry (it
                            # was queue-sent and its reply callback
                            # already fired): complete its returns here.
                            t = self.loop.create_task(
                                self._finalize_actor_entry(
                                    actor_id, ent, None))
                            t.add_done_callback(
                                lambda t: t.cancelled() or t.exception())
                        continue
                    seq = self._actor_seq.get(actor_id, 0)
                    self._actor_seq[actor_id] = seq + 1
                    ent["body"]["seq"] = seq
                    ent["seq"] = seq
                    ent["fut"] = await conn.request_send("push_actor_task",
                                                         ent["body"])
                    unacked[seq] = ent
                    if not ent.get("driver"):
                        ent["fut"].add_done_callback(functools.partial(
                            self._on_actor_reply, actor_id, ent))
            rec.set_result(None)
        except Exception as e:
            rec.set_exception(e)
            raise
        finally:
            self._actor_recovering.pop(actor_id, None)
            if not rec.done():
                rec.set_result(None)

    async def _wait_actor_alive(self, actor_id, overall_timeout=None):
        """Wait until the actor is in a TERMINAL-for-us state: ALIVE or
        DEAD.  A restart in progress (RESTARTING/PENDING) keeps waiting up
        to `overall_timeout` (default cfg.actor_restart_wait_s) instead of
        being misread as death — a restart on a loaded host can take far
        longer than one RPC's patience."""
        overall = (overall_timeout if overall_timeout is not None
                   else cfg.actor_restart_wait_s)
        deadline = time.monotonic() + overall
        view = None
        while True:
            remain = deadline - time.monotonic()
            if remain <= 0:
                return view
            try:
                view = await self._gcs_request(
                    "wait_actor_alive",
                    {"actor_id": actor_id, "timeout": min(30.0, remain)})
            except Exception:
                return view
            if view is None or view.get("state") in ("ALIVE", "DEAD"):
                return view

    async def _actor_conn(self, actor_id, actor_addr):
        """Resolve a live connection to the actor.  Only call while holding
        the per-actor lock.  A reconnect to a *different* address means a new
        actor incarnation: the sequence stream restarts at 0."""
        conn = self._actor_conns.get(actor_id)
        if conn is not None and not conn.closed:
            return conn
        if actor_addr is None or (conn is not None and conn.closed):
            view = await self._wait_actor_alive(actor_id)
            if view is None or view.get("addr") is None or \
                    view.get("state") != "ALIVE":
                raise rexc.ActorDiedError(
                    actor_id, _death_cause_from_view(view) or "not found")
            actor_addr = tuple(view["addr"])
        if self._actor_addr_cache.get(actor_id) not in (None, tuple(actor_addr)):
            self._actor_seq[actor_id] = 0  # new incarnation, new stream
        conn = await protocol.Connection.connect(
            actor_addr[0], actor_addr[1], handler=self._handle,
            name="cw->actor", timeout=cfg.connect_timeout_s,
            on_close=functools.partial(self._on_actor_conn_close,
                                       actor_id))
        self._actor_conns[actor_id] = conn
        self._actor_addr_cache[actor_id] = tuple(actor_addr)
        return conn

    def create_actor(self, class_id: bytes, init_args, init_kwargs,
                     opts: dict) -> ActorID:
        actor_id = ActorID.from_random()
        init_blob = self._pack_args(init_args, init_kwargs)
        pg = opts.get("placement_group")
        spec = ActorCreationSpec.new(
            class_id=class_id,
            class_name=opts.get("class_name", ""),
            init_blob=init_blob,
            resources=_normalize_resources(opts, actor=True),
            max_restarts=opts.get("max_restarts",
                                  cfg.actor_max_restarts_default),
            max_concurrency=opts.get("max_concurrency"),
            concurrency_groups=opts.get("concurrency_groups"),
            name=opts.get("name"),
            namespace=opts.get("namespace", "default"),
            detached=opts.get("lifetime") == "detached",
            scheduling_strategy=_strategy_dict(
                opts.get("scheduling_strategy")),
            runtime_env=(self._pack_runtime_env(opts["runtime_env"])
                         if opts.get("runtime_env") else None),
            placement_group_id=pg.id if pg is not None else None,
            bundle_index=(opts.get("placement_group_bundle_index")
                          if pg is not None else None),
        )
        # The creation task carries its submitter's trace as any task
        # does: the raylet that waits for a worker and the worker that
        # runs the constructor record under it.
        spec["trace"] = _trace_for_submit()
        if "flow" in spec["trace"]:
            _tracing.flow_start(spec["trace"]["flow"])
        reply = self._run(self._gcs_request("create_actor", {
            "actor_id": actor_id, "spec": spec, "job_id": self.job_id}))
        if not reply.get("ok"):
            raise ValueError(reply.get("reason", "actor creation failed"))
        return actor_id

    # ------------------------------------------------------------ misc rpc
    async def rpc_ping(self, conn, body):
        return {"ok": True, "mode": self.mode}

    async def rpc_set_failpoints(self, conn, body):
        """Runtime fault-plane toggle: tests flip failpoints / partition
        rules on a live worker mid-run (see failpoints.apply_rpc)."""
        return failpoints.apply_rpc(body)

    async def rpc_exit(self, conn, body):
        asyncio.get_running_loop().call_later(0.05, os._exit, 0)
        return {"ok": True}


class _ActorExit(SystemExit):
    pass


class _SerializedError:
    """Wrapper stored as the value of errored objects; raising happens at
    get() (reference: RayTaskError stored as the object value)."""

    def __init__(self, exc: Exception | None, repr_str: str, tb: str):
        self.exc = exc
        self.repr_str = repr_str
        self.tb = tb

    def to_exception(self) -> Exception:
        if isinstance(self.exc, (rexc.ActorError, rexc.ObjectLostError,
                                 rexc.RayTpuError)):
            return self.exc
        if isinstance(self.exc, Exception):
            return rexc._wrap_cause(self.exc, self.tb)
        return rexc.TaskError(self.repr_str, self.tb)


def _error_blob(exc: Exception, tb: str = "") -> bytes:
    try:
        blob, _ = serialization.serialize(_SerializedError(exc, repr(exc), tb))
    except Exception:
        blob, _ = serialization.serialize(
            _SerializedError(None, repr(exc), tb))
    return blob.to_bytes()


def _death_cause_from_view(view) -> str | None:
    """Human-readable death cause; appends the actor-init traceback shipped
    by the executing worker (gcs ActorInfo.init_error_blob) when present."""
    if not view:
        return None
    cause = view.get("death_cause")
    blob = view.get("init_error")
    if blob:
        try:
            se = serialization.deserialize(blob)
            tb = getattr(se, "tb", "")
            if tb:
                cause = f"{cause or 'actor init failed'}\n{tb}"
        except Exception:
            pass
    return cause


def _is_system_error(e: Exception) -> bool:
    return isinstance(e, (protocol.ConnectionLost, ConnectionError, OSError,
                          asyncio.TimeoutError))


def _normalize_resources(opts: dict, actor=False) -> dict:
    res = dict(opts.get("resources") or {})
    num_cpus = opts.get("num_cpus")
    if num_cpus is None:
        num_cpus = 0 if actor else 1
    if num_cpus:
        res["CPU"] = float(num_cpus)
    num_tpus = opts.get("num_tpus", opts.get("num_gpus"))
    if num_tpus:
        res["TPU"] = float(num_tpus)
    if opts.get("memory"):
        res["memory"] = float(opts["memory"])
    return res


def _strategy_dict(strategy):
    if strategy is None:
        return None
    if isinstance(strategy, str):
        if strategy == "SPREAD":
            return {"type": "spread"}
        if strategy == "DEFAULT":
            return None
        return None
    # NodeAffinitySchedulingStrategy / PlacementGroupSchedulingStrategy
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)
    if isinstance(strategy, NodeAffinitySchedulingStrategy):
        return {"type": "node_affinity", "node_id": strategy.node_id,
                "soft": strategy.soft}
    return None
