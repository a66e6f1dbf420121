"""Raylet: the per-node daemon — scheduler, worker pool, object-store authority.

TPU-native re-design of the reference raylet (reference:
src/ray/raylet/node_manager.h:143 — HandleRequestWorkerLease
node_manager.cc:1822, HandleReturnWorker :1965; WorkerPool worker_pool.h:153
PopWorker :337; LocalTaskManager local_task_manager.h:58;
PlacementGroupResourceManager placement_group_resource_manager.h; the plasma
store runs in-process, object_manager/plasma/store_runner.cc).

Responsibilities:
  * grants worker *leases* to core workers (lease = a worker process +
    reserved resources; the submitter then pushes tasks directly to the
    worker, amortizing scheduling — same protocol shape as the reference)
  * worker pool: spawn/reuse/kill python worker processes
  * local resource accounting incl. placement-group bundle accounts with
    2-phase prepare/commit (reference: node_manager.proto:365-372)
  * shared-memory object store authority (metadata RPC; data plane is the
    clients' own mmap — see shm_store.py) + inter-node object pulls
    (reference: object_manager/pull_manager.h:47 chunked pulls)
  * blocked-worker CPU release so nested ray.get can't deadlock the pool
    (reference: worker blocked/unblocked resource release in node_manager)
"""

from __future__ import annotations

import asyncio
import logging
import os
import subprocess
import sys
import time

from ray_tpu._private import failpoints, protocol, retry
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as cfg
from ray_tpu._private.ids import NodeID, WorkerID
from ray_tpu._private.shm_store import StoreServer, StoreMapping, default_store_path
from ray_tpu._private.transfer import TransferManager, _remain

logger = logging.getLogger(__name__)


class WorkerHandle:
    def __init__(self, worker_id, proc, conn=None, kind="cpu",
                 env_key: str = ""):
        self.kind = kind
        self.env_key = env_key  # content address of the pip venv ("" = base)
        self.worker_id: WorkerID = worker_id
        self.proc: subprocess.Popen | None = proc
        self.conn: protocol.Connection | None = conn
        self.addr: tuple[str, int] | None = None
        self.pid: int | None = proc.pid if proc else None
        self.lease_id = None
        self.actor_id = None
        self.registered = asyncio.Event()
        self.last_idle = time.monotonic()
        # How the process came to be ("zygote" fork / "cold" start);
        # a lease that finds the worker in the pool calls it "reused".
        self.how = "cold"


class _ContainerProcHandle:
    """Popen facade for a container worker.  Signals must reach the
    CONTAINER (`runtime rm -f <name>`), not just the podman/docker
    client process — SIGKILLing the client detaches the engine-managed
    container, which keeps running (and `--rm` never fires), leaking
    the worker and its lease."""

    # Every in-flight remove-then-kill thread, including those whose
    # worker was already popped from the raylet's table — shutdown must
    # join ALL of them or the engine-managed containers leak.
    _live_kill_threads: "set" = set()

    def __init__(self, proc: subprocess.Popen, runtime: str, name: str):
        self._proc = proc
        self._runtime = runtime
        self._name = name
        self.pid = proc.pid
        self._kill_thread = None

    def poll(self):
        return self._proc.poll()

    def wait(self, timeout=None):
        return self._proc.wait(timeout)

    def kill(self):
        # kill() is invoked from async raylet paths (worker reaping,
        # shutdown); a blocking `rm -f` with a 10s timeout would stall
        # lease scheduling and GCS heartbeats, and several serial kills
        # during drain could exceed the heartbeat timeout and turn an
        # orderly drain into a NODE_DEAD.  But the ORDER still matters:
        # the container must be removed before the client is SIGKILLed
        # (killing the client first detaches the engine-managed
        # container — see class docstring).  So the wait/retry/kill
        # sequence runs on a short-lived daemon thread.  Idempotent:
        # kill() is reached twice on a deliberate kill (rpc_kill_worker
        # then _on_worker_dead's poll()-is-alive check) — a second
        # thread would just race the first's `rm -f` and log spurious
        # failures.
        import threading
        if self._kill_thread is not None:
            return

        def _remove_then_kill():
            try:
                for attempt in (1, 2):
                    try:
                        rc = subprocess.run(
                            [self._runtime, "rm", "-f", self._name],
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL,
                            timeout=10).returncode
                    except Exception:
                        rc = -1
                    if rc == 0:
                        break
                    logger.warning(
                        "container rm -f %s failed (rc=%s, attempt %d)",
                        self._name, rc, attempt)
                try:
                    self._proc.kill()
                except Exception:
                    pass
            finally:
                type(self)._live_kill_threads.discard(
                    threading.current_thread())

        self._kill_thread = threading.Thread(
            target=_remove_then_kill, daemon=True,
            name=f"container-kill-{self._name}")
        type(self)._live_kill_threads.add(self._kill_thread)
        self._kill_thread.start()

    def join_kill(self, timeout: float):
        """Block until the remove-then-kill sequence finishes (raylet
        shutdown must not exit before `rm -f` runs — daemon threads die
        with the interpreter and the containers would leak)."""
        if self._kill_thread is not None:
            self._kill_thread.join(timeout)

    terminate = kill


_PENDING_GAUGE = None


def _pending_leases_gauge():
    global _PENDING_GAUGE
    if _PENDING_GAUGE is None:
        from ray_tpu.util.metrics import Gauge
        _PENDING_GAUGE = Gauge(
            "raylet_pending_leases",
            "queued (ungranted) worker-lease requests",
            tag_keys=("node",))
    return _PENDING_GAUGE


class Lease:
    def __init__(self, lease_id, worker, resources, pg_key):
        self.lease_id = lease_id
        self.worker: WorkerHandle = worker
        self.resources: dict = resources
        self.pg_key = pg_key  # (pg_id, bundle_index) or None
        self.blocked = False
        self.tpu_ids: list = []  # device indices granted to this lease


class Raylet:
    def __init__(self, gcs_addr, resources, labels=None, host="127.0.0.1",
                 session_dir="/tmp/ray_tpu", store_capacity=None,
                 node_name=None):
        self.node_id = NodeID.from_random()
        self.gcs_addr = gcs_addr
        self.host = host
        self.session_dir = session_dir
        self.node_name = node_name
        self.total_resources = dict(resources)
        self.available = dict(resources)
        # Per-device TPU accounting: chip index -> fraction in use
        # (reference: the raylet's GPU-id resource instances backing
        # ray.get_gpu_ids; fractional leases share one chip).
        self._tpu_slots: dict[int, float] = {
            i: 0.0 for i in range(int(resources.get("TPU", 0)))}
        self.labels = labels or {}
        self.server = protocol.RpcServer(self._handle, host=host, name="raylet",
                                         on_disconnect=self._on_conn_lost,
                                         blob_provider=self._blob_sink)
        self.gcs: protocol.Connection | None = None
        self.port = None
        store_capacity = store_capacity or cfg.object_store_memory_bytes
        self.store_path = default_store_path(session_dir, self.node_id.hex())
        self.store = StoreServer(self.store_path, store_capacity)
        self.store_capacity = store_capacity
        self.mapping = StoreMapping(self.store_path, store_capacity)
        # workers, pooled by (kind, env_key): a pip-venv task only ever
        # reuses a worker whose venv matches (reference: worker_pool.h
        # matching runtime_env hashes on PopWorker)
        self.workers: dict[WorkerID, WorkerHandle] = {}
        self.idle_workers: dict[tuple, list[WorkerHandle]] = {}
        self._spawn_sem = None  # created lazily on the loop
        self.leases: dict[bytes, Lease] = {}
        self.pending_leases: list[dict] = []  # queued lease requests
        self._lease_waiters: list = []
        # placement group bundle accounts: (pg_id, idx) -> {"reserved", "avail"}
        self.bundles: dict[tuple, dict] = {}
        # object store waiters: oid -> [futures] waiting for seal
        self.seal_waiters: dict[bytes, list[asyncio.Future]] = {}
        # Spilling (reference: raylet LocalObjectManager::SpillObjects
        # local_object_manager.h:99 + external_storage.py): primary copies
        # move to disk under memory pressure and restore on access.
        self.spill_dir = os.path.join(session_dir, "spill",
                                      self.node_id.hex()[:8])
        self._created_sizes: dict[bytes, int] = {}
        self.primary_objects: dict[bytes, int] = {}  # sealed, creator-pinned
        self.spilled: dict[bytes, tuple[str, int]] = {}  # oid -> (path, size)
        self._spilling: set[bytes] = set()
        self._restores_inflight: dict[bytes, asyncio.Future] = {}
        # cached cluster node table (from GCS pubsub), plus the indexed
        # scheduling view: per-shape candidate sets / score heaps
        # updated incrementally from "nodes" added/removed/updated
        # events, so spillback/spread/hybrid picks don't rescan every
        # node view per lease decision (see sched_policy.ClusterIndex).
        self.cluster_nodes: dict[NodeID, dict] = {}
        from ray_tpu._private.sched_policy import SchedulingPolicies
        self.sched = SchedulingPolicies()
        # Monotonic counter of applied "nodes" pubsub events + the
        # counter value at which each node was last touched by one:
        # _sync_node_views must not let a STALE snapshot override
        # events applied inline while the snapshot was in flight.
        self._node_event_seq = 0
        self._node_touched: dict = {}
        self.peer_conns: dict[NodeID, protocol.Connection] = {}
        self._next_lease = 0
        self._shutdown = False
        self._subproc_env = None
        self._zygote = None  # ZygoteClient once warm (fast fork spawn)
        self._spawn_sem_cap = None
        # per-instance pull dedup (a class attribute would be shared across
        # the in-process multi-raylet test Cluster)
        self._pulls_inflight: dict = {}
        # In-flight push receives: oid -> {"off": arena offset, "size",
        # "sender": id(sender conn), "gen": transfer generation,
        # "last": last-chunk ts, "received": bytes}
        self._push_recv: dict = {}
        self._push_gen = 0  # generation minted per os_push_begin
        # Windowed pull/push engine (admission, striping, retries).
        self.transfers = TransferManager(self)
        # Spill-file read fds kept open across a transfer's chunks:
        # oid -> [fd, last_used, inflight_reads, eof_seen]
        self._spill_read_fds: dict[bytes, list] = {}
        # Oids this node has reported to the GCS object directory, so
        # removal reports fire only for entries that actually exist
        # there (sub-stripe objects are never reported at all).
        self._reported_locs: set[bytes] = set()
        # pins held on behalf of each client conn: id(conn) -> {oid: count}
        self._client_pins: dict[int, dict[bytes, int]] = {}
        # unsealed creates per client conn (freed if the client dies
        # before sealing): id(conn) -> {oid}
        self._creating: dict[int, set[bytes]] = {}
        # resource shapes already warned about as infeasible (event dedup)
        self._infeasible_warned: set[tuple] = set()
        # Pending-lease queue depth gauge (updated from the heartbeat
        # loop; one process-wide metric, one series per node so the
        # in-process multi-raylet cluster doesn't shadow itself).
        try:
            self._pending_gauge = _pending_leases_gauge().series(
                {"node": self.node_id.hex()[:8]})
        except Exception:
            self._pending_gauge = None

    # -------------------------------------------------------------- startup
    async def start(self, port=0):
        self.port = await self.server.start(port)
        # The node tag in the connection name is what the fault plane's
        # partition/slow-link rules match on (test_utils.partition).
        self.gcs = await protocol.Connection.connect(
            self.gcs_addr[0], self.gcs_addr[1], handler=self._handle_gcs_push,
            name=f"raylet:{self.node_id.hex()[:8]}->gcs",
            timeout=cfg.connect_timeout_s)
        reply = await self.gcs.request("register_node", {
            "node_id": self.node_id,
            "addr": (self.host, self.port),
            "resources": self.total_resources,
            "labels": self.labels,
        })
        for view in reply.get("cluster_nodes", []):
            self._observe_node_view(view)
        await self.gcs.request("subscribe", {"channels": ["nodes"]})
        loop = asyncio.get_running_loop()
        loop.create_task(self._heartbeat_loop())
        loop.create_task(self._reap_loop())
        if cfg.worker_zygote_enabled:
            loop.create_task(self._start_zygote())
        if cfg.log_to_driver:
            from ray_tpu._private.log_monitor import LogMonitor

            async def _pub(channel, message):
                await self.gcs.request("publish", {"channel": channel,
                                                   "message": message})

            # Per-raylet log subdir: in the in-process multi-raylet test
            # Cluster all nodes share one session dir, and each monitor
            # must tail only its own workers.
            self._log_monitor = LogMonitor(
                os.path.join(self.session_dir, "logs",
                             self.node_id.hex()[:8]), _pub,
                self.node_id.hex())
            loop.create_task(self._log_monitor.run())
        logger.info("raylet %s on %s:%s resources=%s", self.node_id.hex()[:8],
                    self.host, self.port, self.total_resources)
        return self.port

    def _worker_env(self):
        if self._subproc_env is None:
            env = dict(os.environ)
            env.update(cfg.to_env())
            env.update({
                "RT_RAYLET_HOST": self.host,
                "RT_RAYLET_PORT": str(self.port),
                "RT_GCS_HOST": self.gcs_addr[0],
                "RT_GCS_PORT": str(self.gcs_addr[1]),
                "RT_NODE_ID": self.node_id.hex(),
                "RT_STORE_PATH": self.store_path,
                "RT_STORE_CAP": str(self.store_capacity),
                "RT_SESSION_DIR": self.session_dir,
                # Whatever the node's own environment says, a worker
                # stays off the chips: only a worker leased TPU
                # resources may open them (_worker_env_for).
                "JAX_PLATFORMS": "cpu",
            })
            # The spawned `python -m ray_tpu...` must find the package even
            # when this process imported it via a sys.path entry (script dir,
            # editable layout) that subprocesses don't inherit.
            import ray_tpu
            pkg_root = os.path.dirname(os.path.dirname(
                os.path.abspath(ray_tpu.__file__)))
            parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
            if pkg_root not in parts:
                env["PYTHONPATH"] = os.pathsep.join([pkg_root] + parts)
            self._subproc_env = env
        return self._subproc_env

    # ------------------------------------------------------------ rpc entry
    async def _handle(self, conn, method, body):
        fn = getattr(self, "rpc_" + method, None)
        if fn is None:
            raise protocol.RpcError(f"raylet: no method {method}")
        return await fn(conn, body)

    async def _handle_gcs_push(self, conn, method, body):
        """The GCS talks back over the raylet's own registration connection
        (duplex): pubsub pushes AND control RPCs (actor leases, bundle
        prepare/commit) arrive here."""
        if method == "pubsub":
            if body["channel"] == "nodes":
                await self._on_node_event(body["message"])
            return None
        if method == "pubsub_batch":
            # Coalesced broadcast: one frame carrying a same-channel
            # run of messages, delivered in publish order.
            if body["channel"] == "nodes":
                for msg in protocol.pubsub_batch_messages(body):
                    await self._on_node_event(msg)
            return None
        if method == "pubsub_gap":
            # The GCS shed events we never saw (slow-subscriber
            # bound): the node view may now have silent holes — heal
            # by re-seeding authoritatively instead of waiting for a
            # reconnect that may never come.
            if "nodes" in body.get("channels", ()):
                asyncio.get_running_loop().create_task(
                    self._reseed_node_views())
            return None
        return await self._handle(conn, method, body)

    async def _sync_node_views(self, views, hard_prune: bool,
                               cutoff: int):
        """Resync cluster_nodes + the scheduling index against an
        authoritative view list.  ``hard_prune`` additionally tears
        down data-plane state (peer conns, transfers) for absent nodes
        — only safe when the list is known COMPLETE (get_nodes over
        the full table).  A register reply after a non-persistent GCS
        restart is NOT complete (it holds only nodes re-registered so
        far), so that path soft-prunes: absent nodes stop being
        scheduling targets, but live peer connections and in-flight
        transfers — which don't depend on the GCS — survive until the
        peers re-register and their views return.

        ``cutoff`` is the local node-event counter captured BEFORE the
        snapshot was requested: any node touched by a pubsub event
        applied after that point has NEWER state than the snapshot
        (e.g. an 'added' dispatched inline while the reply was in
        flight) and is left alone entirely — the snapshot must never
        prune or overwrite it."""
        fresh = {v["node_id"] for v in views if v.get("alive", True)}
        for nid in [n for n in self.cluster_nodes
                    if n not in fresh and n != self.node_id
                    and self._node_touched.get(n, 0) <= cutoff]:
            if hard_prune:
                await self._on_node_event({"event": "removed",
                                           "node_id": nid})
            else:
                self.cluster_nodes.pop(nid, None)
                self.sched.index.remove(nid)
        for v in views:
            if self._node_touched.get(v["node_id"], 0) <= cutoff:
                self._observe_node_view(v)
        # Entries at/below the cutoff have served their purpose.
        self._node_touched = {k: s for k, s in self._node_touched.items()
                              if s > cutoff}

    async def _reseed_node_views(self):
        """Authoritative node-view refresh (gap heal / post-shed):
        fetch the FULL table, prune cached nodes no longer alive in
        it, re-observe the rest."""
        if self.gcs is None or self.gcs.closed:
            return
        cutoff = self._node_event_seq
        try:
            views = await self.gcs.request("get_nodes", {}, timeout=30.0)
        except Exception:
            return
        await self._sync_node_views(views, hard_prune=True,
                                    cutoff=cutoff)

    def _observe_node_view(self, view: dict):
        """Seed/replace one full node view (registration reply, added
        event, post-reconnect re-seed) in both the legacy dict and the
        indexed scheduling view (which never tracks this node itself).
        Non-alive views are rejected outright: a dead node never emits
        the "removed" event that would prune it later, so admitting it
        would make it a permanent phantom scheduling target."""
        if not view.get("alive", True):
            self.cluster_nodes.pop(view["node_id"], None)
            self.sched.index.remove(view["node_id"])
            return
        self.cluster_nodes[view["node_id"]] = view
        if view["node_id"] != self.node_id:
            self.sched.index.upsert(view)

    async def _on_node_event(self, msg: dict):
        event = msg["event"]
        nid = msg["node"]["node_id"] if event == "added" \
            else msg["node_id"]
        self._node_event_seq += 1
        self._node_touched[nid] = self._node_event_seq
        if event == "added":
            view = msg["node"]
            self._observe_node_view(view)
            self._respill_pending(view)
        elif event == "removed":
            self.cluster_nodes.pop(msg["node_id"], None)
            self.sched.index.remove(msg["node_id"])
            self.transfers.drop_peer(msg["node_id"])
            conn2 = self.peer_conns.pop(msg["node_id"], None)
            if conn2 is not None:
                await conn2.close()
        elif event == "updated":
            # Heartbeat-delta broadcast: refresh availability/load (and
            # the draining flag) incrementally — this is what keeps
            # spillback/spread/hybrid decisions off stale registration
            # snapshots without any rescan.
            nid = msg["node_id"]
            view = self.cluster_nodes.get(nid)
            if view is not None:
                if "available" in msg:
                    view["available"] = msg["available"]
                if "load" in msg:
                    view["load"] = msg["load"]
                if "draining" in msg:
                    view["draining"] = msg["draining"]
                if "reserved" in msg:
                    view["reserved"] = msg["reserved"]
            if nid != self.node_id:
                from ray_tpu._private import sched_policy
                self.sched.index.update(
                    nid, available=msg.get("available"),
                    load=msg.get("load"),
                    draining=msg.get("draining"),
                    # None clears a reservation, so absent-vs-None must
                    # survive the hop: forward the sentinel when the
                    # delta didn't carry the field.
                    reserved=msg.get("reserved", sched_policy._UNSET))

    def _respill_pending(self, new_node_view):
        """A node joined: queued requests this node can NEVER satisfy but
        the new node can are answered with a spillback to it (the path
        that un-wedges infeasible-queued demand after a scale-up)."""
        total = new_node_view.get("resources", {})
        addr = tuple(new_node_view["addr"])
        # Shapes the new node satisfies are feasible again: forget the
        # warn-dedup so a LATER scale-down + new infeasible demand of the
        # same shape warns operators again.
        for shape in list(self._infeasible_warned):
            if all(total.get(k, 0) >= v for k, v in shape):
                self._infeasible_warned.discard(shape)
        for req in list(self.pending_leases):
            if req["future"].done():
                continue
            res = req["resources"]
            if self._fits_total(res):
                continue  # locally feasible: the scheduler will grant it
            if all(total.get(k, 0) >= v for k, v in res.items()):
                req["future"].set_result({"spillback": addr})
                self.pending_leases.remove(req)

    async def _on_conn_lost(self, conn):
        self._release_client_pins(conn)
        self._abort_pushes_from(conn)
        for oid in self._creating.pop(id(conn), ()):
            got = self.store.get(oid)
            if got is not None and not got[2]:
                # Client died mid-create: free the unsealed allocation.
                self._created_sizes.pop(oid, None)
                self._discard_unsealed(oid)
            elif got is not None and got[2]:
                self.store.release(oid)  # drop the probe pin
        for w in list(self.workers.values()):
            if w.conn is conn:
                await self._on_worker_dead(w, "worker connection lost")

    def _discard_unsealed(self, oid: bytes):
        """Free an unsealed allocation made by a transfer that died —
        abort() drops the alloc-time creator pin (shm_store.cc Alloc:
        refcount=1) and frees the extent atomically; release() refuses
        unsealed entries so a stray release can't free memory under a
        still-writing creator."""
        self.store.abort(oid)

    def _abort_pushes_from(self, conn):
        """Sender connection died: drop its in-flight push transfers so the
        unsealed allocations don't sit in the arena until the stale sweep,
        and so an immediate re-push (new connection) isn't answered {skip}.
        Waiters are woken to re-check the store / fall back to a pull."""
        sender = id(conn)
        for oid, ent in list(self._push_recv.items()):
            if ent["sender"] == sender:
                self._push_recv.pop(oid, None)
                self._discard_unsealed(oid)
                for fut in self.seal_waiters.pop(oid, []):
                    if not fut.done():
                        fut.set_result(None)

    # ------------------------------------------------------- worker lifecycle
    def _idle(self, kind: str, env_key: str = "") -> list:
        return self.idle_workers.setdefault((kind, env_key), [])

    def _ensure_venv(self, env_key: str, pip_specs: list) -> str:
        """Create (once) the content-addressed virtualenv for a pip
        runtime env and return its interpreter path (reference:
        _private/runtime_env/pip.py — spec-hash-keyed cached envs).
        Blocking; call from an executor thread."""
        import subprocess as sp
        root = os.path.join(self.session_dir, "venvs", env_key)
        py = os.path.join(root, "bin", "python")
        done_marker = os.path.join(root, ".ready")
        if os.path.exists(done_marker):
            return py
        lock = root + ".lock"
        os.makedirs(os.path.dirname(root), exist_ok=True)
        import time as _time

        def _lock_stale() -> bool:
            # The builder writes its pid into the lock; a SIGKILLed builder
            # (the chaos-test fault mode) orphans it. Dead pid or an
            # untouched lock older than the build bound means stale.
            try:
                with open(lock) as f:
                    pid = int(f.read().strip() or "0")
            except (OSError, ValueError):
                pid = 0
            if pid:
                try:
                    os.kill(pid, 0)
                    return False  # builder is alive: never stale
                except ProcessLookupError:
                    return True
                except PermissionError:
                    return False  # alive, different uid
            # No readable pid (partial write / legacy lock): fall back to
            # age — an untouched lock older than any plausible build.
            try:
                return _time.time() - os.path.getmtime(lock) > 600.0
            except OSError:
                return False

        deadline = _time.monotonic() + 900.0
        while True:
            try:
                fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.write(fd, str(os.getpid()).encode())
                os.close(fd)
                break
            except FileExistsError:
                if os.path.exists(done_marker):
                    return py
                if _lock_stale():
                    # Clear an orphaned lock.  rename() is atomic, so at
                    # most one waiter unlinks it; everyone then races on
                    # O_EXCL as usual, and ONLY the lock holder touches
                    # the half-built root (below) — no rmtree here, so a
                    # concurrent winner's build can't be deleted.
                    try:
                        os.rename(lock, lock + f".claimed.{os.getpid()}")
                        os.unlink(lock + f".claimed.{os.getpid()}")
                    except OSError:
                        pass
                    continue
                if _time.monotonic() > deadline:
                    raise RuntimeError(
                        f"timed out waiting for venv build lock {lock}")
                # Jittered so a gang of workers racing one build lock
                # don't all re-poll (and re-stat the marker) in phase.
                _time.sleep(retry.jittered(0.5))
        try:
            if not os.path.exists(done_marker):
                # We hold the lock: safe to clear any half-built root left
                # by a SIGKILLed predecessor before building fresh.
                if os.path.isdir(root):
                    import shutil
                    shutil.rmtree(root, ignore_errors=True)
                sp.check_call([sys.executable, "-m", "venv",
                               "--system-site-packages", root],
                              stdout=sp.DEVNULL, stderr=sp.STDOUT)
                # The venv overlays the BASE interpreter's site-packages;
                # when this process itself runs inside a venv (common:
                # /opt/venv), the parent's packages (jax, setuptools...)
                # live one level up and --system-site-packages misses
                # them.  A .pth appends the parent's site dirs AFTER the
                # venv's own, so pip installs still shadow the overlay.
                import site
                parents = [p for p in site.getsitepackages()
                           if os.path.isdir(p)]
                vsite = sp.check_output(
                    [py, "-c", "import site;"
                     "print(site.getsitepackages()[-1])"]).decode().strip()
                with open(os.path.join(vsite, "_parent_overlay.pth"),
                          "w") as f:
                    f.write("\n".join(parents) + "\n")
                sp.check_call([py, "-m", "pip", "install", "--quiet",
                               "--no-build-isolation"] + list(pip_specs),
                              stdout=sp.DEVNULL)
                with open(done_marker, "w") as f:
                    f.write("\n".join(pip_specs))
            return py
        finally:
            try:
                os.unlink(lock)
            except OSError:
                pass

    def prestart_workers(self, n: int, kind: str = "cpu"):
        """Spawn warm workers ahead of demand (reference: WorkerPool
        PrestartWorkers — python startup is expensive, ~2s with jax in the
        interpreter, so cold-start per lease would dominate small tasks)."""
        for _ in range(n):
            w = self._spawn_worker(kind)
            asyncio.get_running_loop().create_task(self._await_prestart(w))

    async def _await_prestart(self, w: WorkerHandle):
        if not await self._wait_registered(w):
            return
        pool = self._idle(w.kind, w.env_key)
        if w.lease_id is None and w not in pool:
            w.last_idle = time.monotonic()
            pool.append(w)
            self._kick_scheduler()

    async def _wait_registered(self, w: WorkerHandle) -> bool:
        """Wait for a spawned worker to register, fast-failing if its
        process dies during startup (bad env, import error) instead of
        sitting out the full register timeout.  Venv workers get triple
        patience: pip may be building their environment first."""
        deadline = time.monotonic() + cfg.worker_register_timeout_s * (
            3 if w.env_key else 1)
        # asyncio.wait, not wait_for: a handler's first step runs inline
        # on its connection's read loop (protocol._dispatch_body), and
        # wait_for's timeout cancels the task it was ENTERED in.
        # Entered here from rpc_lease_worker_for_actor, that is the
        # GCS connection's reader: the connection dropped and the lease
        # reply was lost whenever an actor had to wait for a fresh
        # worker.
        registered = asyncio.ensure_future(w.registered.wait())
        try:
            while not w.registered.is_set():
                if getattr(w, "dead", False):
                    return False
                if w.proc is not None and w.proc.poll() is not None:
                    await self._on_worker_dead(
                        w, f"worker process exited rc={w.proc.returncode} "
                           f"before registering")
                    return False
                if time.monotonic() >= deadline:
                    await self._on_worker_dead(w,
                                               "worker failed to register")
                    return False
                await asyncio.wait([registered], timeout=0.1)
        finally:
            registered.cancel()
        # The event is also set by _on_worker_dead to break this wait.
        return not getattr(w, "dead", False)

    async def _start_zygote(self):
        """Spawn the warm fork-server (zygote.py): one ~2s interpreter +
        import cost per node, after which workers fork in ~10ms instead of
        cold-starting.  Until it's ready, _spawn_worker falls back to
        Popen cold starts."""
        from ray_tpu._private.zygote import ZygoteClient
        sock_path = os.path.join(self.session_dir,
                                 f"zygote_{self.node_id.hex()[:8]}.sock")
        env = dict(self._worker_env())
        env.pop("RT_WORKER_ID", None)
        logfile = os.path.join(self.session_dir, "logs",
                               self.node_id.hex()[:8], "zygote.log")
        os.makedirs(os.path.dirname(logfile), exist_ok=True)
        out = open(logfile, "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.zygote", sock_path],
            env=env, stdout=out, stderr=subprocess.STDOUT,
            start_new_session=True)
        out.close()
        # Owned from the moment it exists (users check .ready), so a
        # shutdown that comes while it is still warming up kills it too.
        self._zygote = zy = ZygoteClient(sock_path, proc)
        if await zy.wait_ready():
            logger.info("zygote ready on %s", self.node_id.hex()[:8])
        elif self._zygote is zy:
            logger.warning("zygote failed to start; using cold spawns")
            zy.kill()
            self._zygote = None

    def _worker_env_for(self, worker_id, kind: str):
        env = dict(self._worker_env())
        env["RT_WORKER_ID"] = worker_id.hex()
        if kind == "tpu":
            # "tpu" and nothing after it: left unset (or with a cpu
            # entry to fall back on), jax logs that it could not open
            # the chip and computes on the host.  This way the failure
            # is an exception in the task or actor that was leased the
            # chip.
            env["JAX_PLATFORMS"] = "tpu"
        return env

    def _worker_logfile(self, worker_id):
        return os.path.join(self.session_dir, "logs",
                            self.node_id.hex()[:8],
                            f"worker-{worker_id.hex()[:8]}.log")

    def _spawn_worker(self, kind: str = "cpu", env_key: str = "",
                      env_spec: dict | None = None) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        env = self._worker_env_for(worker_id, kind)
        logfile = self._worker_logfile(worker_id)
        if env_key:
            # Interpreter-environment runtime env (pip venv / conda env /
            # container image): dedicated worker built asynchronously;
            # the zygote can't serve these — its warm image is the base
            # interpreter.
            spec = env_spec or {}
            w = WorkerHandle(worker_id, None, kind=kind, env_key=env_key)
            self.workers[worker_id] = w
            if spec.get("container"):
                coro = self._spawn_container_worker(
                    w, env, spec["container"], logfile)
            elif spec.get("conda"):
                coro = self._spawn_conda_worker(
                    w, env, spec["conda"], logfile)
            else:
                coro = self._spawn_venv_worker(
                    w, env, env_key, list(spec.get("pip") or []), logfile)
            asyncio.get_running_loop().create_task(coro)
            return w
        if self._zygote is not None and self._zygote.ready:
            # proc is attached asynchronously when the fork reply lands;
            # _wait_registered tolerates proc=None meanwhile.
            w = WorkerHandle(worker_id, None, kind=kind)
            w.how = "zygote"
            self.workers[worker_id] = w
            asyncio.get_running_loop().create_task(
                self._fork_worker(w, env, logfile))
            return w
        proc = self._popen_worker(
            [sys.executable, "-m", "ray_tpu._private.worker_main"],
            env, logfile)
        w = WorkerHandle(worker_id, proc, kind=kind)
        self.workers[worker_id] = w
        return w

    @staticmethod
    def _popen_worker(argv: list, env: dict, logfile: str):
        """One place for the worker-process launch boilerplate shared by
        the base, venv, conda, and container spawn paths."""
        os.makedirs(os.path.dirname(logfile), exist_ok=True)
        out = open(logfile, "ab")
        try:
            return subprocess.Popen(
                argv, env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True)
        finally:
            out.close()

    async def _spawn_venv_worker(self, w: WorkerHandle, env, env_key,
                                 pip_specs, logfile):
        try:
            py = await asyncio.get_running_loop().run_in_executor(
                None, self._ensure_venv, env_key, pip_specs)
            w.proc = self._popen_worker(
                [py, "-m", "ray_tpu._private.worker_main"], env, logfile)
            w.pid = w.proc.pid
        except Exception as e:
            logger.warning("venv worker spawn failed: %s", e)
            await self._on_worker_dead(
                w, f"pip runtime_env creation failed: {e}")

    async def _spawn_conda_worker(self, w: WorkerHandle, env, conda_spec,
                                  logfile):
        """Worker under an EXISTING conda env's interpreter (reference:
        _private/runtime_env/conda.py get_conda_env_dir — envs are
        prebuilt; we resolve name -> prefix -> bin/python)."""
        try:
            prefix = conda_spec
            if not os.path.isdir(prefix):
                prefix = os.path.join(self._conda_root(), "envs",
                                      conda_spec)
            py = os.path.join(prefix, "bin", "python")
            if not os.path.exists(py):
                raise FileNotFoundError(
                    f"conda env {conda_spec!r}: no interpreter at {py}")
            w.proc = self._popen_worker(
                [py, "-m", "ray_tpu._private.worker_main"], env, logfile)
            w.pid = w.proc.pid
        except Exception as e:
            logger.warning("conda worker spawn failed: %s", e)
            await self._on_worker_dead(
                w, f"conda runtime_env creation failed: {e}")

    def _local_env_key(self, env_key: str, env_spec: dict | None) -> str:
        """Pool key for conda envs is resolved LOCALLY, not trusted from
        the submitter: the same interpreter must map to one pool no
        matter how the submitter spelled it (name vs prefix), and two
        distinct envs sharing a basename must not share a pool.  Only
        this raylet knows its filesystem, so the driver-computed key is
        replaced by a hash of the realpath'd prefix (the same
        resolution _spawn_conda_worker applies)."""
        if not env_spec or not env_spec.get("conda"):
            return env_key
        spec = str(env_spec["conda"])
        prefix = spec
        if not os.path.isdir(prefix):
            prefix = os.path.join(self._conda_root(), "envs", spec)
        import hashlib
        return hashlib.sha1(
            ("conda-local:" + os.path.realpath(prefix)).encode()
        ).hexdigest()[:16]

    @staticmethod
    def _conda_root() -> str:
        """The conda INSTALL root (holding envs/), not the active env:
        CONDA_ROOT wins; else derive from CONDA_EXE (<root>/bin/conda);
        else walk an activated env's CONDA_PREFIX (<root>/envs/<name>)
        up to the root; else /opt/conda."""
        root = os.environ.get("CONDA_ROOT")
        if root:
            return root
        exe = os.environ.get("CONDA_EXE")
        if exe:
            return os.path.dirname(os.path.dirname(exe))
        prefix = os.environ.get("CONDA_PREFIX")
        if prefix:
            parent = os.path.dirname(prefix)
            if os.path.basename(parent) == "envs":
                return os.path.dirname(parent)
            return prefix  # base env IS the root
        return "/opt/conda"

    _CONTAINER_ENV_PREFIXES = ("RT_", "JAX_", "XLA_", "PYTHON", "TPU_")

    def _container_command(self, image: str, run_options: list, env: dict,
                           inner: list) -> list:
        """Assemble the `podman/docker run` invocation (reference:
        _private/runtime_env/container.py worker command rewrite).
        --network=host keeps the raylet RPC loopback reachable; the
        session dir bind-mount carries the shm-store arena file, so
        in-container workers mmap the SAME pages (zero-copy object
        reads survive containerization); the repo mount provides the
        framework source when the image doesn't bake it in."""
        import shutil as _shutil
        runtime = os.environ.get("RT_CONTAINER_RUNTIME")             or _shutil.which("podman") or _shutil.which("docker")
        if not runtime:
            raise RuntimeError(
                "container runtime_env needs podman or docker on PATH "
                "(or RT_CONTAINER_RUNTIME)")
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        cmd = [runtime, "run", "--rm", "--network=host",
               "-v", f"{self.session_dir}:{self.session_dir}",
               "-v", f"{repo_root}:{repo_root}:ro"]
        # The store arena usually lives OUTSIDE the session dir (in
        # /dev/shm when writable) — bind-mount the file itself or the
        # worker's mmap of the shared pages fails at startup.
        if self.store_path and not self.store_path.startswith(
                self.session_dir + os.sep):
            cmd += ["-v", f"{self.store_path}:{self.store_path}"]
        keep = {k: v for k, v in env.items()
                if k.startswith(self._CONTAINER_ENV_PREFIXES)}
        keep["PYTHONPATH"] = repo_root + (
            ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        for k, v in sorted(keep.items()):
            cmd += ["-e", f"{k}={v}"]
        cmd += list(run_options)
        cmd.append(image)
        cmd += inner
        return cmd

    async def _spawn_container_worker(self, w: WorkerHandle, env,
                                      container_spec, logfile):
        try:
            name = f"rt-worker-{w.worker_id.hex()[:12]}"
            cmd = self._container_command(
                container_spec["image"],
                ["--name", name]
                + list(container_spec.get("run_options", [])), env,
                ["python", "-m", "ray_tpu._private.worker_main"])
            proc = self._popen_worker(cmd, env, logfile)
            w.proc = _ContainerProcHandle(proc, cmd[0], name)
            w.pid = proc.pid
        except Exception as e:
            logger.warning("container worker spawn failed: %s", e)
            await self._on_worker_dead(
                w, f"container runtime_env creation failed: {e}")

    async def _fork_worker(self, w: WorkerHandle, env, logfile):
        from ray_tpu._private.zygote import PidHandle
        try:
            pid = await self._zygote.fork(env, logfile)
            w.proc = PidHandle(pid)
            w.pid = pid
        except Exception as e:
            logger.warning("zygote fork failed (%s); cold-starting", e)
            w.how = "cold"
            if w.worker_id not in self.workers:
                return  # already reaped
            os.makedirs(os.path.dirname(logfile), exist_ok=True)
            out = open(logfile, "ab")
            w.proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.worker_main"],
                env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True)
            out.close()
            w.pid = w.proc.pid

    async def rpc_register_worker(self, conn, body):
        worker_id = WorkerID.from_hex(body["worker_id"])
        w = self.workers.get(worker_id)
        if w is None:  # e.g. driver-managed process; adopt it
            w = WorkerHandle(worker_id, None)
            self.workers[worker_id] = w
        w.conn = conn
        w.addr = tuple(body["addr"])
        w.pid = body["pid"]
        w.registered.set()
        self._kick_scheduler()
        return {"ok": True, "node_id": self.node_id,
                "store_path": self.store_path,
                "store_capacity": self.store_capacity}

    def _spawn_cap(self) -> int:
        """Concurrent-spawn bound: wide for ~10ms zygote forks, narrow for
        ~2s interpreter cold starts."""
        if self._zygote is not None and self._zygote.ready:
            return 16
        return max(2, int(self.total_resources.get("CPU", 2)))

    async def _get_ready_worker(self, kind: str = "cpu",
                                env_key: str = "",
                                env_spec: dict | None = None
                                ) -> WorkerHandle | None:
        idle = self._idle(kind, env_key)
        while idle:
            w = idle.pop()
            if w.conn is not None and not w.conn.closed:
                w.how = "reused"
                return w
        if len(self.workers) >= cfg.max_workers_per_node:
            return None
        # Bound concurrent cold starts: on a small host an unbounded
        # spawn storm (each ~2s of CPU) starves the running tasks.
        # Zygote forks are ~10ms, so they get a much wider bound; the
        # semaphore is rebuilt whenever the cap changes (zygote warming
        # up or dying) rather than frozen at first use.
        cap = self._spawn_cap()
        if self._spawn_sem is None or self._spawn_sem_cap != cap:
            self._spawn_sem = asyncio.Semaphore(cap)
            self._spawn_sem_cap = cap
        async with self._spawn_sem:
            idle = self._idle(kind, env_key)
            if idle:
                w = idle.pop()
                if w.conn is not None and not w.conn.closed:
                    w.how = "reused"
                    return w
            w = self._spawn_worker(kind, env_key=env_key,
                                   env_spec=env_spec)
            if not await self._wait_registered(w):
                return None
            return w

    async def _on_worker_dead(self, w: WorkerHandle, reason: str):
        if getattr(w, "dead", False):
            return  # already reaped (e.g. spawn failure + register timeout)
        w.dead = True
        w.registered.set()  # wake _wait_registered immediately, not at
        # its deadline — it checks w.dead and reports the spawn failure
        self.workers.pop(w.worker_id, None)
        pool = self._idle(w.kind, w.env_key)
        if w in pool:
            pool.remove(w)
        if w.actor_id is not None and self.gcs is not None:
            try:
                await self.gcs.request("report_actor_death", {
                    "actor_id": w.actor_id, "reason": reason})
            except Exception:
                pass
        if w.proc is not None and w.proc.poll() is None:
            try:
                w.proc.kill()
            except Exception:
                pass
        if w.kind == "tpu":
            # The chips stay open until the process is gone.  Its lease
            # is released only then, so that free TPU resources always
            # mean chips the next worker can open.
            await self._wait_exit(w)
        # Container workers: engine removal runs on a background
        # thread; hold the dead worker's lease resources until removal
        # completes so a replacement isn't granted the same TPU /
        # host-network ports while the old container still holds them.
        join = getattr(w.proc, "join_kill", None)
        if join is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, join, 25.0)
        if w.lease_id is not None:
            lease = self.leases.pop(w.lease_id, None)
            if lease is not None:
                self._release_resources(lease)
        self._kick_scheduler()

    @staticmethod
    async def _wait_exit(w: WorkerHandle, timeout: float = 30.0):
        deadline = time.monotonic() + timeout
        while w.proc is not None and w.proc.poll() is None:
            if time.monotonic() >= deadline:
                logger.error("TPU worker pid %s still alive %.0fs after "
                             "SIGKILL; its chips may stay busy",
                             w.pid, timeout)
                return
            await asyncio.sleep(0.02)

    async def _end_lease(self, w: WorkerHandle):
        """Release a live worker's lease and pool the worker — except a
        TPU worker, which may have opened its chips and holds them for
        as long as it lives.  It dies with its lease (and the lease is
        released once it is gone), so the next TPU lease gets a fresh
        process that can open the chips."""
        w.actor_id = None
        if w.kind == "tpu":
            await self._on_worker_dead(w, "TPU lease ended")
            return
        lease = self.leases.pop(w.lease_id, None)
        if lease is not None:
            self._release_resources(lease)
        w.lease_id = None
        if w.conn is not None and not w.conn.closed:
            w.last_idle = time.monotonic()
            self._idle(w.kind, w.env_key).append(w)

    async def rpc_kill_worker(self, conn, body):
        w = self.workers.get(body["worker_id"])
        if w is None:
            return {"ok": False}
        w.actor_id = None  # killed deliberately; no death report
        if w.proc is not None:
            try:
                w.proc.kill()
            except Exception:
                pass
        await self._on_worker_dead(w, "killed")
        return {"ok": True}

    async def _reap_loop(self):
        while not self._shutdown:
            # Jittered: N raylets in one test process (or container)
            # must not wake and sweep their worker tables in phase.
            await asyncio.sleep(retry.jittered(0.2))
            for w in list(self.workers.values()):
                if w.proc is not None and w.proc.poll() is not None:
                    await self._on_worker_dead(
                        w, f"worker exited with code {w.proc.returncode}")
            # trim long-idle workers
            now = time.monotonic()
            for key, idle in self.idle_workers.items():
                keep = []
                for w in idle:
                    if now - w.last_idle > cfg.idle_worker_keep_s:
                        if w.proc is not None:
                            try:
                                w.proc.terminate()
                            except Exception:
                                pass
                    else:
                        keep.append(w)
                self.idle_workers[key] = keep

    # ------------------------------------------------------------ resources
    def _fits(self, resources: dict, pg_key=None) -> bool:
        pool = self.bundles[pg_key]["avail"] if pg_key else self.available
        return all(pool.get(k, 0) >= v - 1e-9 for k, v in resources.items())

    def _fits_total(self, resources: dict) -> bool:
        return all(self.total_resources.get(k, 0) >= v - 1e-9
                   for k, v in resources.items())

    def _acquire(self, resources: dict, pg_key=None):
        pool = self.bundles[pg_key]["avail"] if pg_key else self.available
        for k, v in resources.items():
            pool[k] = pool.get(k, 0) - v

    def _release(self, resources: dict, pg_key=None):
        pool = self.available if pg_key is None else None
        if pg_key is not None:
            bundle = self.bundles.get(pg_key)
            if bundle is None:
                return
            pool = bundle["avail"]
        for k, v in resources.items():
            pool[k] = pool.get(k, 0) + v

    def _release_resources(self, lease: Lease):
        if not lease.blocked:
            self._release(lease.resources, lease.pg_key)
        else:
            non_cpu = {k: v for k, v in lease.resources.items() if k != "CPU"}
            self._release(non_cpu, lease.pg_key)
        self._free_tpu_ids(lease)

    # ----------------------------------------------------- TPU device ids
    def _alloc_tpu_ids(self, lease: Lease) -> list:
        """Pin specific chip indices to a lease.  Whole-chip requests
        take exclusively-free slots; fractional requests bin-pack onto
        the fullest slot that still fits (so two 0.5 leases share one
        chip and whole chips stay free for whole-chip leases).  Ids are
        advisory — allocation failure (fragmentation) grants the lease
        with no pinned ids rather than blocking it."""
        amount = float(lease.resources.get("TPU", 0) or 0)
        if amount <= 0 or not self._tpu_slots:
            return []
        ids: list = []
        if amount >= 1.0 - 1e-9:
            free = [i for i, used in self._tpu_slots.items()
                    if used <= 1e-9]
            k = int(round(amount))
            if len(free) < k:
                return []
            ids = free[:k]
            for i in ids:
                self._tpu_slots[i] = 1.0
        else:
            cands = [(used, i) for i, used in self._tpu_slots.items()
                     if used + amount <= 1.0 + 1e-9]
            if not cands:
                return []
            _, best = max(cands)
            self._tpu_slots[best] += amount
            ids = [best]
        lease.tpu_ids = ids
        return ids

    def _free_tpu_ids(self, lease: Lease):
        amount = float(lease.resources.get("TPU", 0) or 0)
        if not lease.tpu_ids:
            return
        if amount >= 1.0 - 1e-9:
            for i in lease.tpu_ids:
                self._tpu_slots[i] = 0.0
        else:
            for i in lease.tpu_ids:
                left = self._tpu_slots[i] - amount
                # Snap float residue to exactly 0.0: non-binary
                # fractions (three 0.3 leases, say) otherwise leave
                # ~1e-17 occupancy that blocks whole-chip grants on
                # this slot forever.
                self._tpu_slots[i] = 0.0 if left < 1e-9 else left
        lease.tpu_ids = []

    # --------------------------------------------------------------- leases
    async def rpc_request_worker_lease(self, conn, body):
        """Lease protocol (reference: NodeManager::HandleRequestWorkerLease
        node_manager.cc:1822 — grant locally, queue, or reply with a
        spillback node for the submitter to retry on)."""
        resources = body.get("resources") or {}
        pg_id = body.get("pg_id")
        bundle_index = body.get("bundle_index")
        hopped = body.get("hops", 0) > 0
        pg_key = None
        strat = body.get("strategy") or {}
        affinity_local = False
        if pg_id is None and strat.get("type") == "node_affinity":
            # Locality-routed task (e.g. the data layer's streaming
            # executor placing a map task where its input block lives):
            # redirect the lease to the target raylet when it is a
            # live, non-draining peer; a SOFT miss (dead/unknown
            # target) falls through to the ordinary policy chain,
            # a hard miss errors.  The spillback reply nulls the
            # strategy on the worker side, so the target just grants
            # or queues locally.
            target = self._affinity_node(strat.get("node_id"))
            soft = bool(strat.get("soft", False))
            if target is not None and target != self.node_id \
                    and not hopped:
                view = self.cluster_nodes.get(target)
                if view is not None and view.get("alive", True) \
                        and view.get("addr") \
                        and not view.get("draining"):
                    return {"spillback": tuple(view["addr"])}
                target = None  # known-dead / not-yet-known target
            if target == self.node_id:
                # Affinity to THIS node — soft or hard — must not be
                # re-spilled by the busy-shed hybrid policy below:
                # "busy right now" is exactly when a locality-placed
                # task should QUEUE here rather than run somewhere it
                # has to pull its input from (warm idle leases hold
                # CPUs, so a shed would fire on every loaded node).
                # Soft only governs the dead/unknown-target fallback;
                # an infeasible-forever shape still spills via the
                # fits-total branch above.
                affinity_local = True
            elif target is None and not soft:
                return {"error": "node affinity target is not "
                                 "schedulable (dead or unknown)"}
        if pg_id is not None:
            pg_key = self._bundle_key_for(pg_id, bundle_index, resources)
            if pg_key is None:
                return {"error": f"placement group {pg_id} bundle "
                                 f"{bundle_index} not on this node"}
        elif not self._fits_total(resources):
            # Infeasible here — spill to a node where it can ever fit.
            target = self._pick_spillback(resources)
            if target is not None:
                return {"spillback": target}
            # Infeasible CLUSTER-WIDE: queue, don't error (reference: the
            # raylet's infeasible task queue — the request becomes
            # autoscaler demand via pending_shapes, and _respill_pending
            # redirects it when a capable node joins).  Surface the wait
            # as a cluster event ONCE PER SHAPE (a fan-out of identical
            # requests must not flood the bounded event ring).
            shape = tuple(sorted(resources.items()))
            if shape not in self._infeasible_warned:
                self._infeasible_warned.add(shape)
                try:
                    await self.gcs.request("publish", {
                        "channel": "events",
                        "message": {"severity": "WARNING",
                                    "source": "raylet",
                                    "message": f"task demand {resources} "
                                               f"is infeasible on the "
                                               f"current cluster; waiting "
                                               f"for scale-up"}})
                except Exception:
                    pass
        elif (body.get("strategy") or {}).get("type") == "spread":
            target = self._pick_spread_target(resources)
            if target is not None:
                return {"spillback": target}
        elif hopped or affinity_local:
            # Already spilled here once (or hard-affinity-pinned here):
            # queue locally — re-spilling on a stale resource view of
            # the sender ping-pongs the request until its hop budget
            # dies (reference: the lease protocol's spillback count).
            pass
        elif not self._fits(resources):
            # Feasible here but busy: shed to a node that can run it NOW,
            # scored by post-placement critical-resource utilization
            # (reference: hybrid pack/spread scoring,
            # raylet/scheduling/policy/hybrid_scheduling_policy.h:48 +
            # scorer.h — local-first, spill at saturation).
            target = self._pick_hybrid_target(resources)
            if target is not None:
                return {"spillback": target}
        fut = asyncio.get_running_loop().create_future()
        self.pending_leases.append({"resources": resources, "pg_key": pg_key,
                                    "future": fut,
                                    "env_key": self._local_env_key(
                                        body.get("env_key", ""),
                                        body.get("env_spec")),
                                    "env_spec": body.get("env_spec"),
                                    "request_id": body.get("request_id")})
        self._kick_scheduler()
        granted = await fut
        return granted

    async def rpc_cancel_lease_requests(self, conn, body):
        """Cancel queued (not yet granted) lease requests (reference:
        node_manager.proto CancelWorkerLease — submitters cancel speculative
        leases when their task queue drains)."""
        ids = set(body["request_ids"])
        cancelled = 0
        for req in list(self.pending_leases):
            if req.get("request_id") in ids and not req["future"].done():
                req["future"].set_result({"cancelled": True})
                self.pending_leases.remove(req)
                cancelled += 1
        return {"cancelled": cancelled}

    def _affinity_node(self, nid):
        """Resolve a node_affinity target to a known NodeID.  Callers
        commonly pass the hex string from ray_tpu.nodes(); the data
        layer passes owner-recorded NodeIDs directly."""
        if nid is None:
            return None
        if nid == self.node_id or nid in self.cluster_nodes:
            return nid
        if isinstance(nid, str):
            if nid == self.node_id.hex():
                return self.node_id
            for k in self.cluster_nodes:
                if getattr(k, "hex", None) and k.hex() == nid:
                    return k
        return None

    def _bundle_key_for(self, pg_id, bundle_index, resources):
        if bundle_index is not None and bundle_index >= 0:
            key = (pg_id, bundle_index)
            return key if key in self.bundles else None
        for key, acct in self.bundles.items():
            if key[0] == pg_id and all(acct["avail"].get(k, 0) >= v
                                       for k, v in resources.items()):
                return key
        for key in self.bundles:
            if key[0] == pg_id:
                return key
        return None

    # Spillback / spread / hybrid targeting now rides the composable
    # policy chain over the incrementally-indexed cluster view
    # (sched_policy.py): same scoring semantics as the old inline scans
    # (parity-tested in tests/test_sched_policy.py), but a decision
    # costs O(candidates-inspected) instead of a rescan of every node
    # view, and spillback rotates among eligible targets instead of
    # pile-driving the first total-fit node in view order.

    def _pick_spillback(self, resources):
        return self.sched.pick_spillback(resources, exclude=self.node_id)

    def _pick_hybrid_target(self, resources):
        """Least-utilized node with the request's resources AVAILABLE
        right now; None keeps the task queued locally."""
        return self.sched.pick_hybrid(resources, exclude=self.node_id)

    def _pick_spread_target(self, resources):
        """SPREAD strategy: redirect to the least-loaded feasible node
        (reference: scheduling/policy/spread_scheduling_policy)."""
        return self.sched.pick_spread(resources, self._load(),
                                      exclude=self.node_id)

    def _load(self):
        return len(self.pending_leases)

    def _kick_scheduler(self):
        self._kick_pending = True
        asyncio.get_running_loop().call_soon(
            lambda: asyncio.get_running_loop().create_task(
                self._schedule_leases()))

    _scheduling = False
    _kick_pending = False

    async def _schedule_leases(self):
        """Grant pending lease requests from the idle pool; never block on a
        worker cold-start (spawns run as background tasks and re-kick)."""
        if self._shutdown:
            return  # the store handle is gone; a late kick must not touch it
        if self._scheduling:
            self._kick_pending = True
            return
        self._scheduling = True
        try:
            need_spawn: dict = {}
            # Object-store backpressure (reference: memory-aware admission
            # in the raylet): admitting more tasks while the arena is
            # nearly all PINNED only adds more pinned args — the running
            # tasks must finish (and release pins) first.  Gate on
            # pinned+unsealed, not used(): unpinned secondary copies are
            # evictable on demand and must not throttle admission.  One
            # lease always proceeds so the node can't wedge.  Sampled
            # once per pass (it scans the object table under the store
            # mutex).
            store_pressured = False
            if len(self.leases) >= 1 and self.pending_leases:
                st = self.store.stats()
                store_pressured = (st["pinned_bytes"] + st["unsealed_bytes"]
                                   > 0.85 * self.store_capacity)
            for req in list(self.pending_leases):
                if req["future"].done():
                    self.pending_leases.remove(req)
                    continue
                if not self._fits(req["resources"], req["pg_key"]):
                    continue
                if store_pressured and len(self.leases) >= 1:
                    break
                kind = "tpu" if req["resources"].get("TPU") else "cpu"
                env_key = req.get("env_key", "")
                w = None
                idle = self._idle(kind, env_key)
                while idle:
                    cand = idle.pop()
                    if cand.conn is not None and not cand.conn.closed:
                        w = cand
                        break
                if w is None:
                    cur = need_spawn.setdefault(
                        (kind, env_key), [0, req.get("env_spec")])
                    cur[0] += 1
                    continue
                self._acquire(req["resources"], req["pg_key"])
                self.pending_leases.remove(req)
                lease_id = os.urandom(8)
                lease = Lease(lease_id, w, req["resources"], req["pg_key"])
                self.leases[lease_id] = lease
                w.lease_id = lease_id
                req["future"].set_result({
                    "lease_id": lease_id,
                    "worker_addr": w.addr,
                    "worker_id": w.worker_id,
                    "node_id": self.node_id,
                    "tpu_ids": self._alloc_tpu_ids(lease),
                })
            for (kind, env_key), (n, env_spec) in need_spawn.items():
                self._ensure_spawning(kind, n, env_key=env_key,
                                      env_spec=env_spec)
        finally:
            self._scheduling = False
            if self._kick_pending and self.pending_leases:
                self._kick_pending = False
                asyncio.get_running_loop().create_task(
                    self._schedule_leases())

    _spawns_outstanding = 0

    def _ensure_spawning(self, kind: str, demand: int,
                         env_key: str = "", env_spec: dict | None = None):
        """Keep at most `demand` additional cold starts in flight, bounded by
        the node CPU count and the pool cap (reference: WorkerPool
        maximum_startup_concurrency).  Zygote forks are cheap, so the
        bound widens once the fork server is warm."""
        cap = self._spawn_cap()
        can_spawn = min(
            demand - self._spawns_outstanding,
            cap - self._spawns_outstanding,
            cfg.max_workers_per_node - len(self.workers),
        )
        for _ in range(max(0, can_spawn)):
            self._spawns_outstanding += 1
            w = self._spawn_worker(kind, env_key=env_key,
                                   env_spec=env_spec)
            asyncio.get_running_loop().create_task(self._finish_spawn(w))

    async def _finish_spawn(self, w: WorkerHandle):
        try:
            if not await self._wait_registered(w):
                return
        finally:
            self._spawns_outstanding -= 1
        pool = self._idle(w.kind, w.env_key)
        if w.lease_id is None and w not in pool:
            w.last_idle = time.monotonic()
            pool.append(w)
        self._kick_scheduler()

    async def rpc_return_worker(self, conn, body):
        lease = self.leases.get(body["lease_id"])
        if lease is None:
            return {"ok": False}
        if body.get("kill"):
            await self._on_worker_dead(lease.worker,
                                       "lease returned with kill")
        else:
            await self._end_lease(lease.worker)
        self._kick_scheduler()
        return {"ok": True}

    async def rpc_worker_blocked(self, conn, body):
        """Worker is blocked in get(); temporarily release its CPUs so the
        pool can make progress (reference: node_manager blocked-worker
        resource release — prevents nested-get deadlock)."""
        lease = self.leases.get(body["lease_id"])
        if lease is None or lease.blocked:
            return {"ok": False}
        lease.blocked = True
        cpus = {k: v for k, v in lease.resources.items() if k == "CPU"}
        if cpus:
            self._release(cpus, lease.pg_key)
            self._kick_scheduler()
        return {"ok": True}

    async def rpc_worker_unblocked(self, conn, body):
        lease = self.leases.get(body["lease_id"])
        if lease is None or not lease.blocked:
            return {"ok": False}
        lease.blocked = False
        cpus = {k: v for k, v in lease.resources.items() if k == "CPU"}
        if cpus:
            self._acquire(cpus, lease.pg_key)  # may overcommit briefly
        return {"ok": True}

    # -------------------------------------------------------- actor leasing
    async def rpc_lease_worker_for_actor(self, conn, body):
        resources = body.get("resources") or {}
        pg_id = body.get("pg_id")
        pg_key = None
        if pg_id is not None:
            pg_key = self._bundle_key_for(pg_id, body.get("bundle_index"),
                                          resources)
            if pg_key is None:
                return {"ok": False, "reason": "bundle not here"}
        if not self._fits(resources, pg_key):
            return {"ok": False, "reason": "resources busy"}
        self._acquire(resources, pg_key)
        kind = "tpu" if resources.get("TPU") else "cpu"
        renv = (body.get("spec") or {}).get("runtime_env") or {}
        from ray_tpu.runtime_env import env_spec as _env_spec
        from ray_tpu.runtime_env import worker_env_key
        espec = _env_spec(renv)
        t_asked = time.time()
        w = await self._get_ready_worker(
            kind,
            env_key=self._local_env_key(worker_env_key(renv), espec),
            env_spec=espec)
        if w is None:
            self._release(resources, pg_key)
            return {"ok": False, "reason": "no worker"}
        # The wait for a worker, under the creation task's trace (once
        # an actor): the worker gets the same two timestamps for its
        # start's books and the span id to hang its own boot under.
        trace = (body.get("spec") or {}).get("trace")
        worker_start = {"how": w.how, "t0": t_asked, "t1": time.time(),
                        "span_id": _tracing.fresh_id()}
        if trace:
            _tracing.record(
                "raylet", "raylet.worker_start", t_asked,
                worker_start["t1"] - t_asked,
                trace={"trace_id": trace["trace_id"],
                       "span_id": worker_start["span_id"],
                       "parent_id": trace.get("parent_id")},
                args={"how": w.how, "kind": kind})
        lease_id = os.urandom(8)
        lease = Lease(lease_id, w, resources, pg_key)
        self.leases[lease_id] = lease
        w.lease_id = lease_id
        w.actor_id = body["actor_id"]
        tpu_ids = self._alloc_tpu_ids(lease)
        try:
            # No deadline: this runs the user's constructor, which may
            # load or compile a model for minutes (like push_task, which
            # has none either).  A worker that dies closes its
            # connection, and that fails the request.
            reply = await w.conn.request("create_actor", {
                "actor_id": body["actor_id"],
                "spec": body["spec"],
                "lease_id": lease_id,
                "tpu_ids": tpu_ids,
                "worker_start": worker_start,
            }, timeout=None)
        except Exception as e:
            await self._on_worker_dead(w, f"actor creation failed: {e}")
            return {"ok": False, "reason": f"create_actor failed: {e}"}
        if not reply.get("ok"):
            await self._end_lease(w)
            return {"ok": False, "reason": reply.get("error", "init failed"),
                    "init_error": reply.get("error_blob")}
        return {"ok": True, "worker_addr": w.addr, "worker_id": w.worker_id,
                "pid": w.pid}

    # ------------------------------------------------------ placement groups
    async def rpc_prepare_bundle(self, conn, body):
        resources = body["resources"]
        if not self._fits(resources):
            return {"ok": False}
        self._acquire(resources)
        key = (body["pg_id"], body["bundle_index"])
        self.bundles[key] = {"reserved": dict(resources),
                             "avail": dict(resources), "committed": False}
        return {"ok": True}

    async def rpc_commit_bundle(self, conn, body):
        key = (body["pg_id"], body["bundle_index"])
        if key in self.bundles:
            self.bundles[key]["committed"] = True
            return {"ok": True}
        return {"ok": False}

    async def rpc_return_bundle(self, conn, body):
        key = (body["pg_id"], body["bundle_index"])
        acct = self.bundles.pop(key, None)
        if acct is not None:
            self._release(acct["reserved"])
            self._kick_scheduler()
        return {"ok": True}

    # ---------------------------------------------------------- object store
    async def rpc_os_create(self, conn, body):
        oid: bytes = body["oid"]
        size: int = body["size"]
        if size > self.store_capacity:
            # Can never fit — fail NOW, not after the full retry window.
            return {"error": f"object of {size} bytes exceeds the "
                             f"object store capacity "
                             f"({self.store_capacity} bytes)"}
        # One bounded converge loop for BOTH transient obstacles:
        #  - memory pinned by running tasks' zero-copy args: QUEUE the
        #    create instead of failing (reference: the plasma store's
        #    create-request queue blocks until eviction frees room) —
        #    pins drop as tasks finish, backoff re-probes ever more
        #    gently after a nearly-free first retry;
        #  - an UNSEALED in-flight creation of the same oid (inbound
        #    pull/push, another worker): alloc raises KeyError but
        #    contains() is sealed-only, so {exists} would make the
        #    client skip its write while trusting a transfer that may
        #    yet abort (leaving the object permanently unsealed).  Wait
        #    it out: the seal turns the NEXT iteration's contains()
        #    into {exists}; an abort frees the entry and our alloc
        #    wins.
        deadline = (asyncio.get_running_loop().time()
                    + cfg.create_retry_timeout_s)
        backoff = retry.ExpBackoff(0.02, 0.5)
        off = None
        inflight = False
        while True:
            if self.store.contains(oid):
                # Idempotent create: a reconstruction re-executing the
                # producing task on a node that still holds a SEALED
                # copy must not error — the client skips its
                # write+seal and the existing copy stands.
                return {"exists": True}
            try:
                off = await self._alloc_with_spill(oid, size)
                inflight = False
            except KeyError:
                off, inflight = None, True
            if off is not None or self._shutdown or \
                    asyncio.get_running_loop().time() >= deadline:
                break
            await asyncio.sleep(backoff.next())
        if self._shutdown:
            return {"error": "raylet shutting down"}
        if inflight:
            return {"error": f"creation of {oid.hex()} raced an "
                             f"in-flight transfer that neither sealed "
                             f"nor aborted within "
                             f"{cfg.create_retry_timeout_s:.0f}s"}
        if off is None:
            try:
                holders = {}
                for conn_id, pins in self._client_pins.items():
                    who = "?"
                    for w in self.workers.values():
                        if w.conn is not None and id(w.conn) == conn_id:
                            who = f"worker:{w.pid}"
                            break
                    holders[f"{who}#{conn_id % 9973}"] = sum(pins.values())
                logger.warning(
                    "create of %d bytes timed out; stats=%s primaries=%d "
                    "holders=%s", size, self.store.stats(),
                    len(self.primary_objects), holders)
            except Exception:
                pass
            return {"error": f"object store OOM allocating {size} bytes "
                             f"(after spilling)"}
        self._created_sizes[oid] = size
        # Remember who is mid-create: if the client dies before sealing,
        # its unsealed allocation must be discarded (conn-loss handler).
        self._creating.setdefault(id(conn), set()).add(oid)
        return {"offset": off}

    async def _alloc_with_spill(self, oid: bytes, size: int):
        """alloc, spilling primary copies to disk on memory pressure (the
        C++ store already LRU-evicts unpinned secondary copies).  Spills
        escalate: a fragmented arena may need several times `size` freed
        before first-fit finds a contiguous hole, so keep spilling until
        the alloc lands or nothing spillable remains."""
        off = self.store.alloc(oid, size)
        attempt = 0
        while off is None and attempt < 6:
            freed = await self._spill_bytes(size * (1 + attempt))
            off = self.store.alloc(oid, size)
            if freed == 0 and off is None:
                break
            attempt += 1
        return off

    async def _spill_bytes(self, need: int) -> int:
        """Move primary copies to disk, oldest first, until ~need bytes of
        pinned space have been released.  Returns bytes freed."""
        os.makedirs(self.spill_dir, exist_ok=True)
        freed = 0
        loop = asyncio.get_running_loop()
        for oid in list(self.primary_objects):
            if freed >= need:
                break
            size = self.primary_objects.get(oid)
            if size is None or oid in self.spilled \
                    or oid in self._spilling:
                # _spilling guard: concurrent OOM allocs must not spill the
                # same object twice (double file write + pin over-release).
                continue
            self._spilling.add(oid)
            got = self.store.get(oid)
            try:
                if got is None:
                    self.primary_objects.pop(oid, None)
                    continue
                offset, sz, sealed = got
                if not sealed:
                    # Get() takes no pin on unsealed objects — nothing
                    # to release (a release here would have stolen the
                    # creator's pin and freed the extent under its
                    # in-progress write; the store now rejects it).
                    continue
                path = os.path.join(self.spill_dir, oid.hex())
                data = bytes(self.mapping.slice(offset, sz))
                await loop.run_in_executor(None, self._write_spill_file,
                                           path, data)
                self.store.release(oid)        # our read pin
                self.spilled[oid] = (path, sz)
                self.primary_objects.pop(oid, None)
                # Deferred delete + drop the creator pin: the arena region
                # is reclaimed once concurrent readers release.
                self.store.delete(oid)
                self.store.release(oid)
                freed += sz
                logger.info("spilled %s (%d bytes) to %s",
                            oid.hex()[:8], sz, path)
            finally:
                self._spilling.discard(oid)
        return freed

    @staticmethod
    def _write_spill_file(path: str, data: bytes):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    async def _restore_spilled(self, oid: bytes) -> bool:
        """Bring a spilled object back into the arena (reference:
        SpilledObjectReader)."""
        ent = self.spilled.get(oid)
        if ent is None:
            return False
        fut = self._restores_inflight.get(oid)
        if fut is not None:
            return await asyncio.shield(fut)
        fut = asyncio.get_running_loop().create_future()
        self._restores_inflight[oid] = fut
        off = None
        try:
            path, size = ent
            off = await self._alloc_with_spill(oid, size)
            if off is None:
                fut.set_result(False)
                return False
            data = await asyncio.get_running_loop().run_in_executor(
                None, lambda: open(path, "rb").read())
            self.mapping.slice(off, size)[:] = data
            # Restored copy is evictable (the disk copy remains the
            # primary until os_delete).
            self._seal_release_notify(oid)
            fut.set_result(True)
            return True
        except Exception as e:
            logger.warning("restore of %s failed: %s", oid.hex()[:8], e)
            if off is not None:
                self._discard_unsealed(oid)
            if not fut.done():
                fut.set_result(False)
            return False
        finally:
            self._restores_inflight.pop(oid, None)

    async def rpc_os_seal(self, conn, body):
        oid = body["oid"]
        creating = self._creating.get(id(conn))
        if creating is not None:
            creating.discard(oid)
        self.store.seal(oid)
        size = self._created_sizes.pop(oid, None)
        if size is not None:
            # Client-created (not pulled): this node holds the primary copy.
            self.primary_objects[oid] = size
        for fut in self.seal_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(None)
        self._report_sealed(oid)
        return {"ok": True}

    def _report_sealed(self, oid: bytes):
        """Report a fresh sealed copy to the GCS object directory —
        only when it is big enough to ever stripe: the directory's sole
        consumer is multi-source pull selection, and sub-threshold
        objects would just accrete entries the C store can LRU-evict
        without telling anyone."""
        got = self.store.get(oid)
        if got is None:
            return
        self.store.release(oid)
        if got[1] >= cfg.transfer_stripe_min_bytes:
            self._reported_locs.add(oid)
            self._report_locations([oid], added=True)

    def _report_locations(self, oids, added: bool):
        """Fire-and-forget report of sealed copies appearing/vanishing
        on this node to the GCS object directory (the striped-pull
        source list).  Best-effort: a lost report only costs a pull its
        extra sources, and stat-at-pull filters stale entries."""
        if self.gcs is None or self.gcs.closed or self._shutdown:
            return
        method = ("object_locations_added" if added
                  else "object_locations_removed")
        try:
            task = asyncio.get_running_loop().create_task(
                self.gcs.push(method, {"node_id": self.node_id,
                                       "oids": list(oids)}))
            task.add_done_callback(
                lambda t: t.cancelled() or t.exception())
        except Exception:
            pass

    async def rpc_os_get(self, conn, body):
        """Resolve objects to (offset, size) in the local arena, pulling from
        remote nodes when needed (locations provided by owners).  The
        client's timeout becomes ONE deadline for the whole resolution —
        every wait and every pulled chunk draws from the same budget
        (previously each chunk request was re-granted the full timeout,
        so a transfer could legally take timeout x n_chunks)."""
        oid = body["oid"]
        timeout = body.get("timeout", 60.0)
        deadline = time.monotonic() + timeout
        location = body.get("location")  # NodeID where the object lives
        # Caller's span context (worker-side get): a pull recorded here
        # links into the task's trace, crossing worker -> raylet.  The
        # flow edge closes HERE, not inside TransferManager.pull: the
        # resolution may be served without a fresh pull (already local,
        # joined an in-flight pull or push), and the worker's flow-start
        # must not dangle in those cases.
        trace = body.get("trace")
        if trace and trace.get("flow"):
            _tracing.flow_end(trace["flow"], "transfer")
        if oid in self.spilled and not self.store.contains(oid):
            await self._restore_spilled(oid)
        got = self.store.get(oid)
        if got is not None:
            offset, size, sealed = got
            if sealed:
                self._track_pin(conn, oid)
                return {"offset": offset, "size": size}
            await self._wait_sealed(oid, self._remaining(deadline))
            got = self.store.get(oid)
            if got and got[2]:
                # Keep the re-get's pin and track it: the client's later
                # os_release must find a pin of its own to drop, not steal
                # the creator's.
                self._track_pin(conn, oid)
                return {"offset": got[0], "size": got[1]}
            # "timeout": the caller's budget ran out, the object still
            # exists — the worker maps this to GetTimeoutError, never to
            # an ObjectLostError that would trigger reconstruction.
            return {"error": "timeout waiting for object seal",
                    "timeout": True}
        if location is not None and location != self.node_id:
            # A failed pull is only "lost" if the control plane agrees no
            # copy-holding node is alive; an unreachable-but-alive source
            # (partition, restart, half-open link) is transient, so the
            # pull retries under the caller's budget.  Reporting a merely
            # partitioned object as lost would re-execute its creating
            # task even though the copy still exists.
            backoff = retry.ExpBackoff(0.05, 1.0)
            ok = False
            while True:
                ok = await self._pull_object(oid, location, deadline,
                                             trace)
                if ok:
                    break
                if time.monotonic() >= deadline:
                    return {"error": f"pull deadline exceeded fetching "
                                     f"{oid.hex()}", "timeout": True}
                if not await self._object_source_alive(oid, location):
                    return {"error": f"failed to pull {oid.hex()} from "
                                     f"{location.hex()[:8]}: no live "
                                     f"source"}
                rem = self._remaining(deadline)
                await asyncio.sleep(min(backoff.next(), rem or 0.001))
            got = self.store.get(oid)
            if got and got[2]:
                self._track_pin(conn, oid)
                return {"offset": got[0], "size": got[1]}
        await self._wait_sealed(oid, self._remaining(deadline))
        got = self.store.get(oid)
        if got and got[2]:
            self._track_pin(conn, oid)
            return {"offset": got[0], "size": got[1]}
        # NOT flagged as a timeout even when the budget is spent: with no
        # pullable location and nothing sealed locally the object may be
        # genuinely gone, and ObjectLostError is what lets the owner fall
        # back to lineage reconstruction.
        return {"error": f"object {oid.hex()} not found"}

    # One deadline clamp for the whole transfer plane (shared with
    # TransferManager so the floor/None semantics can't diverge).
    _remaining = staticmethod(_remain)

    async def _object_source_alive(self, oid, location) -> bool:
        """Is ANY node believed to hold a copy of ``oid`` still alive
        per the control plane?  Decides pull-retry (alive: the failure
        is transient) vs ObjectLost/reconstruction (dead).  Liveness
        is answered from the pubsub-synced local node view — this runs
        once per failed pull attempt, and re-dumping the whole node
        table from the GCS on every retry across many degraded pulls
        would stampede the very service the jittered retries protect —
        with one cheap directory RPC for extra copy-holders.  An
        unreachable GCS cannot prove death, so it answers alive."""
        candidates = {location}
        if self.gcs is not None and not self.gcs.closed:
            try:
                reply = await self.gcs.request(
                    "get_object_locations", {"oid": oid}, timeout=5.0)
                candidates.update(reply.get("locations", []))
            except Exception:
                return True  # partitioned from the GCS: inconclusive
        for nid in candidates:
            view = self.cluster_nodes.get(nid)
            if view is not None and view.get("alive", True):
                return True
        return False

    async def _wait_sealed(self, oid, timeout):
        fut = asyncio.get_running_loop().create_future()
        self.seal_waiters.setdefault(oid, []).append(fut)
        try:
            await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError:
            pass

    async def _peer(self, node_id) -> protocol.Connection | None:
        conn = self.peer_conns.get(node_id)
        if conn is not None and not conn.closed:
            return conn
        view = self.cluster_nodes.get(node_id)
        if view is None and self.gcs is not None:
            # Routed through _observe_node_view: the scheduling index
            # must learn anything this fallback discovers, and dead
            # (alive=False) views must stay rejected — get_nodes
            # returns the full table including the departed.
            for v in await self.gcs.request("get_nodes", {}):
                self._observe_node_view(v)
            view = self.cluster_nodes.get(node_id)
        if view is None:
            return None
        try:
            conn = await protocol.Connection.connect(
                view["addr"][0], view["addr"][1], handler=self._handle,
                name=f"raylet:{self.node_id.hex()[:8]}"
                     f"->raylet:{node_id.hex()[:8]}",
                timeout=cfg.connect_timeout_s,
                blob_provider=self._blob_sink)
        except Exception:
            return None
        self.peer_conns[node_id] = conn
        return conn

    async def _pull_object(self, oid, location, deadline,
                           trace=None) -> bool:
        if oid in self._pulls_inflight:
            try:
                return await asyncio.wait_for(
                    asyncio.shield(self._pulls_inflight[oid]),
                    self._remaining(deadline))
            except asyncio.TimeoutError:
                return False
        fut = asyncio.get_running_loop().create_future()
        self._pulls_inflight[oid] = fut
        try:
            ok = await self._do_pull(oid, location, deadline, trace)
            if not fut.done():
                fut.set_result(ok)
            return ok
        except Exception as e:
            if not fut.done():
                fut.set_result(False)
            logger.warning("pull %s failed: %s", oid.hex()[:8], e)
            return False
        finally:
            self._pulls_inflight.pop(oid, None)

    async def _do_pull(self, oid, location, deadline, trace=None) -> bool:
        if oid in self._push_recv:
            # A push of this object is already streaming in: wait for its
            # seal instead of double-allocating.  If the pushing sender
            # dies, _abort_pushes_from (conn loss) or the stale sweep
            # cleans the transfer and wakes us to fall through to a pull.
            await self._wait_sealed(oid, self._remaining(deadline))
            got = self.store.get(oid)
            if got is not None and got[2]:
                self.store.release(oid)  # get() pinned the sealed copy
                return True
            if oid in self._push_recv:
                # Push stream still live after the full deadline: it owns
                # the allocation, so a pull can't proceed.
                return False
        # Windowed, possibly striped transfer (TransferManager resolves
        # extra sealed sources via the GCS object directory).
        return await self.transfers.pull(oid, location, deadline,
                                         trace=trace)

    async def rpc_os_stat(self, conn, body):
        oid = body["oid"]
        got = self.store.get(oid)
        if got is None or not got[2]:
            spilled = self.spilled.get(oid)
            if spilled is not None:
                return {"size": spilled[1]}
            if oid in self._reported_locs:
                # Directory self-heal: this node once advertised a copy
                # the C store has since LRU-evicted (eviction has no
                # Python hook).  The first wasted stat prunes the stale
                # entry so later pulls stop selecting this node.
                self._reported_locs.discard(oid)
                self._report_locations([oid], added=False)
            return {"error": "not here"}
        self.store.release(oid)
        return {"size": got[1]}

    async def rpc_os_map(self, conn, body):
        """Same-host zero-copy pull support: pin the sealed object and
        expose its arena location so a co-located raylet can mmap this
        node's arena file read-only and memcpy the bytes directly
        (reference: plasma clients share the store mmap; here each
        raylet owns an arena, so cross-raylet same-host reads map the
        peer's file).  The caller MUST os_release when the copy is done
        (conn loss releases tracked pins as usual)."""
        oid = body["oid"]
        got = self.store.get(oid)
        if got is None or not got[2]:
            return {"error": "not here"}  # spilled/unsealed: wire path
        offset, size, _ = got
        self._track_pin(conn, oid)
        return {"offset": offset, "size": size,
                "store_path": self.store_path,
                "capacity": self.store_capacity}

    async def rpc_os_read_chunk(self, conn, body):
        """Serve one chunk of a sealed (or spilled) object.  The reply
        rides a raw KIND_BLOB_REP frame: the arena slice goes to the
        transport as ONE memoryview (the read pin is dropped once the
        transport no longer references it) — chunk bytes never touch
        pickle.  ``body["pickle"]`` selects the legacy pickled-dict
        reply for old-style sequential readers (and the bench's
        stop-and-wait baseline)."""
        oid = body["oid"]
        legacy = body.get("pickle", False)
        if failpoints.ACTIVE:
            act = failpoints.check("raylet.serve_chunk",
                                   peer=self.node_id.hex()[:8])
            if act is not None:
                if act.kind == "error":
                    return {"error": "failpoint: injected serve error"}
                if act.kind == "delay":
                    await asyncio.sleep(act.delay_s)
                elif act.kind == "drop":
                    # A lost reply: stall past any sane chunk deadline
                    # so the puller times out / reroutes, exactly as if
                    # the frame had vanished on the wire.
                    await asyncio.sleep(act.delay_s or 60.0)
                    return {"error": "failpoint: chunk reply dropped"}
        got = self.store.get(oid)
        if got is None or not got[2]:
            spilled = self.spilled.get(oid)
            if spilled is not None:
                # Serve peer pulls straight from the spill file — no need
                # to churn the arena for a pass-through transfer.  One fd
                # per in-progress transfer, positional reads (pread), so
                # concurrent windowed chunks don't reopen the file or
                # race a shared seek offset.
                path, size = spilled
                start = body["offset"]
                n = min(body["len"], size - start)
                ent = self._spill_fd_acquire(oid, path)
                if ent is None:
                    return {"error": "spill file unavailable"}
                try:
                    data = await asyncio.get_running_loop().run_in_executor(
                        None, os.pread, ent[0], n, start)
                except OSError as e:
                    return {"error": f"spill read failed: {e}"}
                finally:
                    self._spill_fd_release(oid, ent,
                                           eof=start + n >= size)
                if legacy:
                    return {"data": data}
                return protocol.Blob({"len": len(data)}, data)
            return {"error": "not here"}
        offset, size, _ = got
        start = body["offset"]
        n = min(body["len"], size - start)
        if legacy:
            data = bytes(self.mapping.slice(offset + start, n))
            self.store.release(oid)
            return {"data": data}
        return protocol.Blob(
            {"len": n}, self.mapping.slice(offset + start, n),
            on_sent=lambda: self.store.release(oid))

    # One open fd serves every chunk of an in-progress spilled-object
    # transfer (the old path reopened the file PER CHUNK); closed when
    # the last chunk has been read out or by the stale sweep.
    def _spill_fd_acquire(self, oid: bytes, path: str):
        ent = self._spill_read_fds.get(oid)
        if ent is None:
            try:
                fd = os.open(path, os.O_RDONLY)
            except OSError:
                return None
            ent = self._spill_read_fds[oid] = \
                [fd, time.monotonic(), 0, False]
        ent[1] = time.monotonic()
        ent[2] += 1
        return ent

    def _spill_fd_release(self, oid: bytes, ent, eof: bool):
        ent[2] -= 1
        if eof:
            ent[3] = True
        if ent[3] and ent[2] <= 0 \
                and self._spill_read_fds.get(oid) is ent:
            self._close_spill_fd(oid)

    def _retire_spill_fd(self, oid: bytes):
        """Close the cached spill fd — unless executor-thread preads are
        still in flight, in which case mark it close-on-last-read:
        closing under a reader would let a reused fd number serve bytes
        of some unrelated file as chunk data."""
        ent = self._spill_read_fds.get(oid)
        if ent is not None and ent[2] > 0:
            ent[3] = True  # the final _spill_fd_release closes it
        else:
            self._close_spill_fd(oid)

    def _close_spill_fd(self, oid: bytes):
        ent = self._spill_read_fds.pop(oid, None)
        if ent is not None:
            try:
                os.close(ent[0])
            except OSError:
                pass

    def _track_pin(self, conn, oid: bytes):
        pins = self._client_pins.setdefault(id(conn), {})
        pins[oid] = pins.get(oid, 0) + 1

    def _release_client_pins(self, conn):
        """Client (worker/driver) went away: drop every pin it held so its
        objects become evictable again (reference: plasma releases a
        client's objects when its socket closes)."""
        pins = self._client_pins.pop(id(conn), None)
        if not pins:
            return
        for oid, count in pins.items():
            for _ in range(count):
                self.store.release(oid)

    async def rpc_os_release(self, conn, body):
        oid = body["oid"]
        pins = self._client_pins.get(id(conn))
        if pins and pins.get(oid):
            pins[oid] -= 1
            if pins[oid] <= 0:
                del pins[oid]
        self.store.release(oid)
        if self.pending_leases:
            # Freed pins may clear the store-pressure admission gate.
            self._kick_scheduler()
        return {"ok": True}

    async def rpc_os_delete(self, conn, body):
        oid = body["oid"]
        was_primary = self.primary_objects.pop(oid, None) is not None
        self.store.delete(oid)
        if was_primary:
            # Drop the creator pin (held since alloc so the primary copy
            # could never be LRU-evicted).  Without this the delete stays
            # deferred forever and a put/delete loop leaks the arena dry.
            self.store.release(oid)
        self._created_sizes.pop(oid, None)
        self._retire_spill_fd(oid)
        spilled = self.spilled.pop(oid, None)
        if spilled is not None:
            try:
                os.remove(spilled[0])
            except OSError:
                pass
        # Only objects actually in the directory need a removal report —
        # the common sub-stripe object was never added, and a push per
        # GC'd oid would tax the hot release path for nothing.
        if oid in self._reported_locs:
            self._reported_locs.discard(oid)
            self._report_locations([oid], added=False)
        return {"ok": True}

    async def rpc_os_contains(self, conn, body):
        return {"contains": self.store.contains(body["oid"])}

    # ---------------------------------------------------------- push path
    # Reference: the PushManager half of the object manager
    # (src/ray/object_manager/push_manager.h) — the owner side streams
    # chunks unsolicited so broadcast-shaped flows (weight sync, large
    # shared args) pre-position copies instead of N cold pulls.

    def _seal_release_notify(self, oid):
        """Seal a transferred-in copy, drop the creator pin, and wake
        seal waiters (shared by the pull, restore, and push receive
        paths).  The new sealed copy is reported to the GCS object
        directory so later pulls can stripe across it."""
        self.store.seal(oid)
        self.store.release(oid)
        for fut in self.seal_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(None)
        self._report_sealed(oid)

    async def rpc_os_push_to(self, conn, body):
        """Replicate a local sealed object to peer raylets (targets are
        node ids).  Transfers run concurrently — one slow peer doesn't
        serialize the broadcast."""
        oid = body["oid"]
        results = await asyncio.gather(
            *(self.transfers.push(oid, node_id)
              for node_id in body["targets"]))
        pushed, failed = [], []
        for node_id, ok in zip(body["targets"], results):
            (pushed if ok else failed).append(node_id.hex())
        return {"pushed": pushed, "failed": failed}

    def _sweep_stale_pushes(self, now):
        """Drop transfers with no chunk activity for more than
        cfg.push_stale_sweep_s (sender died mid-stream) so their
        unsealed allocations don't leak the arena, and close spill-read
        fds idle past the same threshold.  Staleness is measured from
        the LAST chunk, so a legitimately slow large push is never swept
        while it is still making progress.  Waiters are woken (they
        re-check the store and fall back to a pull or a timeout error
        instead of hanging out their full timeout)."""
        stale_s = cfg.push_stale_sweep_s
        for stale, ent in list(self._push_recv.items()):
            if now - ent["last"] > stale_s:
                conn = ent.get("conn")
                if conn is not None and conn._sink_reads:
                    # A chunk body is mid-read into this extent right
                    # now: not stale, and freeing it would corrupt the
                    # write.  Fresh grace period.
                    ent["last"] = now
                    continue
                self._push_recv.pop(stale, None)
                self._discard_unsealed(stale)
                for fut in self.seal_waiters.pop(stale, []):
                    if not fut.done():
                        fut.set_result(None)
        for oid, fent in list(self._spill_read_fds.items()):
            if fent[2] <= 0 and now - fent[1] > stale_s:
                self._close_spill_fd(oid)

    async def rpc_os_push_begin(self, conn, body):
        """Open one inbound push transfer: allocate the destination
        extent and register the transfer under the sender connection.
        Subsequent os_push chunk frames from that connection land
        straight in the allocation (see _blob_sink).  A concurrent push
        of the same oid from a second sender is answered {skip} rather
        than clobbering the live transfer (reference: PushManager dedups
        pushes per (object, node))."""
        oid, size = body["oid"], body["size"]
        now = time.monotonic()
        sender = id(conn)
        self._sweep_stale_pushes(now)
        ent = self._push_recv.get(oid)
        if ent is not None:
            if ent["sender"] != sender:
                # A live transfer from another sender owns this oid.
                return {"skip": True}
            # Same sender restarting its own stream: start clean.
            self._push_recv.pop(oid, None)
            self._discard_unsealed(oid)
        elif self.store.contains(oid) or oid in self._pulls_inflight:
            return {"skip": True}
        try:
            off = await self._alloc_with_spill(oid, size)
        except KeyError:
            return {"skip": True}  # concurrent pull/push won
        if off is None:
            return {"error": "object store OOM receiving push"}
        # Each transfer gets its own generation, echoed back in every
        # chunk header: a same-sender restart pops the old entry, but
        # its already-in-flight chunks must NOT count toward the new
        # transfer's "received" (they may duplicate offsets the new
        # stream will resend, sealing an object with unwritten holes).
        self._push_gen += 1
        gen = self._push_gen
        # "chunks" records the starting offset of every chunk already
        # counted: a duplicated frame (retry, network dup, chaos dup
        # action) must be idempotent, never double-counted — a byte
        # counter alone would seal the object early with holes.
        self._push_recv[oid] = {"off": off, "size": size, "sender": sender,
                                "gen": gen, "conn": conn, "last": now,
                                "received": 0, "chunks": set()}
        return {"ok": True, "gen": gen}

    def _blob_sink(self, conn, method, header, nbytes):
        """Blob-frame sink resolution (runs synchronously on the read
        loop BEFORE the payload is consumed): inbound os_push chunk
        bytes are written straight into the arena extent their transfer
        allocated in os_push_begin — no staging buffer, no pickle.
        Returns None (frame buffered normally) for anything that isn't
        a live, in-range chunk of a transfer owned by this sender."""
        if method != "os_push" or not isinstance(header, dict):
            return None
        ent = self._push_recv.get(header.get("oid"))
        if ent is None or ent["sender"] != id(conn) \
                or ent["gen"] != header.get("gen"):
            return None
        pos, n = header.get("offset", -1), header.get("len", -1)
        if n != nbytes or pos < 0 or pos + n > ent["size"]:
            return None
        return self.mapping.writable(ent["off"] + pos, n)

    async def rpc_os_push(self, conn, body):
        """Account one pushed chunk (its bytes were already routed into
        the arena by _blob_sink while the frame was being read); seal
        once every byte has arrived.  ``body`` is a protocol.BlobFrame —
        body.data is None on the fast path, or carries the raw bytes
        when the sink was declined (transfer swept/superseded between
        frames, or an out-of-range header)."""
        hdr = body.header
        oid = hdr["oid"]
        ent = self._push_recv.get(oid)
        if ent is None or ent["sender"] != id(conn) \
                or ent["gen"] != hdr.get("gen"):
            # Transfer swept as stale, superseded by a restart, or never
            # opened: these bytes were NOT kept.  An explicit error (not
            # a silent ok/skip) so the sender doesn't report a replica
            # on a node that discarded the data.
            return {"error": "push transfer not live"}
        ent["last"] = time.monotonic()
        if body.data is not None:
            # Declined sink with a live entry: validate and fall back to
            # an explicit copy into the extent.
            pos, n = hdr.get("offset", -1), hdr.get("len", -1)
            if n != len(body.data) or pos < 0 or pos + n > ent["size"]:
                return {"error": "push chunk out of range"}
            dest = self.mapping.writable(ent["off"], ent["size"])
            dest[pos:pos + n] = body.data
        if hdr["offset"] in ent["chunks"]:
            # Duplicate delivery of a chunk this transfer already
            # counted: the (re)write above was byte-identical, so just
            # ack without advancing "received".
            return {"ok": True, "duplicate": True}
        ent["chunks"].add(hdr["offset"])
        ent["received"] += hdr["len"]
        if ent["received"] >= ent["size"]:
            self._push_recv.pop(oid, None)
            self._seal_release_notify(oid)
        return {"ok": True}

    async def rpc_os_used(self, conn, body):
        return {"used": self.store.used(), "capacity": self.store_capacity}

    async def rpc_transfer_stats(self, conn, body):
        """Transfer-plane counters (pull/push volumes, striping,
        retries) for tests and observability."""
        return dict(self.transfers.stats)

    async def rpc_dump_trace(self, conn, body):
        """Pull-path trace dump for this node: the raylet's own span
        ring plus — with include_workers (default on) — every
        registered worker's ring, fanned out concurrently.  Returns
        {"processes": [per-process dump...]}; a worker that fails to
        answer contributes an {"error": ...} stub instead of failing
        the node dump."""
        body = body or {}
        stats_only = bool(body.get("stats_only"))
        clear = bool(body.get("clear"))
        procs = [dict(_tracing.dump(stats_only=stats_only, clear=clear),
                      role="raylet", node_id=self.node_id.hex())]
        if body.get("include_workers", True):
            targets = [w for w in list(self.workers.values())
                       if w.conn is not None and not w.conn.closed]

            async def _one(w):
                try:
                    d = await w.conn.request(
                        "dump_trace", {"stats_only": stats_only,
                                       "clear": clear}, timeout=10.0)
                    d["role"] = "worker"
                    d["worker_id"] = w.worker_id.hex()
                    return d
                except Exception as e:
                    return {"role": "worker", "pid": w.pid,
                            "worker_id": w.worker_id.hex(),
                            "error": f"{type(e).__name__}: {e}"}

            procs.extend(await asyncio.gather(*[_one(w)
                                                for w in targets]))
        return {"processes": procs, "node_id": self.node_id.hex()}

    # ------------------------------------------------------ state API feeds
    async def rpc_pool_stats(self, conn, body):
        """Worker-pool quiescence probe: spawned-but-unregistered workers
        are still paying interpreter startup (~2s of CPU each with jax in
        the image) — benchmarks and tests wait for zero before timing."""
        unregistered = sum(1 for w in self.workers.values()
                           if not w.registered.is_set())
        return {"workers": len(self.workers), "starting": unregistered,
                "leases": len(self.leases)}

    async def rpc_list_leases(self, conn, body):
        """Running + queued work on this node (reference: per-worker task
        state feeding python/ray/experimental/state/api.py list_tasks)."""
        running = []
        for lease in self.leases.values():
            running.append({
                "lease_id": lease.lease_id.hex(),
                "worker_id": lease.worker.worker_id.hex(),
                "pid": lease.worker.pid,
                "resources": lease.resources,
                "actor_id": (lease.worker.actor_id.hex()
                             if lease.worker.actor_id else None),
                "blocked": lease.blocked,
                "state": "RUNNING",
            })
        queued = [{"resources": p.get("resources", {}),
                   "state": "PENDING_NODE_ASSIGNMENT"}
                  for p in self.pending_leases]
        return {"running": running, "queued": queued,
                "node_id": self.node_id.hex()}

    async def rpc_list_local_objects(self, conn, body):
        objs = []
        for oid, size in self.primary_objects.items():
            objs.append({"object_id": oid.hex(), "size": size,
                         "where": "memory", "primary": True})
        for oid, (_path, size) in self.spilled.items():
            objs.append({"object_id": oid.hex(), "size": size,
                         "where": "spilled", "primary": True})
        return {"objects": objs, "node_id": self.node_id.hex(),
                "store_used": self.store.used(),
                "store_capacity": self.store_capacity}

    # ------------------------------------------------------------- lifecycle
    async def _heartbeat_loop(self):
        """Versioned-snapshot resource sync (reference: RaySyncer,
        common/ray_syncer/ray_syncer.h:88 — reporters version their
        snapshots; only versions the receiver hasn't acked travel).

        Every tick sends a liveness beat carrying just (node_id,
        version); the resource payload is attached only while the GCS's
        acked version lags the local one.  A restarted GCS acks 0, so
        the next beat automatically carries a full snapshot."""
        report_period = cfg.resource_report_period_ms / 1000.0
        beat_period = cfg.heartbeat_period_ms / 1000.0
        last_report = None
        last_beat = 0.0
        self._last_hw_report = 0.0
        self._sync_version = 0
        self._gcs_acked_version = -1
        last_sweep = 0.0
        while not self._shutdown:
            await asyncio.sleep(report_period)
            try:
                # Periodic transfer-plane sweep: a node that only SERVES
                # pulls never receives os_push_begin (the other sweep
                # trigger), so without this tick its aborted transfers'
                # cached spill-read fds and stale push extents would
                # leak until shutdown.
                tick = time.monotonic()
                if tick - last_sweep >= min(30.0, cfg.push_stale_sweep_s):
                    last_sweep = tick
                    self._sweep_stale_pushes(tick)
                report = (dict(self.available), self._load(),
                          [dict(p["resources"])
                           for p in self.pending_leases[:32]])
                if self._pending_gauge is not None:
                    self._pending_gauge.set(len(self.pending_leases))
                if report != last_report:
                    self._sync_version += 1
                    last_report = report
                need_payload = \
                    self._gcs_acked_version < self._sync_version
                now = time.monotonic()
                # Payload deltas ride the fast tick; liveness-only beats
                # ride the slow heartbeat period (an idle node costs one
                # tiny RPC per heartbeat_period_ms).
                if not need_payload and now - last_beat < beat_period:
                    continue
                last_beat = now
                body = {"node_id": self.node_id,
                        "version": self._sync_version}
                # Hardware report rides the slow beat (reference:
                # reporter_agent.py relaying psutil stats; here the
                # per-node raylet process samples directly).
                if now - self._last_hw_report >= beat_period:
                    self._last_hw_report = now
                    from ray_tpu._private.reporter import sample_node_stats
                    body["node_stats"] = sample_node_stats(
                        session_dir=self.session_dir, store=self.store,
                        store_capacity=self.store_capacity,
                        n_workers=len(self.workers))
                if need_payload:
                    body.update({
                        "available": report[0],
                        "load": report[1],
                        # Resource shapes of queued leases: the
                        # autoscaler's demand signal (reference:
                        # ResourceLoad feeding LoadMetrics).
                        "pending_shapes": report[2],
                    })
                if failpoints.ACTIVE:
                    act = failpoints.check("raylet.heartbeat",
                                           peer=self.node_id.hex()[:8])
                    if act is not None:
                        if act.kind == "drop":
                            continue  # this beat never leaves the node
                        if act.kind == "delay":
                            await asyncio.sleep(act.delay_s)
                        elif act.kind in ("error", "disconnect"):
                            raise protocol.ConnectionLost(
                                "failpoint: injected heartbeat "
                                f"{act.kind}")
                # Bounded wait: during a partition this request must
                # fail fast enough that the loop keeps beating through
                # the reconnect path instead of wedging on one RPC.
                reply = await self.gcs.request(
                    "heartbeat", body,
                    timeout=max(2.0, cfg.heartbeat_period_ms / 250.0))
                if reply.get("ok"):
                    self._gcs_acked_version = reply.get(
                        "acked_version", self._gcs_acked_version)
                elif "unknown node" in reply.get("reason", ""):
                    # GCS restarted and lost the node table: re-register
                    # (reference: NotifyGCSRestart node_manager.proto:343).
                    self._gcs_acked_version = -1
                    await self._reconnect_gcs()
            except Exception:
                if self._shutdown:
                    return
                self._gcs_acked_version = -1
                await self._reconnect_gcs()

    def _register_body(self):
        return {
            "node_id": self.node_id,
            "addr": (self.host, self.port),
            "resources": self.total_resources,
            "labels": self.labels,
            "node_name": self.node_name,
        }

    async def _reconnect_gcs(self):
        """Reconnect + re-register after a GCS restart/partition.  A
        raylet retries forever (it is useless without a control plane)
        but with full-jitter backoff, so a thousand raylets losing one
        GCS don't stampede its recovery in lockstep.  Bounded per-RPC
        timeouts keep a half-open link from wedging an attempt."""
        backoff = retry.ExpBackoff(cfg.gcs_reconnect_base_s,
                                   cfg.gcs_reconnect_cap_s)
        while not self._shutdown:
            try:
                conn = await protocol.Connection.connect(
                    self.gcs_addr[0], self.gcs_addr[1],
                    handler=self._handle_gcs_push,
                    name=f"raylet:{self.node_id.hex()[:8]}->gcs",
                    timeout=5.0)
                try:
                    # Events applied after this point are newer than
                    # the register reply's snapshot (the implicit
                    # subscription starts with registration) — the
                    # sync below must not override them.
                    cutoff = self._node_event_seq
                    reply = await conn.request("register_node",
                                               self._register_body(),
                                               timeout=10.0)
                    old, self.gcs = self.gcs, conn
                    if old is not None and not old.closed:
                        try:
                            await old.close()
                        except Exception:
                            pass
                    # Events missed while disconnected are gone, so
                    # cached nodes absent from the reply must stop
                    # being scheduling targets (soft prune: the reply
                    # may be INCOMPLETE after a non-persistent GCS
                    # restart, so live peer conns are not torn down —
                    # see _sync_node_views).
                    await self._sync_node_views(
                        reply.get("cluster_nodes", []),
                        hard_prune=False, cutoff=cutoff)
                    await self.gcs.request("subscribe",
                                           {"channels": ["nodes"]},
                                           timeout=10.0)
                except BaseException:
                    if self.gcs is not conn:
                        await conn.close()
                    raise
                logger.info("raylet %s re-registered with GCS",
                            self.node_id.hex()[:8])
                return
            except Exception:
                await asyncio.sleep(backoff.next())

    async def rpc_shutdown(self, conn, body):
        asyncio.get_running_loop().create_task(self.shutdown())
        return {"ok": True}

    async def rpc_ping(self, conn, body):
        return {"ok": True, "node_id": self.node_id}

    async def rpc_set_failpoints(self, conn, body):
        """Runtime fault-plane toggle: tests flip failpoints / partition
        rules on a live raylet mid-run (see failpoints.apply_rpc)."""
        return failpoints.apply_rpc(body)

    async def shutdown(self):
        self._shutdown = True
        # Announce planned exit BEFORE dropping the GCS connection, so
        # the control plane records an orderly drain instead of a node
        # death (which would log errors and churn actor restarts during
        # every clean shutdown).
        if self.gcs is not None:
            try:
                await self.gcs.request("node_draining",
                                       {"node_id": self.node_id},
                                       timeout=2.0)
            except Exception:
                pass  # GCS already gone: its disconnect path handles it
        for w in list(self.workers.values()):
            if w.proc is not None:
                try:
                    w.proc.kill()
                except Exception:
                    pass
        # A node that has shut down has let go of its chips: whoever
        # starts next on this host (a second init, the next phase of a
        # script) must be able to open them.
        for w in list(self.workers.values()):
            if w.kind == "tpu":
                await self._wait_exit(w, timeout=5.0)
        # Container workers: their kill() runs `rm -f` on a daemon
        # thread — wait for removal before the process exits, or the
        # engine-managed containers outlive the node.  One shared
        # deadline >= the thread's 2x10s retry budget, covering threads
        # whose worker was already popped from self.workers.
        deadline = time.monotonic() + 22.0
        for t in list(_ContainerProcHandle._live_kill_threads):
            t.join(max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                logger.warning(
                    "container removal %s still running at raylet "
                    "exit; the container may leak", t.name)
        if self._zygote is not None:
            self._zygote.kill()
            self._zygote = None
        await self.server.stop()
        if self.gcs is not None:
            await self.gcs.close()
        for oid in list(self._spill_read_fds):
            self._close_spill_fd(oid)
        self.transfers.close()
        self.mapping.close()
        self.store.close()


def main():
    import argparse
    import json
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs-host", required=True)
    parser.add_argument("--gcs-port", type=int, required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--session-dir", default="/tmp/ray_tpu")
    parser.add_argument("--store-capacity", type=int, default=0)
    parser.add_argument("--node-name", default=None)
    parser.add_argument("--prestart-workers", type=int, default=-1)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="[raylet] %(levelname)s %(message)s")
    resources = json.loads(args.resources)
    labels = json.loads(args.labels)
    if not resources:
        from ray_tpu._private.resources import detect_node_resources
        resources = detect_node_resources()

    async def run():
        raylet = Raylet((args.gcs_host, args.gcs_port), resources,
                        labels=labels, host=args.host,
                        session_dir=args.session_dir,
                        store_capacity=args.store_capacity or None,
                        node_name=args.node_name)
        port = await raylet.start(args.port)
        print(f"RAYLET_PORT={port}", flush=True)
        # Consumed by NodeProcesses so provider-launched nodes can be
        # matched to GCS node views (autoscaler idle drain).
        print(f"RAYLET_NODE_ID={raylet.node_id.hex()}", flush=True)
        n_warm = args.prestart_workers
        if n_warm < 0:
            n_warm = min(2, max(1, int(resources.get("CPU", 1))))
        if n_warm:
            raylet.prestart_workers(n_warm)
        # Graceful SIGTERM (rt stop): close the store so the RAM-backed
        # /dev/shm arena is unlinked instead of leaking until reboot.
        import signal as _signal
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        protocol.enable_eager_tasks(loop)
        loop.add_signal_handler(_signal.SIGTERM, stop.set)
        await stop.wait()
        await raylet.shutdown()

    asyncio.run(run())


if __name__ == "__main__":
    main()
