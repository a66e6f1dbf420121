"""GCS — Global Control Service: the cluster's control plane.

TPU-native re-design of the reference GCS server (reference:
src/ray/gcs/gcs_server/gcs_server.h:70 and its managers —
GcsNodeManager gcs_node_manager.h:36, GcsActorManager gcs_actor_manager.h:213
with the actor state machine documented at :181-232, GcsPlacementGroupManager
gcs_placement_group_manager.h:173 with 2-phase Prepare/Commit reservation,
GcsJobManager, InternalKV gcs_kv_manager.h:31, pubsub hub src/ray/pubsub/).

One asyncio process on the head node holding:
  * node table + heartbeat liveness + load aggregation
  * actor table + scheduling + restart state machine
  * placement groups with 2-phase bundle reservation (PACK/SPREAD/STRICT_*),
    including an ICI-topology-aware STRICT_PACK for TPU sub-meshes
  * internal KV (function/class exports, named actors, collective rendezvous)
  * long-poll-free pubsub: subscribers hold a persistent connection and
    receive pushes (the reference batches over long-polls; a persistent
    duplex conn gives the same O(#subscribers) property more simply)
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from collections import deque

from ray_tpu._private import protocol
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as cfg
from ray_tpu._private.ids import ActorID, NodeID, PlacementGroupID
from ray_tpu._private.placement import (choose_nodes_for_bundles,
                                        PlacementError)

logger = logging.getLogger(__name__)

# Actor lifecycle states (reference: gcs_actor_manager.h:181-232).
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"


class NodeInfo:
    def __init__(self, node_id, addr, resources, labels, conn):
        self.node_id: NodeID = node_id
        self.addr: tuple[str, int] = tuple(addr)
        self.total_resources: dict = dict(resources)
        self.available_resources: dict = dict(resources)
        self.labels: dict = dict(labels or {})
        self.conn: protocol.Connection = conn
        self.alive = True
        self.draining = False  # planned shutdown announced (drain RPC)
        self.drain_deadline = None  # monotonic expiry of the drain flag
        # Autopilot reservation: while set (to the beneficiary workload
        # id) the node drains its current leases instead of accepting
        # new low-priority ones — sched filters treat it like draining,
        # but GCS actor placement (serve replicas / train workers)
        # ignores it so the reclaim beneficiary can land there.
        self.reserved: str | None = None
        self.reserve_deadline = None
        self.last_heartbeat = time.monotonic()
        self.load = 0  # queued lease count reported by the raylet
        self.pending_shapes: list = []
        self.node_stats: dict = {}  # hardware report (cpu/mem/disk/store)
        # Versioned resource sync (reference: ray_syncer.h).
        self.sync_version = 0
        self.sync_beats = 0
        self.sync_payloads = 0

    def view(self):
        return {
            "node_id": self.node_id,
            "addr": self.addr,
            "resources": self.total_resources,
            "available": self.available_resources,
            "labels": self.labels,
            "alive": self.alive,
            "draining": self.draining,
            "reserved": self.reserved,
            "load": self.load,
            # Versioned-sync introspection (beats = all heartbeats,
            # payloads = beats that carried a resource snapshot).
            "sync_version": self.sync_version,
            "sync_beats": self.sync_beats,
            "sync_payloads": self.sync_payloads,
            "node_stats": self.node_stats,
        }


class ActorInfo:
    def __init__(self, actor_id, spec, owner_conn_id, job_id):
        self.actor_id: ActorID = actor_id
        self.spec = spec  # dict: class_key, init payload, resources, opts
        self.state = PENDING_CREATION
        self.node_id: NodeID | None = None
        self.addr: tuple[str, int] | None = None
        self.worker_id = None
        self.num_restarts = 0
        self.max_restarts = spec.get("max_restarts", 0)
        self.name = spec.get("name")
        self.namespace = spec.get("namespace", "default")
        self.detached = spec.get("detached", False)
        self.owner_conn_id = owner_conn_id
        self.job_id = job_id
        self.death_cause: str | None = None
        self.init_error_blob: bytes | None = None
        self.pg_id = spec.get("placement_group_id")

    def view(self):
        return {
            "actor_id": self.actor_id,
            "state": self.state,
            "addr": self.addr,
            "node_id": self.node_id,
            "name": self.name,
            "num_restarts": self.num_restarts,
            "max_restarts": self.max_restarts,
            "death_cause": self.death_cause,
            "init_error": self.init_error_blob,
            "class_name": self.spec.get("class_name"),
            "pid": self.spec.get("pid"),
        }


class PlacementGroupInfo:
    def __init__(self, pg_id, bundles, strategy, name, job_id):
        self.pg_id: PlacementGroupID = pg_id
        self.bundles: list[dict] = bundles
        self.strategy = strategy
        self.name = name
        self.job_id = job_id
        self.state = "PENDING"
        self.bundle_nodes: list[NodeID] = []
        # Bundle indices released back to their node by an elastic
        # shrink (release_bundles RPC); grow re-reserves them through
        # the same two-phase prepare/commit before spawning joiners.
        self.released_bundles: set[int] = set()

    def view(self):
        return {
            "pg_id": self.pg_id,
            "bundles": self.bundles,
            "strategy": self.strategy,
            "state": self.state,
            "bundle_nodes": self.bundle_nodes,
            "name": self.name,
        }


class _Subscriber:
    """Per-subscriber outbound pubsub state: a bounded FIFO of
    (channel, message) drained by one pump task.  The pump folds a
    backlog into batch frames, so a slow subscriber throttles only its
    own queue (and starts losing its OLDEST events past the bound)
    instead of head-of-line-blocking every other subscriber's
    broadcast."""

    __slots__ = ("conn", "queue", "wake", "task", "dropped", "gapped")

    def __init__(self, conn):
        self.conn = conn
        self.queue = deque()
        self.wake = asyncio.Event()
        self.task = None
        self.dropped = 0
        # Channels whose events this subscriber LOST to the queue
        # bound; the pump follows up with a pubsub_gap notification so
        # the consumer can re-seed authoritatively instead of running
        # on a silently-holed view forever.
        self.gapped: set = set()


class GcsServer:
    def __init__(self, host="127.0.0.1", persist_path: str | None = None):
        self.host = host
        self.server = protocol.RpcServer(self._handle, host=host, name="gcs",
                                         on_disconnect=self._on_disconnect)
        self.nodes: dict[NodeID, NodeInfo] = {}
        self.actors: dict[ActorID, ActorInfo] = {}
        self.named_actors: dict[tuple[str, str], ActorID] = {}
        self.placement_groups: dict[PlacementGroupID, PlacementGroupInfo] = {}
        self.kv: dict[str, dict[bytes, bytes]] = {}
        # Object directory: oid -> node ids reporting a sealed copy
        # (reference: gcs object location table backing the pull
        # manager's source selection).  Fed by best-effort raylet
        # reports; consumers stat-verify, so staleness is tolerated.
        self.object_locations: dict[bytes, set] = {}
        self._dir_writes = 0  # object-directory mutation counter
        self.subscribers: dict[str, set[protocol.Connection]] = {}
        # Coalesced pubsub (id(conn) -> _Subscriber) + broadcast stats.
        self._subs: dict[int, _Subscriber] = {}
        self.pubsub_stats = {"published": 0, "sent_msgs": 0,
                             "sent_frames": 0, "batches": 0,
                             "batched_msgs": 0, "max_batch": 0,
                             "dropped": 0, "evicted": 0}
        # Incremental cluster-resource aggregation: totals/availability
        # maintained from registration + heartbeat deltas so
        # cluster_resources / autoscaler demand polls don't rescan every
        # node view (the per-heartbeat-period full-rescan hot spot).
        self._agg_total: dict = {}
        self._agg_avail: dict = {}
        self._demand_nodes: set = set()  # nodes with queued lease shapes
        self.jobs: dict = {}
        self._pending_actor_creations: dict[ActorID, asyncio.Task] = {}
        self._actor_waiters: dict[ActorID, list[asyncio.Future]] = {}
        self._node_waiters: list[asyncio.Future] = []
        self._probing: set = set()  # node ids with a death probe in flight
        self._drivers: dict[int, dict] = {}  # conn-id -> {job_id}
        self._start_time = time.time()
        # Persistence (reference: gcs/store_client/redis_store_client.h:28 —
        # table storage that survives GCS restart; pluggable backends per
        # gcs/store_client — persist_path accepts a URI: plain/file://
        # (atomic-rename snapshot), sqlite:// (transactional versioned,
        # point at a shared mount for cross-machine failover), or a
        # registered external scheme).
        self._persist_path = persist_path
        self._store_client = None
        if persist_path:
            from ray_tpu._private.gcs_storage import get_store_client
            self._store_client = get_store_client(persist_path)
        self._kv_writes = 0
        # Structured cluster events (reference: src/ray/util/event.h:102
        # EventManager + dashboard/modules/event): bounded ring
        # (RT_GCS_EVENTS_MAX) with an explicit drop count, surfaced via
        # the state API and dashboard.
        self.events = deque(maxlen=max(1, cfg.gcs_events_max))
        self.events_dropped = 0
        self._events_seq = 0
        # Snapshot bookkeeping (age/size exported as metrics).
        self.restored_from_snapshot = False
        self._last_snapshot_ts = None
        self._last_snapshot_bytes = 0
        self._snapshot_count = 0
        self._metrics = None
        # Cluster autopilot: the SLO-driven resource broker.  Policy
        # state is deliberately NOT persisted (see _snapshot_state) —
        # a restarted GCS starts with zero grants and rebuilds the
        # table from client reports within one report period, which is
        # what makes "no stale grants after snapshot restore" hold by
        # construction.
        from ray_tpu._private.arbiter import ArbiterPolicy
        self.arbiter = ArbiterPolicy()
        # Gang elasticity registry (wid -> bool) fed by train-gang
        # reports, so rt resize can answer NOT_ELASTIC structurally.
        self._gang_elastic: dict[str, bool] = {}
        self._arbiter_last_counts = {"grants": 0, "revocations": 0,
                                     "breach_s": 0.0}
        if persist_path:
            self._load_snapshot()

    async def start(self, port=0):
        port = await self.server.start(port)
        self._bg_tasks = [
            asyncio.get_running_loop().create_task(self._liveness_loop()),
            asyncio.get_running_loop().create_task(self._arbiter_loop())]
        if self._persist_path:
            self._bg_tasks.append(
                asyncio.get_running_loop().create_task(
                    self._snapshot_loop()))
        logger.info("GCS listening on %s:%s", self.host, port)
        return port

    async def stop(self):
        for t in getattr(self, "_bg_tasks", []):
            t.cancel()
        for sub in list(self._subs.values()):
            if sub.task is not None:
                sub.task.cancel()
        self._subs.clear()
        await self.server.stop()

    # ----------------------------------------------------------- persistence
    # KV namespaces that are ephemeral push-streams, not recovery state —
    # excluded from snapshots (they would dominate the write cost).
    _EPHEMERAL_KV_NS = ("telemetry",)

    def _snapshot_state(self) -> dict:
        """Copy the durable tables.  MUST run on the event-loop thread
        (concurrent RPCs mutate these dicts); the pickle+write then happens
        off-loop on the copies."""
        return {
            "kv": {ns: dict(d) for ns, d in self.kv.items()
                   if ns not in self._EPHEMERAL_KV_NS},
            "named_actors": dict(self.named_actors),
            "jobs": dict(self.jobs),
            # Node table: a restarted GCS seeds these as alive-pending-
            # re-register entries with a fresh heartbeat grace window,
            # so mid-restart churn never produces a false NODE_DEAD and
            # actors keep their placements while raylets reconnect.
            "nodes": [
                {"node_id": n.node_id, "addr": n.addr,
                 "resources": dict(n.total_resources),
                 "available": dict(n.available_resources),
                 "labels": dict(n.labels), "load": n.load,
                 "draining": n.draining}
                for n in self.nodes.values() if n.alive
            ],
            # Object directory (stripe-size objects only, so compact):
            # restored entries are stat-verified by consumers, making
            # staleness harmless.
            "object_locations": {oid: list(locs) for oid, locs
                                 in self.object_locations.items()},
            # Event-log tail: recent history survives the restart
            # instead of being replayed from scratch (or lost).
            "events": (list(self.events)[-cfg.gcs_snapshot_events_tail:]
                       if cfg.gcs_snapshot_events_tail > 0 else []),
            "events_dropped": self.events_dropped,
            "actors": [
                {"actor_id": a.actor_id, "spec": dict(a.spec),
                 "state": a.state, "addr": a.addr, "node_id": a.node_id,
                 "worker_id": a.worker_id, "num_restarts": a.num_restarts,
                 "death_cause": a.death_cause, "job_id": a.job_id}
                for a in self.actors.values()
            ],
            "placement_groups": [
                {"pg_id": p.pg_id, "bundles": list(p.bundles),
                 "strategy": p.strategy, "name": p.name,
                 "job_id": p.job_id, "state": p.state,
                 "bundle_nodes": list(p.bundle_nodes),
                 "released_bundles": list(p.released_bundles)}
                for p in self.placement_groups.values()
            ],
            # Autopilot broker state (declarations, grants, breach
            # timers) is INTENTIONALLY absent: grants are leases over
            # live capacity, and resurrecting them from a snapshot
            # could hand out budget against nodes/workloads that died
            # with the old GCS.  Clients re-report within one
            # autopilot_report_period_s, rebuilding the table from
            # scratch — a restart can only under-grant, never leak.
        }

    def _write_snapshot(self, state: dict):
        import pickle
        blob = pickle.dumps(state)
        self._store_client.write(blob)
        self._last_snapshot_ts = time.monotonic()
        self._last_snapshot_bytes = len(blob)
        self._snapshot_count += 1

    def _load_snapshot(self):
        import pickle
        try:
            blob = self._store_client.read()
            if blob is None:
                return
            snap = pickle.loads(blob)
        except Exception as e:
            logger.warning("GCS snapshot load failed: %s", e)
            return
        self.kv = snap.get("kv", {})
        self.named_actors = dict(snap.get("named_actors", {}))
        self.jobs = dict(snap.get("jobs", {}))
        for nv in snap.get("nodes", []):
            # Restored as alive with conn=None ("recovering"): the
            # raylet's reconnect loop re-registers within a heartbeat,
            # and until then the fresh last_heartbeat grants the full
            # grace window — a restart mid-churn must not flip healthy
            # nodes to NODE_DEAD (nor orphan the actors placed there).
            info = NodeInfo(nv["node_id"], nv["addr"], nv["resources"],
                            nv.get("labels"), None)
            info.available_resources = dict(
                nv.get("available", nv["resources"]))
            info.load = nv.get("load", 0)
            info.draining = bool(nv.get("draining", False))
            if info.draining:
                # Restored drain flags get a fresh bounded window — a
                # deadline-less flag would never expire (and so never
                # un-exclude a node that lingers instead of exiting).
                info.drain_deadline = time.monotonic() + \
                    cfg.heartbeat_timeout_ms / 1000.0 * 2
            self.nodes[info.node_id] = info
            self._agg_add(self._agg_total, info.total_resources)
            self._agg_add(self._agg_avail, info.available_resources)
        for oid, locs in snap.get("object_locations", {}).items():
            self.object_locations[oid] = set(locs)
        for ev in snap.get("events", []):
            self.events.append(ev)
        self.events_dropped = snap.get("events_dropped", 0)
        for a in snap.get("actors", []):
            info = ActorInfo(a["actor_id"], a["spec"], None, a["job_id"])
            info.state = a["state"]
            info.addr = a["addr"]
            info.node_id = a["node_id"]
            info.worker_id = a["worker_id"]
            info.num_restarts = a["num_restarts"]
            info.death_cause = a["death_cause"]
            self.actors[info.actor_id] = info
        for p in snap.get("placement_groups", []):
            info = PlacementGroupInfo(p["pg_id"], p["bundles"],
                                      p["strategy"], p["name"], p["job_id"])
            info.state = p["state"]
            info.bundle_nodes = p["bundle_nodes"]
            info.released_bundles = set(p.get("released_bundles", ()))
            self.placement_groups[info.pg_id] = info
        self.restored_from_snapshot = True
        self._record_event(
            "INFO", "GCS_RESTORED",
            f"restored {len(self.nodes)} nodes / {len(self.actors)} "
            f"actors / {len(self.placement_groups)} PGs / "
            f"{len(self.kv)} kv namespaces from snapshot")
        logger.info("GCS restored %d nodes / %d actors / %d PGs / %d kv "
                    "namespaces from %s", len(self.nodes),
                    len(self.actors), len(self.placement_groups),
                    len(self.kv), self._persist_path)

    def _state_fingerprint(self):
        """Cheap change detector so the snapshot loop writes only when
        durable state moved — KV can hold 100MB runtime_env packages, and
        re-pickling them twice a second would be sustained disk churn."""
        kv_sizes = (self._kv_writes,) + tuple(sorted(
            (ns, len(d)) for ns, d in self.kv.items()
            if ns not in self._EPHEMERAL_KV_NS))
        actors = tuple(sorted(
            (a.actor_id.binary(), a.state, a.num_restarts)
            for a in self.actors.values()))
        pgs = tuple(sorted((p.pg_id.binary(), p.state)
                           for p in self.placement_groups.values()))
        jobs = tuple(sorted((bytes(k) if isinstance(k, bytes) else str(k),
                             str(v.get("state")))
                            for k, v in self.jobs.items()))
        nodes = tuple(sorted(
            (n.node_id.binary(), n.draining) for n in self.nodes.values()
            if n.alive))
        return hash((kv_sizes, actors, pgs, jobs, nodes,
                     self._dir_writes, self._events_seq,
                     len(self.named_actors)))

    async def _snapshot_loop(self):
        loop = asyncio.get_running_loop()
        last_fp = None
        while True:
            await asyncio.sleep(max(0.05, cfg.gcs_snapshot_period_s))
            try:
                fp = self._state_fingerprint()
                if fp == last_fp:
                    continue
                state = self._snapshot_state()  # copy on the loop thread
                await loop.run_in_executor(None, self._write_snapshot,
                                           state)
                last_fp = fp
            except Exception as e:
                logger.warning("GCS snapshot write failed: %s", e)

    # ------------------------------------------------------------------ rpc
    async def _handle(self, conn, method, body):
        fn = getattr(self, "rpc_" + method, None)
        if fn is None:
            raise protocol.RpcError(f"GCS: no method {method}")
        return await fn(conn, body)

    async def _on_disconnect(self, conn):
        # A raylet died, or a driver exited.
        self._evict_subscriber(conn)
        for node in list(self.nodes.values()):
            if node.conn is conn and node.alive:
                if self._drain_active(node):
                    # Planned shutdown (drain RPC preceded the close):
                    # not a failure — don't page operators with a
                    # NODE_DEAD error for an orderly exit.
                    await self._mark_node_dead(
                        node, "drained (planned shutdown)", planned=True)
                else:
                    # An UNANNOUNCED connection loss is not proof of
                    # death: the raylet may have failed a suspect
                    # half-open link on purpose (keepalive) or be
                    # partitioned from us while healthy.  Probe its
                    # server: refusal proves the process is gone; an
                    # unreachable node keeps the heartbeat-timeout
                    # grace window (_liveness_loop is the backstop).
                    asyncio.get_running_loop().create_task(
                        self._probe_suspect_node(node))
        drv = self._drivers.pop(id(conn), None)
        if drv is not None:
            await self._cleanup_job(drv["job_id"])

    async def _probe_suspect_node(self, node: NodeInfo):
        if node.node_id in self._probing or not node.alive:
            return
        self._probing.add(node.node_id)
        tag = node.node_id.hex()[:8]
        try:
            probe = await protocol.Connection.connect(
                node.addr[0], node.addr[1],
                name=f"gcs->raylet:{tag}",
                timeout=cfg.node_probe_timeout_s)
            try:
                await probe.request("ping", {},
                                    timeout=cfg.node_probe_timeout_s)
            finally:
                try:
                    await probe.close()
                except Exception:
                    pass
            logger.info(
                "node %s dropped its GCS connection but answers pings; "
                "keeping it alive pending re-register", tag)
        except (ConnectionRefusedError, ConnectionResetError) as e:
            # Nothing is listening on the raylet's port: the process is
            # gone — declare death NOW (reconstruction, actor restarts
            # and directory pruning must not wait a full grace window).
            if node.alive:
                await self._mark_node_dead(
                    node, f"raylet connection lost (probe: "
                          f"{type(e).__name__})")
        except Exception as e:
            # Unreachable (timeout / partition / injected fault): NOT
            # proof of death.  The node stays alive until its heartbeat
            # grace window expires or it re-registers.
            logger.info(
                "node %s unreachable after connection loss (%s); "
                "liveness grace window decides", tag, e)
        finally:
            self._probing.discard(node.node_id)

    # ---------------------------------------------------------------- nodes
    async def rpc_node_draining(self, conn, body):
        """A raylet announces its own PLANNED shutdown — the subsequent
        connection close is then an orderly removal, not a death.
        (Distinct from rpc_drain_node below, the autoscaler-initiated
        COMMAND telling a raylet to exit.)  Only the node's OWN
        connection may announce its drain (a misdirected announcement
        would permanently downgrade a later genuine crash to an orderly
        drain), and the flag expires: a node that announces draining
        but then lingers past the grace window is again reported as an
        unplanned death if it crashes."""
        node_id = body["node_id"]
        node = self.nodes.get(node_id)
        ok = node is not None and node.conn is conn
        if ok:
            node.draining = True
            node.drain_deadline = time.monotonic() + \
                cfg.heartbeat_timeout_ms / 1000.0 * 2
            # Tell the schedulers: spillback/spread targets must stop
            # selecting a node that announced its exit.
            await self._publish("nodes", {
                "event": "updated", "node_id": node.node_id,
                "draining": True})
        return {"ok": ok}

    @staticmethod
    def _drain_active(node) -> bool:
        return node.draining and (
            node.drain_deadline is None
            or time.monotonic() < node.drain_deadline)

    @staticmethod
    def _agg_add(agg: dict, d: dict):
        for k, v in d.items():
            agg[k] = agg.get(k, 0) + v

    @staticmethod
    def _agg_sub(agg: dict, d: dict):
        for k, v in d.items():
            left = agg.get(k, 0) - v
            if -1e-9 < left < 1e-9:
                agg.pop(k, None)
            else:
                agg[k] = left

    def _agg_drop_node(self, node: "NodeInfo"):
        """Remove a node's contribution from the incremental cluster
        aggregates (death / re-registration replacing a live entry)."""
        self._agg_sub(self._agg_total, node.total_resources)
        self._agg_sub(self._agg_avail, node.available_resources)
        self._demand_nodes.discard(node.node_id)

    async def rpc_register_node(self, conn, body):
        node_id = body["node_id"]
        prev = self.nodes.get(node_id)
        if prev is not None and prev.alive:
            # Re-registration (GCS restart / reconnect): replace the
            # entry's aggregate contribution instead of double-counting.
            self._agg_drop_node(prev)
        info = NodeInfo(node_id, body["addr"], body["resources"],
                        body.get("labels"), conn)
        self.nodes[node_id] = info
        self._agg_add(self._agg_total, info.total_resources)
        self._agg_add(self._agg_avail, info.available_resources)
        # Implicit "nodes" subscription BEFORE the reply snapshot is
        # built: a node registering between this reply and an explicit
        # subscribe RPC would otherwise be missed forever (the reply
        # and the event stream must be atomic for the event-fed
        # scheduling views).  The raylet's explicit subscribe stays
        # idempotent.
        if cfg.gcs_pubsub_coalesce:
            self._ensure_subscriber(conn)
        self.subscribers.setdefault("nodes", set()).add(conn)
        await self._publish("nodes", {"event": "added", "node": info.view()})
        for fut in self._node_waiters:
            if not fut.done():
                fut.set_result(None)
        self._node_waiters.clear()
        # Seed view: ALIVE nodes only — a dead node will never emit the
        # "removed" event that would prune it from the joiner's
        # scheduling view, so it must not be handed out in the first
        # place.
        return {"ok": True, "cluster_nodes": [
            n.view() for n in self.nodes.values() if n.alive]}

    async def rpc_heartbeat(self, conn, body):
        """Liveness + versioned resource sync: payload-free beats just
        refresh liveness; beats carrying a payload advance the node's
        acked sync version (reference: ray_syncer.h versioned
        snapshots)."""
        node = self.nodes.get(body["node_id"])
        if node is None:
            return {"ok": False, "reason": "unknown node (gcs restarted?)"}
        if not node.alive:
            # Late heartbeat from a node we already declared dead (or a
            # zombie that outlived its timeout): it must NOT leak into
            # the demand set or re-advertise the node to schedulers —
            # tell it to re-register instead ("unknown node" is the
            # phrase the raylet's re-register path matches on).
            return {"ok": False,
                    "reason": "unknown node (marked dead; re-register)"}
        node.last_heartbeat = time.monotonic()
        if "available" in body:
            avail = body["available"]
            load = body.get("load", node.load)
            changed = (avail != node.available_resources
                       or load != node.load)
            # Incremental aggregate maintenance: swap this node's
            # availability contribution in place of a full rescan (the
            # node is alive — dead nodes were bounced above).
            self._agg_sub(self._agg_avail, node.available_resources)
            self._agg_add(self._agg_avail, avail)
            node.available_resources = avail
            node.load = load
            node.pending_shapes = body.get("pending_shapes", [])
            if node.pending_shapes:
                self._demand_nodes.add(node.node_id)
            else:
                self._demand_nodes.discard(node.node_id)
            node.sync_version = body.get("version", 0)
            node.sync_payloads += 1
            if changed and cfg.gcs_publish_resource_updates:
                # Delta broadcast keeping raylet-side scheduling views
                # (spillback/spread/hybrid indexes) fresh; coalesced
                # pubsub folds these into batch frames.
                await self._publish("nodes", {
                    "event": "updated", "node_id": node.node_id,
                    "available": avail, "load": load})
        if "node_stats" in body:
            # Hardware utilization relayed by the node's reporter
            # (reference: reporter_agent stats feeding the dashboard).
            node.node_stats = body["node_stats"]
        node.sync_beats += 1
        return {"ok": True, "acked_version": node.sync_version}

    async def rpc_get_resource_demands(self, conn, body):
        """Aggregate demand for the autoscaler: queued lease shapes from
        every raylet + unplaced placement-group bundles (reference:
        LoadMetrics + pending PG demand in autoscaler.py:346)."""
        shapes = []
        # Only nodes that reported queued shapes are visited (the
        # _demand_nodes set is maintained from heartbeat deltas) — the
        # autoscaler poll no longer rescans every node view.
        for nid in self._demand_nodes:
            n = self.nodes.get(nid)
            if n is not None and n.alive:
                shapes.extend(n.pending_shapes)
        pending_pgs = []
        for pg in self.placement_groups.values():
            if pg.state in ("PENDING", "INFEASIBLE", "RESCHEDULING"):
                pending_pgs.append({"pg_id": pg.pg_id,
                                    "bundles": pg.bundles,
                                    "strategy": pg.strategy})
        return {"shapes": shapes, "pending_pgs": pending_pgs}

    async def rpc_get_nodes(self, conn, body):
        return [n.view() for n in self.nodes.values()]

    async def rpc_wait_for_nodes(self, conn, body):
        count = body["count"]
        timeout = body.get("timeout", 60.0)
        deadline = time.monotonic() + timeout
        while len([n for n in self.nodes.values() if n.alive]) < count:
            fut = asyncio.get_running_loop().create_future()
            self._node_waiters.append(fut)
            try:
                await asyncio.wait_for(fut, max(0.01, deadline - time.monotonic()))
            except asyncio.TimeoutError:
                return {"ok": False}
        return {"ok": True}

    async def rpc_drain_node(self, conn, body):
        node = self.nodes.get(body["node_id"])
        if node is None or not node.alive:
            return {"ok": False}
        node.draining = True
        try:
            await node.conn.request("shutdown", {})
        except Exception:
            pass
        # Autoscaler downscale is intentional — an orderly drain, not a
        # node death (no ERROR event, no operator page).
        await self._mark_node_dead(node, "drained", planned=True)
        return {"ok": True}

    async def _liveness_loop(self):
        period = cfg.heartbeat_period_ms / 1000.0
        timeout = cfg.heartbeat_timeout_ms / 1000.0
        while True:
            await asyncio.sleep(period)
            try:
                self._update_metrics()
            except Exception:
                pass
            now = time.monotonic()
            for node in list(self.nodes.values()):
                if node.alive and node.draining \
                        and not self._drain_active(node):
                    # The announced drain expired but the node lingers
                    # alive: clear the flag AND broadcast it — the
                    # draining=True update permanently excluded the
                    # node from every raylet's spillback/spread/hybrid
                    # targeting, and nothing else would ever publish
                    # the reversal.
                    node.draining = False
                    node.drain_deadline = None
                    await self._publish("nodes", {
                        "event": "updated", "node_id": node.node_id,
                        "draining": False})
                if node.alive and node.reserved is not None \
                        and (node.reserve_deadline is None
                             or now >= node.reserve_deadline):
                    # Same shape as the drain-expiry reversal above: a
                    # reservation permanently excluded the node from
                    # lease scheduling, so its expiry must be broadcast
                    # or the node stays fenced forever.
                    node.reserved = None
                    node.reserve_deadline = None
                    await self._publish("nodes", {
                        "event": "updated", "node_id": node.node_id,
                        "reserved": None})
                if node.alive and now - node.last_heartbeat > timeout:
                    # A node that announced its drain and then stalled
                    # during teardown is still an orderly exit, not a
                    # failure to page on — unless the drain window
                    # expired (then it's a genuine wedge/crash).
                    if self._drain_active(node):
                        await self._mark_node_dead(
                            node, "drain timed out (heartbeat lost "
                            "while draining)", planned=True)
                    else:
                        await self._mark_node_dead(node,
                                                   "heartbeat timeout")

    def _record_event(self, severity: str, label: str, message: str,
                      source: str = "gcs"):
        if self.events.maxlen and len(self.events) >= self.events.maxlen:
            # The ring is about to shed its oldest entry: count it so
            # operators can see history was lost (and how much).
            self.events_dropped += 1
        self._events_seq += 1
        self.events.append({"ts": time.time(), "severity": severity,
                            "label": label, "message": message,
                            "source": source})

    async def rpc_list_events(self, conn, body):
        limit = body.get("limit", 200)
        events = list(self.events)[-limit:]
        if body.get("with_stats"):
            return {"events": events, "dropped": self.events_dropped,
                    "cap": self.events.maxlen}
        return events

    async def rpc_record_event(self, conn, body):
        self._record_event(body.get("severity", "INFO"),
                           body.get("label", ""),
                           body.get("message", ""),
                           body.get("source", "client"))
        return {"ok": True}

    async def rpc_set_failpoints(self, conn, body):
        """Runtime fault-plane toggle: tests flip failpoints / partition
        rules on a live GCS mid-run (see failpoints.apply_rpc)."""
        from ray_tpu._private import failpoints
        return failpoints.apply_rpc(body)

    async def _mark_node_dead(self, node: NodeInfo, reason: str,
                              planned: bool = False):
        if not node.alive:
            return
        node.alive = False
        self._agg_drop_node(node)
        if planned:
            logger.info("node %s removed: %s", node.node_id.hex()[:8],
                        reason)
            self._record_event("INFO", "NODE_DRAINED",
                               f"node {node.node_id.hex()[:8]}: {reason}")
        else:
            logger.warning("node %s dead: %s", node.node_id.hex()[:8],
                           reason)
            self._record_event("ERROR", "NODE_DEAD",
                               f"node {node.node_id.hex()[:8]}: {reason}")
        await self._publish("nodes", {"event": "removed",
                                      "node_id": node.node_id,
                                      "reason": reason})
        # Restart or fail actors that lived there.
        for actor in list(self.actors.values()):
            if actor.node_id == node.node_id and actor.state in (ALIVE,
                                                                 PENDING_CREATION,
                                                                 RESTARTING):
                await self._on_actor_interrupted(actor,
                                                 f"node died: {reason}")
        # Invalidate placement groups with bundles there (reschedule).
        for pg in self.placement_groups.values():
            if node.node_id in pg.bundle_nodes and pg.state == "CREATED":
                pg.state = "RESCHEDULING"
                asyncio.get_running_loop().create_task(self._schedule_pg(pg))
        # Drop the dead node from the object directory so striped pulls
        # stop selecting it as a source.
        held = [o for o, locs in self.object_locations.items()
                if node.node_id in locs]
        for oid in held:
            locs = self.object_locations[oid]
            locs.discard(node.node_id)
            if not locs:
                del self.object_locations[oid]
        if held:
            self._dir_writes += 1

    # ----------------------------------------------------- object directory
    async def rpc_object_locations_added(self, conn, body):
        node_id = body["node_id"]
        for oid in body["oids"]:
            locs = self.object_locations.setdefault(oid, set())
            if node_id not in locs:
                locs.add(node_id)
                # Only real mutations dirty the snapshot fingerprint:
                # raylet location reports are idempotent best-effort,
                # and a no-op report must not trigger a full-state
                # snapshot rewrite every period.
                self._dir_writes += 1
        return {"ok": True}

    async def rpc_object_locations_removed(self, conn, body):
        node_id = body["node_id"]
        for oid in body["oids"]:
            locs = self.object_locations.get(oid)
            if locs is not None and node_id in locs:
                locs.discard(node_id)
                if not locs:
                    self.object_locations.pop(oid, None)
                self._dir_writes += 1
        return {"ok": True}

    async def rpc_get_object_locations(self, conn, body):
        """Alive nodes believed to hold a sealed copy of oid (striped
        pulls fan chunk ranges across these)."""
        locs = self.object_locations.get(body["oid"], ())
        alive = []
        for nid in locs:
            info = self.nodes.get(nid)
            if info is not None and info.alive:
                alive.append(nid)
        return {"locations": alive}

    # ------------------------------------------------------------------- kv
    async def rpc_kv_put(self, conn, body):
        ns_name = body.get("ns", "")
        ns = self.kv.setdefault(ns_name, {})
        overwrite = body.get("overwrite", True)
        if not overwrite and body["key"] in ns:
            return {"ok": False, "exists": True}
        ns[body["key"]] = body["value"]
        if ns_name not in self._EPHEMERAL_KV_NS:
            # In-place overwrites don't change namespace sizes, so the
            # snapshot fingerprint needs an explicit write counter.
            self._kv_writes += 1
        return {"ok": True}

    async def rpc_kv_get(self, conn, body):
        ns = self.kv.get(body.get("ns", ""), {})
        return {"value": ns.get(body["key"])}

    async def rpc_kv_del(self, conn, body):
        ns_name = body.get("ns", "")
        ns = self.kv.get(ns_name, {})
        existed = ns.pop(body["key"], None) is not None
        if existed and ns_name not in self._EPHEMERAL_KV_NS:
            self._kv_writes += 1
        return {"ok": existed}

    async def rpc_kv_keys(self, conn, body):
        ns = self.kv.get(body.get("ns", ""), {})
        prefix = body.get("prefix", b"")
        return {"keys": [k for k in ns if k.startswith(prefix)]}

    # --------------------------------------------------------------- pubsub
    # Coalesced broadcast (reference: src/ray/pubsub/publisher.h — the
    # publisher buffers per-subscriber mailboxes and ships batches; here
    # the mailbox is a bounded deque drained by a per-subscriber pump).
    # _publish is O(#subscribers) dict appends; the pumps fold bursts
    # into KIND_BATCH frames (one write per drain) and same-channel runs
    # into ONE pubsub_batch message, so an actor-event storm costs
    # O(events) instead of O(events x subscribers) serialized awaits.

    async def rpc_subscribe(self, conn, body):
        if cfg.gcs_pubsub_coalesce:
            self._ensure_subscriber(conn)
        for channel in body["channels"]:
            self.subscribers.setdefault(channel, set()).add(conn)
        return {"ok": True}

    async def rpc_publish(self, conn, body):
        await self._publish(body["channel"], body["message"])
        return {"ok": True}

    def _ensure_subscriber(self, conn) -> _Subscriber:
        sub = self._subs.get(id(conn))
        if sub is None:
            sub = _Subscriber(conn)
            self._subs[id(conn)] = sub
            sub.task = asyncio.get_running_loop().create_task(
                self._sub_pump(sub))
        return sub

    def _evict_subscriber(self, conn):
        sub = self._subs.pop(id(conn), None)
        for members in self.subscribers.values():
            members.discard(conn)
        if sub is not None:
            self.pubsub_stats["evicted"] += 1
            if sub.task is not None \
                    and sub.task is not asyncio.current_task():
                sub.task.cancel()

    async def _publish(self, channel: str, message):
        subs = self.subscribers.get(channel)
        if not subs:
            return
        self.pubsub_stats["published"] += 1
        if not cfg.gcs_pubsub_coalesce:
            await self._publish_legacy(channel, subs, message)
            return
        qmax = cfg.gcs_pubsub_queue_max
        # ONE shared cell per event: every subscriber queue holds the
        # same [channel, message, blob] list, so when a pump needs the
        # pickled form for a batch frame it serializes ONCE and every
        # other pump reuses it — fan-out serialization is O(events),
        # not O(events x subscribers).
        cell = [channel, message, None]
        dead = None
        for conn in subs:
            if conn.closed:
                dead = dead or []
                dead.append(conn)
                continue
            sub = self._ensure_subscriber(conn)
            if len(sub.queue) >= qmax:
                # Slow-subscriber bound: shed the OLDEST queued event
                # and remember its channel — the pump tells the
                # subscriber about the gap so it can re-seed instead
                # of running on a silently-holed view.
                shed = sub.queue.popleft()
                sub.gapped.add(shed[0])
                sub.dropped += 1
                self.pubsub_stats["dropped"] += 1
            sub.queue.append(cell)
            sub.wake.set()
        for conn in dead or ():
            self._evict_subscriber(conn)

    async def _publish_legacy(self, channel: str, subs, message):
        """Pre-coalescing path (one awaited push per subscriber per
        event) — kept as the bench baseline and the
        RT_GCS_PUBSUB_COALESCE=0 escape hatch."""
        dead = []
        # Snapshot: the awaits below yield, and concurrent
        # subscribe/disconnect handlers mutate the live set.
        for conn in list(subs):
            if conn.closed:
                dead.append(conn)
                continue
            try:
                await conn.push("pubsub",
                                {"channel": channel, "message": message})
                self.pubsub_stats["sent_msgs"] += 1
                self.pubsub_stats["sent_frames"] += 1
            except Exception:
                dead.append(conn)
        for conn in dead:
            subs.discard(conn)

    async def _sub_pump(self, sub: _Subscriber):
        """Drain one subscriber's queue: each pass ships everything
        queued (bounded by gcs_pubsub_batch_max) as one KIND_BATCH
        frame, with consecutive same-channel messages folded into a
        single pubsub_batch push carrying PRE-PICKLED message blobs
        (serialized once per event in the shared cell, reused by every
        subscriber's pump).  Within a channel, delivery order ==
        publish order (the queue is FIFO and runs preserve it)."""
        conn = sub.conn
        st = self.pubsub_stats
        try:
            while not conn.closed:
                if not sub.queue:
                    sub.wake.clear()
                    if not sub.queue:
                        await sub.wake.wait()
                    continue
                batch_max = max(1, cfg.gcs_pubsub_batch_max)
                drained = []
                q = sub.queue
                t_flush = time.time()
                while q and len(drained) < batch_max:
                    drained.append(q.popleft())
                st["sent_msgs"] += len(drained)
                # Every run — singletons included — ships the shared
                # pre-pickled blob: interleaved channels must not
                # degrade fan-out serialization back to
                # O(events x subscribers).
                items = []
                i = 0
                while i < len(drained):
                    ch = drained[i][0]
                    j = i
                    while j < len(drained) and drained[j][0] == ch:
                        j += 1
                    run = drained[i:j]
                    for c in run:
                        if c[2] is None:
                            c[2] = protocol.dumps(c[1])
                    items.append(("pubsub_batch",
                                  {"channel": ch,
                                   "raw": [c[2] for c in run]}))
                    i = j
                st["batches"] += 1
                st["batched_msgs"] += len(drained)
                if len(drained) > st["max_batch"]:
                    st["max_batch"] = len(drained)
                if sub.gapped and not q:
                    # Only once the queue is FULLY drained: everything
                    # queued at shed time predates the gap notice, so
                    # the consumer's authoritative re-seed can never be
                    # overwritten by a stale event still in flight
                    # behind it.  (A backlog deeper than one
                    # batch_max drain keeps the flag for a later pass.)
                    items.append(("pubsub_gap",
                                  {"channels": sorted(sub.gapped)}))
                    sub.gapped.clear()
                st["sent_frames"] += len(items)
                conn.push_send_many_nowait(items)
                # Batch flushes (2+ coalesced events) land in the span
                # ring: the timeline shows WHEN fan-out bursts happened
                # and how much one frame folded.  Singleton pushes are
                # steady-state noise and stay out of the ring.
                if len(drained) > 1:
                    _tracing.record(
                        "gcs", "gcs.pubsub_flush", t_flush,
                        time.time() - t_flush,
                        args={"events": len(drained),
                              "frames": len(items),
                              "subscriber": getattr(conn, "name", "?")})
                await conn.backpressure()
        except asyncio.CancelledError:
            return
        except Exception as e:
            # Evicting a subscriber whose conn still looks healthy
            # must leave a trace: it silently stops ALL its event
            # delivery (unlike queue overflow, which sends a gap
            # notice), so a swallowed pump bug would present as a
            # permanently stale consumer with zero diagnostics.
            logger.warning("pubsub pump for %s failed (%s: %s); "
                           "evicting subscriber",
                           getattr(conn, "name", conn),
                           type(e).__name__, e)
        finally:
            self._evict_subscriber(conn)

    # ----------------------------------------------------------------- jobs
    async def rpc_register_driver(self, conn, body):
        job_id = body["job_id"]
        self._drivers[id(conn)] = {"job_id": job_id}
        self.jobs[job_id] = {"job_id": job_id, "start_time": time.time(),
                             "driver_pid": body.get("pid"), "state": "RUNNING",
                             "entrypoint": body.get("entrypoint", "")}
        return {"ok": True, "nodes": [n.view() for n in self.nodes.values()]}

    async def _cleanup_job(self, job_id):
        if job_id in self.jobs:
            self.jobs[job_id]["state"] = "FINISHED"
        for actor in list(self.actors.values()):
            if actor.job_id == job_id and not actor.detached and actor.state != DEAD:
                await self._kill_actor(actor, "job finished", no_restart=True)
        for pg in list(self.placement_groups.values()):
            if pg.job_id == job_id:
                await self._remove_pg(pg)

    async def rpc_list_jobs(self, conn, body):
        return list(self.jobs.values())

    # --------------------------------------------------------------- actors
    async def rpc_create_actor(self, conn, body):
        """Register + schedule an actor (reference: GcsActorManager::
        RegisterActor + GcsActorScheduler::Schedule, gcs_actor_scheduler.cc:49)."""
        actor_id = body["actor_id"]
        spec = body["spec"]
        actor = ActorInfo(actor_id, spec, id(conn), body.get("job_id"))
        if actor.name:
            key = (actor.namespace, actor.name)
            if key in self.named_actors:
                existing = self.actors.get(self.named_actors[key])
                if existing is not None and existing.state != DEAD:
                    return {"ok": False,
                            "reason": f"actor name '{actor.name}' already taken"}
            self.named_actors[key] = actor_id
        self.actors[actor_id] = actor
        task = asyncio.get_running_loop().create_task(self._schedule_actor(actor))
        self._pending_actor_creations[actor_id] = task
        # Completed schedules must not accumulate (one dead Task per
        # actor EVER created is a control-plane leak at scale).
        task.add_done_callback(
            lambda _t, aid=actor_id:
            self._pending_actor_creations.pop(aid, None))
        return {"ok": True}

    async def _schedule_actor(self, actor: ActorInfo):
        resources = dict(actor.spec.get("resources") or {})
        strategy = actor.spec.get("scheduling_strategy")
        deadline = time.monotonic() + 120.0
        t_sched = time.time()
        attempts = 0
        while time.monotonic() < deadline:
            attempts += 1
            node = self._pick_node(resources, strategy, actor.pg_id,
                                   actor.spec.get("bundle_index"))
            if node is None:
                await asyncio.sleep(0.05)
                continue
            try:
                # The reply comes after the actor's constructor has run,
                # so no deadline here either (see the raylet's
                # create_actor request): the 120 s above bound the
                # search for a node, not the user's __init__.  A raylet
                # that dies closes this connection.
                reply = await node.conn.request("lease_worker_for_actor", {
                    "actor_id": actor.actor_id,
                    "resources": resources,
                    "pg_id": actor.pg_id,
                    "bundle_index": actor.spec.get("bundle_index"),
                    "spec": actor.spec,
                }, timeout=None)
            except Exception as e:
                logger.warning("actor lease on node %s failed: %s",
                               node.node_id.hex()[:8], e)
                await asyncio.sleep(0.05)
                continue
            if not reply.get("ok"):
                if reply.get("init_error") is not None:
                    # Deterministic failure inside the actor's __init__ /
                    # class unpickle — retrying cannot help (reference:
                    # GcsActorManager marks the actor DEAD on creation-task
                    # failure, gcs_actor_manager.h:181-232).
                    actor.state = DEAD
                    actor.death_cause = reply.get("reason", "init failed")
                    actor.init_error_blob = reply.get("init_error")
                    await self._publish("actors", {"event": "dead",
                                                   "actor": actor.view()})
                    self._wake_actor_waiters(actor)
                    return
                await asyncio.sleep(0.02)
                continue
            actor.node_id = node.node_id
            actor.addr = tuple(reply["worker_addr"])
            actor.worker_id = reply.get("worker_id")
            actor.spec["pid"] = reply.get("pid")
            actor.state = ALIVE
            # Scheduling-decision span: queue-to-ALIVE latency with the
            # chosen node and how many pick/lease rounds it took.
            _tracing.record(
                "gcs", "gcs.schedule_actor", t_sched,
                time.time() - t_sched,
                args={"actor_id": actor.actor_id.hex()[:12],
                      "node": node.node_id.hex()[:12],
                      "attempts": attempts})
            await self._publish("actors", {"event": "alive",
                                           "actor": actor.view()})
            self._wake_actor_waiters(actor)
            return
        actor.state = DEAD
        actor.death_cause = "scheduling timed out (infeasible resources?)"
        await self._publish("actors", {"event": "dead", "actor": actor.view()})
        self._wake_actor_waiters(actor)

    def _pick_node(self, resources, strategy, pg_id=None, bundle_index=None):
        """Hybrid pack policy with PG/node-affinity support (reference:
        hybrid_scheduling_policy.h:48, node_affinity; bundle policies)."""
        if pg_id is not None:
            pg = self.placement_groups.get(pg_id)
            if pg is None or pg.state != "CREATED":
                return None
            if bundle_index is not None and bundle_index >= 0:
                nid = pg.bundle_nodes[bundle_index]
                node = self.nodes.get(nid)
                return node if node and node.alive else None
            candidates = [self.nodes[n] for n in pg.bundle_nodes
                          if n in self.nodes and self.nodes[n].alive
                          and self.nodes[n].conn is not None]
        else:
            # conn None = snapshot-restored node still reconnecting:
            # alive for liveness purposes, but not leasable yet.
            candidates = [n for n in self.nodes.values()
                          if n.alive and n.conn is not None]
        if strategy and strategy.get("type") == "node_affinity":
            nid = strategy["node_id"]
            node = self.nodes.get(nid)
            if node is None and isinstance(nid, str):
                # Callers commonly pass the hex form from ray_tpu.nodes().
                node = next((n for k, n in self.nodes.items()
                             if k.hex() == nid), None)
            if node and node.alive and node.conn is not None \
                    and self._fits(node, resources):
                return node
            if not strategy.get("soft", False):
                return None
        feasible = [n for n in candidates if self._fits_total(n, resources)]
        if not feasible:
            return None
        avail = [n for n in feasible if self._fits(n, resources)]
        pool = avail or feasible
        if strategy and strategy.get("type") == "spread":
            return min(pool, key=lambda n: n.load)
        # pack: prefer most-utilized node that still fits (hybrid policy).
        return max(pool, key=lambda n: n.load if avail else -n.load)

    @staticmethod
    def _fits(node: NodeInfo, resources: dict) -> bool:
        return all(node.available_resources.get(k, 0) >= v
                   for k, v in resources.items())

    @staticmethod
    def _fits_total(node: NodeInfo, resources: dict) -> bool:
        return all(node.total_resources.get(k, 0) >= v
                   for k, v in resources.items())

    async def rpc_get_actor(self, conn, body):
        actor = self.actors.get(body["actor_id"])
        if actor is None:
            return None
        return actor.view()

    async def rpc_wait_actor_alive(self, conn, body):
        actor = self.actors.get(body["actor_id"])
        if actor is None:
            return None
        if actor.state in (ALIVE, DEAD):
            return actor.view()
        fut = asyncio.get_running_loop().create_future()
        self._actor_waiters.setdefault(actor.actor_id, []).append(fut)
        try:
            await asyncio.wait_for(fut, body.get("timeout", 120.0))
        except asyncio.TimeoutError:
            pass
        return actor.view()

    def _wake_actor_waiters(self, actor: ActorInfo):
        for fut in self._actor_waiters.pop(actor.actor_id, []):
            if not fut.done():
                fut.set_result(None)

    async def rpc_get_named_actor(self, conn, body):
        key = (body.get("namespace", "default"), body["name"])
        actor_id = self.named_actors.get(key)
        if actor_id is None:
            return None
        actor = self.actors.get(actor_id)
        return actor.view() if actor and actor.state != DEAD else None

    async def rpc_list_named_actors(self, conn, body):
        out = []
        for (ns, name), aid in self.named_actors.items():
            a = self.actors.get(aid)
            if a is not None and a.state != DEAD:
                out.append({"name": name, "namespace": ns})
        return out

    async def rpc_report_actor_death(self, conn, body):
        """A raylet reports that an actor's worker process died."""
        actor = self.actors.get(body["actor_id"])
        if actor is None or actor.state == DEAD:
            return {"ok": True}
        await self._on_actor_interrupted(actor, body.get("reason", "worker died"))
        return {"ok": True}

    async def _on_actor_interrupted(self, actor: ActorInfo, reason: str):
        """Actor restart state machine (reference: gcs_actor_manager.h:181-232:
        ALIVE -> RESTARTING while restarts remain, else -> DEAD)."""
        if actor.max_restarts != 0 and (
                actor.max_restarts < 0 or actor.num_restarts < actor.max_restarts):
            actor.num_restarts += 1
            actor.state = RESTARTING
            actor.addr = None
            self._record_event(
                "WARNING", "ACTOR_RESTARTING",
                f"actor {actor.actor_id.hex()[:8]} "
                f"({actor.spec.get('class_name')}): {reason}")
            await self._publish("actors", {"event": "restarting",
                                           "actor": actor.view()})
            asyncio.get_running_loop().create_task(self._schedule_actor(actor))
        else:
            actor.state = DEAD
            actor.death_cause = reason
            self._record_event(
                "ERROR", "ACTOR_DEAD",
                f"actor {actor.actor_id.hex()[:8]} "
                f"({actor.spec.get('class_name')}): {reason}")
            await self._publish("actors", {"event": "dead",
                                           "actor": actor.view()})
            self._wake_actor_waiters(actor)

    async def rpc_kill_actor(self, conn, body):
        actor = self.actors.get(body["actor_id"])
        if actor is None:
            return {"ok": False}
        await self._kill_actor(actor, "ray_tpu.kill",
                               no_restart=body.get("no_restart", True))
        return {"ok": True}

    async def _kill_actor(self, actor: ActorInfo, reason, no_restart=True):
        if no_restart:
            actor.max_restarts = 0
        if actor.node_id is not None:
            node = self.nodes.get(actor.node_id)
            if node is not None and node.alive:
                try:
                    await node.conn.request("kill_worker",
                                            {"worker_id": actor.worker_id})
                except Exception:
                    pass
        if no_restart:
            actor.state = DEAD
            actor.death_cause = str(reason)
            await self._publish("actors", {"event": "dead", "actor": actor.view()})
            self._wake_actor_waiters(actor)

    async def rpc_list_actors(self, conn, body):
        return [a.view() for a in self.actors.values()]

    # ----------------------------------------------------- placement groups
    async def rpc_create_placement_group(self, conn, body):
        pg = PlacementGroupInfo(body["pg_id"], body["bundles"],
                                body.get("strategy", "PACK"),
                                body.get("name"), body.get("job_id"))
        self.placement_groups[pg.pg_id] = pg
        asyncio.get_running_loop().create_task(self._schedule_pg(pg))
        return {"ok": True}

    async def _schedule_pg(self, pg: PlacementGroupInfo):
        """Two-phase bundle reservation (reference:
        gcs_placement_group_scheduler.h:264 — Prepare on all nodes, then
        Commit; bundle policies PACK/SPREAD/STRICT_* in
        raylet/scheduling/policy/bundle_scheduling_policy.h)."""
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            alive = [n for n in self.nodes.values()
                     if n.alive and n.conn is not None]
            try:
                assignment = choose_nodes_for_bundles(
                    pg.bundles, pg.strategy, alive)
            except PlacementError:
                assignment = None
            if assignment is None:
                await asyncio.sleep(0.05)
                continue
            # Phase 1: prepare (reserve) on each node.
            prepared = []
            ok = True
            for bundle_index, (node, bundle) in enumerate(
                    zip(assignment, pg.bundles)):
                try:
                    r = await node.conn.request("prepare_bundle", {
                        "pg_id": pg.pg_id, "bundle_index": bundle_index,
                        "resources": bundle})
                except Exception:
                    r = {"ok": False}
                if r.get("ok"):
                    prepared.append((node, bundle_index))
                else:
                    ok = False
                    break
            if not ok:
                for node, bundle_index in prepared:
                    try:
                        await node.conn.request("return_bundle", {
                            "pg_id": pg.pg_id, "bundle_index": bundle_index})
                    except Exception:
                        pass
                await asyncio.sleep(0.05)
                continue
            # Phase 2: commit.
            for node, bundle_index in prepared:
                try:
                    await node.conn.request("commit_bundle", {
                        "pg_id": pg.pg_id, "bundle_index": bundle_index})
                except Exception:
                    pass
            pg.bundle_nodes = [n.node_id for n in assignment]
            pg.state = "CREATED"
            await self._publish("placement_groups",
                                {"event": "created", "pg": pg.view()})
            return
        pg.state = "INFEASIBLE"
        await self._publish("placement_groups",
                            {"event": "infeasible", "pg": pg.view()})

    async def rpc_get_placement_group(self, conn, body):
        pg = self.placement_groups.get(body["pg_id"])
        return pg.view() if pg else None

    async def rpc_wait_placement_group(self, conn, body):
        deadline = time.monotonic() + body.get("timeout", 60.0)
        while time.monotonic() < deadline:
            pg = self.placement_groups.get(body["pg_id"])
            if pg is None:
                return None
            if pg.state in ("CREATED", "INFEASIBLE"):
                return pg.view()
            await asyncio.sleep(0.01)
        return pg.view() if pg else None

    async def rpc_remove_placement_group(self, conn, body):
        pg = self.placement_groups.get(body["pg_id"])
        if pg is None:
            return {"ok": False}
        await self._remove_pg(pg)
        return {"ok": True}

    async def _remove_pg(self, pg: PlacementGroupInfo):
        for bundle_index, node_id in enumerate(pg.bundle_nodes):
            node = self.nodes.get(node_id)
            if node is not None and node.alive:
                try:
                    await node.conn.request("return_bundle", {
                        "pg_id": pg.pg_id, "bundle_index": bundle_index})
                except Exception:
                    pass
        pg.state = "REMOVED"
        self.placement_groups.pop(pg.pg_id, None)
        await self._publish("placement_groups",
                            {"event": "removed", "pg": pg.view()})

    async def rpc_list_placement_groups(self, conn, body):
        return [pg.view() for pg in self.placement_groups.values()]

    # ------------------------------------------------------------ stats/etc
    async def rpc_cluster_resources(self, conn, body):
        # Served from the incrementally-maintained aggregates (swapped
        # in/out on register / heartbeat delta / death) — O(resource
        # kinds), not O(nodes).
        return {"total": dict(self._agg_total),
                "available": dict(self._agg_avail)}

    async def rpc_ping(self, conn, body):
        return {"ok": True, "uptime": time.time() - self._start_time}

    # ------------------------------------------------------------ autopilot
    def _arbiter_capacity(self) -> int:
        """Arbitration currency: aggregate CPU slots across alive
        nodes (1 unit backs 1 serve replica / train worker / data
        task slot; the autopilot bench provisions 1-CPU nodes so a
        unit is a node)."""
        return int(self._agg_total.get("CPU", 0))

    async def _arbiter_loop(self):
        while True:
            await asyncio.sleep(max(0.02, cfg.autopilot_period_s))
            try:
                await self._arbiter_tick()
            except asyncio.CancelledError:
                return
            except Exception:
                logger.exception("arbiter tick failed")

    async def _arbiter_tick(self):
        t0 = time.time()
        capacity = self._arbiter_capacity()
        decisions = self.arbiter.tick(capacity=capacity)
        if not decisions:
            return
        breached = [w for w in
                    self.arbiter._workloads.values()
                    if w.kind == "serve" and w.breached]
        beneficiary = breached[0].wid if breached else None
        for dec in decisions:
            reclaim = (dec["action"] == "revoke"
                       and dec["kind"] in ("train", "data")
                       and beneficiary is not None)
            if reclaim:
                # Fence the reclaimed capacity: the most-idle alive
                # nodes stop admitting new low-priority leases (sched
                # filters treat reserved like draining) while the
                # beneficiary's replicas can still land there.
                await self._reserve_nodes(dec["from"] - dec["to"],
                                          beneficiary)
            sev = "WARNING" if dec["action"] == "revoke" else "INFO"
            self._record_event(
                sev, "AUTOPILOT_" + dec["action"].upper(),
                f"{dec['wid']}: {dec['from']} -> {dec['to']} units "
                f"({dec['reason']})")
            await self._publish("arbiter", dict(dec))
        _tracing.record(
            "gcs", "gcs.arbitrate", t0, time.time() - t0,
            args={"capacity": capacity,
                  "decisions": [
                      {"wid": d["wid"], "action": d["action"],
                       "from": d["from"], "to": d["to"],
                       "reason": d["reason"]} for d in decisions]})

    async def _reserve_nodes(self, count: int, beneficiary: str):
        if count <= 0:
            return
        idle = sorted(
            (n for n in self.nodes.values()
             if n.alive and not n.draining and n.reserved is None),
            key=lambda n: -n.available_resources.get("CPU", 0))
        now = time.monotonic()
        for node in idle[:count]:
            node.reserved = beneficiary
            node.reserve_deadline = now + cfg.autopilot_reserve_ttl_s
            await self._publish("nodes", {
                "event": "updated", "node_id": node.node_id,
                "reserved": beneficiary})

    async def rpc_arbiter_register(self, conn, body):
        try:
            wl = self.arbiter.register(
                body["wid"], body["kind"],
                priority=body.get("priority", 100),
                min_units=body.get("min_units", 0),
                max_units=body.get("max_units"),
                slo=body.get("slo"))
        except ValueError as e:
            return {"ok": False, "error": {"code": "BAD_DECLARATION",
                                           "message": str(e)}}
        if body["kind"] == "train":
            self._gang_elastic[wl.wid] = bool(body.get("elastic", True))
        return {"ok": True, "granted": wl.granted}

    async def rpc_arbiter_report(self, conn, body):
        decl = body.get("decl") or {}
        if decl.get("kind") == "train" and "elastic" in decl:
            self._gang_elastic[body["wid"]] = bool(decl["elastic"])
        return self.arbiter.report(
            body["wid"], want=body.get("want", 0),
            units_now=body.get("units_now", 0),
            signals=body.get("signals"),
            **{k: v for k, v in decl.items() if k != "elastic"})

    async def rpc_arbiter_unregister(self, conn, body):
        self._gang_elastic.pop(body["wid"], None)
        return {"ok": self.arbiter.unregister(body["wid"])}

    async def rpc_arbiter_status(self, conn, body):
        st = self.arbiter.status()
        st["capacity"] = self._arbiter_capacity()
        st["reserved_nodes"] = {
            n.node_id.hex()[:8]: n.reserved
            for n in self.nodes.values()
            if n.alive and n.reserved is not None}
        return st

    async def rpc_resize_gang(self, conn, body):
        """Operator/broker entry point for elastic gang resize: the
        target rides the gang's next report reply as a directive, so
        `rt resize` and the arbiter's own grants share one path into
        BackendExecutor.request_elastic_resize."""
        gang = body["gang"]
        wid = gang if gang.startswith("train:") else f"train:{gang}"
        wl = self.arbiter.get(wid)
        if wl is None or wl.kind != "train":
            known = sorted(w.wid for w in self.arbiter._workloads.values()
                           if w.kind == "train")
            return {"ok": False, "error": {
                "code": "UNKNOWN_GANG",
                "message": f"no train gang {gang!r} is registered with "
                           f"the arbiter (known: {known})"}}
        if not self._gang_elastic.get(wid, True):
            return {"ok": False, "error": {
                "code": "NOT_ELASTIC",
                "message": f"gang {gang!r} was not started with "
                           f"ScalingConfig(elastic=True); only elastic "
                           f"gangs can be resized in place"}}
        target = int(body["target"])
        if target < wl.min_units:
            return {"ok": False, "error": {
                "code": "BELOW_QUORUM",
                "message": f"target {target} is below the gang's "
                           f"elastic_min_workers floor "
                           f"({wl.min_units})"}}
        if wl.max_units is not None and target > wl.max_units:
            return {"ok": False, "error": {
                "code": "ABOVE_CAPACITY",
                "message": f"target {target} exceeds the gang's "
                           f"placement-group capacity "
                           f"({wl.max_units})"}}
        self.arbiter.set_directive(wid, target)
        self._record_event(
            "INFO", "GANG_RESIZE_REQUESTED",
            f"{wid}: operator/broker directive -> {target} workers")
        return {"ok": True, "wid": wid, "target": target}

    async def rpc_release_bundles(self, conn, body):
        """Elastic shrink support: hand named PG bundle indices back to
        their nodes so the freed CPU really returns to the cluster pool
        (a shrunk gang must not keep its old reservation pinned)."""
        pg = self.placement_groups.get(body["pg_id"])
        if pg is None:
            return {"ok": False, "reason": "no such placement group"}
        released = []
        for bundle_index in body["indices"]:
            if bundle_index in pg.released_bundles \
                    or bundle_index >= len(pg.bundle_nodes):
                continue
            node = self.nodes.get(pg.bundle_nodes[bundle_index])
            if node is not None and node.alive and node.conn is not None:
                try:
                    await node.conn.request("return_bundle", {
                        "pg_id": pg.pg_id, "bundle_index": bundle_index})
                except Exception:
                    pass
            pg.released_bundles.add(bundle_index)
            released.append(bundle_index)
        return {"ok": True, "released": released}

    async def rpc_reacquire_bundles(self, conn, body):
        """Elastic grow support: re-reserve previously released bundle
        indices through the same two-phase prepare/commit used at PG
        creation.  Failure (capacity taken by another tenant) is a
        clean refusal — the caller retries on a later grant."""
        pg = self.placement_groups.get(body["pg_id"])
        if pg is None:
            return {"ok": False, "reason": "no such placement group"}
        reacquired, failed = [], []
        for bundle_index in body["indices"]:
            if bundle_index not in pg.released_bundles:
                continue
            node = self.nodes.get(pg.bundle_nodes[bundle_index])
            ok = False
            if node is not None and node.alive and node.conn is not None:
                try:
                    r = await node.conn.request("prepare_bundle", {
                        "pg_id": pg.pg_id, "bundle_index": bundle_index,
                        "resources": pg.bundles[bundle_index]})
                    if r.get("ok"):
                        await node.conn.request("commit_bundle", {
                            "pg_id": pg.pg_id,
                            "bundle_index": bundle_index})
                        ok = True
                except Exception:
                    ok = False
            if ok:
                pg.released_bundles.discard(bundle_index)
                reacquired.append(bundle_index)
            else:
                failed.append(bundle_index)
        return {"ok": not failed, "reacquired": reacquired,
                "failed": failed}

    # -------------------------------------------------------------- metrics
    def _ensure_metrics(self):
        """GCS control-plane gauges/counters on the shared
        ray_tpu.util.metrics registry (in-process clusters see them in
        the driver's registry; the standalone GCS process self-exports
        them through the telemetry KV below)."""
        if self._metrics is not None:
            return self._metrics
        from ray_tpu.util.metrics import Counter, Gauge
        self._metrics = {
            "queue_depth": Gauge(
                "gcs_pubsub_queue_depth",
                "deepest per-subscriber outbound pubsub queue"),
            "subscribers": Gauge(
                "gcs_pubsub_subscribers", "live pubsub subscriber conns"),
            "batch_avg": Gauge(
                "gcs_pubsub_batch_size_avg",
                "mean messages folded per coalesced batch frame"),
            "dropped": Counter(
                "gcs_pubsub_dropped_total",
                "pubsub events shed by slow-subscriber queue bounds"),
            "pending_actors": Gauge(
                "gcs_pending_actor_creations",
                "actor creations awaiting scheduling"),
            "events_dropped": Counter(
                "gcs_events_dropped_total",
                "cluster events shed by the bounded event ring"),
            "snapshot_age": Gauge(
                "gcs_snapshot_age_seconds",
                "seconds since the last durable snapshot write"),
            "snapshot_bytes": Gauge(
                "gcs_snapshot_bytes", "size of the last snapshot blob"),
            "autopilot_grants": Counter(
                "autopilot_grants_total",
                "arbiter decisions that raised a workload budget"),
            "autopilot_revocations": Counter(
                "autopilot_revocations_total",
                "arbiter decisions that lowered a workload budget"),
            "autopilot_breach": Counter(
                "autopilot_slo_breach_seconds",
                "cumulative seconds any serve workload spent over its "
                "declared p99 TTFT SLO"),
            "autopilot_budget": Gauge(
                "autopilot_budget_units",
                "current arbiter-granted budget per workload"),
            "autopilot_workloads": Gauge(
                "autopilot_workloads",
                "workloads registered with the arbiter"),
        }
        # Counters exported as monotonic totals: remember last values.
        self._metric_last = {"dropped": 0, "events_dropped": 0,
                             "autopilot_grants": 0,
                             "autopilot_revocations": 0,
                             "autopilot_breach": 0.0}
        return self._metrics

    def _update_metrics(self):
        try:
            m = self._ensure_metrics()
        except Exception:
            return
        st = self.pubsub_stats
        depth = max((len(s.queue) for s in self._subs.values()),
                    default=0)
        m["queue_depth"].set(depth)
        m["subscribers"].set(len(self._subs))
        m["batch_avg"].set(
            round(st["batched_msgs"] / st["batches"], 2)
            if st["batches"] else 0.0)

        def export_counter(key, metric, current):
            delta = current - self._metric_last[key]
            if delta > 0:
                metric.inc(delta)
                self._metric_last[key] = current

        export_counter("dropped", m["dropped"], st["dropped"])
        export_counter("events_dropped", m["events_dropped"],
                       self.events_dropped)
        export_counter("autopilot_grants", m["autopilot_grants"],
                       self.arbiter.grants_total)
        export_counter("autopilot_revocations",
                       m["autopilot_revocations"],
                       self.arbiter.revocations_total)
        export_counter("autopilot_breach", m["autopilot_breach"],
                       self.arbiter.slo_breach_seconds)
        m["autopilot_workloads"].set(len(self.arbiter._workloads))
        for wl in self.arbiter._workloads.values():
            m["autopilot_budget"].set(
                wl.granted, tags={"workload": wl.wid, "kind": wl.kind})
        m["pending_actors"].set(len(self._pending_actor_creations))
        if self._last_snapshot_ts is not None:
            m["snapshot_age"].set(
                round(time.monotonic() - self._last_snapshot_ts, 3))
            m["snapshot_bytes"].set(self._last_snapshot_bytes)
        # Standalone GCS process: self-publish the gcs_* series into the
        # telemetry KV so the dashboard head aggregates them like any
        # worker's.  In-process clusters skip this (the driver's own
        # telemetry loop already exports the shared registry).
        try:
            from ray_tpu._private import worker as worker_mod
            if worker_mod.global_worker is None:
                import pickle
                from ray_tpu.util.metrics import registry_snapshot
                snaps = [s for s in registry_snapshot()
                         if s["name"].startswith("gcs_")]
                self.kv.setdefault("telemetry", {})[b"__gcs__"] = \
                    pickle.dumps({"snapshots": snaps,
                                  "profile": [],
                                  "pid": os.getpid(), "mode": "gcs"})
        except Exception:
            pass

    async def rpc_control_plane_stats(self, conn, body):
        """Raw control-plane instrumentation (bench + tests): pubsub
        queue/batch/drop counters, event-ring stats, snapshot age/size,
        scheduling table sizes."""
        self._update_metrics()
        return {
            "pubsub": {
                **self.pubsub_stats,
                "subscribers": len(self._subs),
                "queue_depth": max(
                    (len(s.queue) for s in self._subs.values()),
                    default=0),
            },
            "events": {"len": len(self.events),
                       "cap": self.events.maxlen,
                       "dropped": self.events_dropped},
            "snapshot": {
                "count": self._snapshot_count,
                "bytes": self._last_snapshot_bytes,
                "age_s": (round(time.monotonic() - self._last_snapshot_ts,
                                3)
                          if self._last_snapshot_ts is not None else None),
                "restored": self.restored_from_snapshot,
            },
            "nodes": {"alive": sum(1 for n in self.nodes.values()
                                   if n.alive),
                      "total": len(self.nodes),
                      "demand_nodes": len(self._demand_nodes)},
            "pending_actor_creations": len(self._pending_actor_creations),
        }

    async def rpc_dump_trace(self, conn, body):
        """Pull-path trace dump: the GCS process's span ring
        (scheduling decisions, pubsub batch flushes, slow RPC
        handlers) for rt timeline --cluster / rt trace."""
        body = body or {}
        return dict(_tracing.dump(stats_only=bool(body.get("stats_only")),
                                  clear=bool(body.get("clear"))),
                    role="gcs")


def main():
    import argparse
    import sys
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--persist-path", default=None)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="[gcs] %(levelname)s %(message)s")

    async def run():
        gcs = GcsServer(host=args.host, persist_path=args.persist_path)
        protocol.enable_eager_tasks()
        port = await gcs.start(args.port)
        print(f"GCS_PORT={port}", flush=True)
        sys.stdout.flush()
        await asyncio.Event().wait()

    asyncio.run(run())


if __name__ == "__main__":
    main()
