"""Deployment state reconciliation: target state vs running replicas.

Reference: python/ray/serve/_private/deployment_state.py — DeploymentState
(:897) with the STARTING/RUNNING/STOPPING replica sets, DeploymentStateManager
(:1567) driving update() every control-loop tick, ActorReplicaWrapper (:162)
hiding the actor lifecycle.  Rolling updates: new-version replicas start
first; old-version replicas stop only as new ones become ready, so serving
capacity never drops to zero (zero-downtime rollout).
"""

from __future__ import annotations

import logging
import time
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
from ray_tpu.serve.config import DeploymentConfig, ReplicaConfig
from ray_tpu.util import metrics as _metrics

logger = logging.getLogger(__name__)

STARTING = "STARTING"
RUNNING = "RUNNING"
DRAINING = "DRAINING"
STOPPING = "STOPPING"

DRAINING_GAUGE = _metrics.Gauge(
    "serve_replica_draining",
    "Replicas draining (no new admissions, finishing in-flight work "
    "before retirement)",
    tag_keys=("deployment",))


class ReplicaWrapper:
    """One replica actor's lifecycle (reference: ActorReplicaWrapper)."""

    def __init__(self, deployment_name: str, version: str,
                 config: DeploymentConfig, replica_config: ReplicaConfig):
        self.deployment_name = deployment_name
        self.version = version
        self.replica_tag = f"{deployment_name}#{uuid.uuid4().hex[:8]}"
        self.state = STARTING
        self._config = config
        self._replica_config = replica_config
        self._actor = None
        self._ready_ref = None
        self._drain_ref = None
        # The start's root span, serve.replica_start: (trace id, span
        # id) and when the decision to start was taken.
        self._start_ctx = (_tracing.fresh_id(), _tracing.fresh_id())
        self._start_t0 = 0.0

    def start(self):
        from ray_tpu.serve._private.replica import RTServeReplica
        opts = dict(self._replica_config.ray_actor_options or {})
        opts.setdefault("num_cpus", 0.1)
        opts.setdefault("name",
                        f"SERVE_REPLICA::{self.replica_tag}")
        opts.setdefault("max_concurrency", 1000)
        cls = ray_tpu.remote(RTServeReplica)
        # One trace a start: the creation task and the readiness probe
        # are submitted under the root's context, so the raylet's wait
        # for a worker, the worker's boot and everything the
        # constructor records link under it.
        self._start_t0 = time.time()
        token = _tracing.set_current(*self._start_ctx)
        try:
            self._actor = cls.options(**opts).remote(
                self.deployment_name, self.replica_tag,
                self._replica_config.deployment_def,
                self._replica_config.init_args,
                self._replica_config.init_kwargs,
                self._config.user_config, self.version)
            # Readiness probe: resolves when __init__ + reconfigure
            # finished (an engine's warm-up may still be running).
            self._ready_ref = self._actor.get_metadata.remote()
        finally:
            _tracing.reset_current(token)

    def check_ready(self) -> Optional[bool]:
        """None = still starting, True = ready, False = failed."""
        done, _ = ray_tpu.wait([self._ready_ref], num_returns=1, timeout=0)
        if not done:
            return None
        try:
            ray_tpu.get(self._ready_ref, timeout=1)
            self.state = RUNNING
            self._end_start(True)
            return True
        except Exception as e:
            logger.warning("replica %s failed to start: %s",
                           self.replica_tag, e)
            self._end_start(False)
            return False

    def _end_start(self, ok: bool):
        """Close the root span where the controller learns how the
        start ended, say its id beside the replica's tag (`rt trace
        <id>` prints the start), and hand the root's two timestamps to
        the replica for its start's books."""
        t1 = time.time()
        trace_id, span_id = self._start_ctx
        _tracing.record(
            "serve", "serve.replica_start", self._start_t0,
            t1 - self._start_t0,
            trace={"trace_id": trace_id, "span_id": span_id,
                   "parent_id": None},
            args={"deployment": self.deployment_name,
                  "replica_tag": self.replica_tag, "ok": ok})
        logger.info("replica %s %s after %.2fs (start trace %s)",
                    self.replica_tag, "ready" if ok else "failed",
                    t1 - self._start_t0, trace_id)
        if ok:
            self._actor.start_acknowledged.options(num_returns=0).remote(
                self._start_t0, t1)

    def reconfigure(self, user_config, version: str):
        self.version = version
        return self._actor.reconfigure.remote(user_config, version)

    def begin_stop(self, timeout_s: float):
        self.state = STOPPING
        if self._actor is not None:
            self._drain_ref = self._actor.prepare_for_shutdown.remote(
                timeout_s)

    def check_stopped(self) -> bool:
        if self._actor is None:
            return True
        if self._drain_ref is not None:
            done, _ = ray_tpu.wait([self._drain_ref], num_returns=1,
                                   timeout=0)
            if not done:
                return False
        try:
            ray_tpu.kill(self._actor)
        except Exception:
            pass
        self._actor = None
        return True

    def running_info(self) -> Dict:
        info = {
            "replica_tag": self.replica_tag,
            "deployment": self.deployment_name,
            "version": self.version,
            "actor": self._actor,
            "max_concurrent_queries": self._config.max_concurrent_queries,
        }
        # KV-affinity extras piggyback on the load sample the autoscale
        # poll already collects: the replica's prefix digest (what it
        # has cached) and its migration pull address.  Routers receive
        # them with the membership broadcast — no extra poll plane.
        load = self.last_load
        if load:
            for key in ("kv_digest", "kv_rdv"):
                if load.get(key):
                    info[key] = load[key]
        return info

    def num_ongoing(self) -> Optional[int]:
        try:
            return ray_tpu.get(self._actor.num_ongoing_requests.remote(),
                               timeout=2)
        except Exception:
            return None

    _load_ref = None
    _load_sent_at = 0.0
    last_load: Optional[Dict] = None

    def poll_load(self, now: float) -> Optional[Dict]:
        """Non-blocking load tracking (the autoscaler's input): fire a
        get_autoscale_metrics probe, collect it on a later tick, and
        always answer from the cached last sample — one hung replica
        must never stall the control loop the way a blocking get
        would."""
        if self._actor is None:
            return self.last_load
        if self._load_ref is None:
            self._load_ref = \
                self._actor.get_autoscale_metrics.remote()
            self._load_sent_at = now
            return self.last_load
        done, _ = ray_tpu.wait([self._load_ref], num_returns=1,
                               timeout=0)
        if done:
            try:
                self.last_load = ray_tpu.get(self._load_ref, timeout=1)
            except Exception:
                pass  # keep the previous sample; health checks judge
            self._load_ref = None
        elif now - self._load_sent_at > 10.0:
            self._load_ref = None  # probe lost; re-fire next tick
        return self.last_load

    _drain_deadline = 0.0
    _drain_started = 0.0

    def begin_drain(self, now: float, timeout_s: float):
        """Scale-down path: stop admitting (the reconciler's broadcast
        only carries RUNNING replicas, so routers drop this one on the
        next long-poll) and let in-flight work — including long-lived
        streams — finish before the actor is retired."""
        self.state = DRAINING
        self._drain_started = now
        self._drain_deadline = now + timeout_s
        # Timeline annotation: scale-downs show up against the serve
        # spans they displace (controller process ring).
        _tracing.event("serve", "serve.drain",
                       args={"replica": self.replica_tag,
                             "timeout_s": timeout_s})
        # Demand a FRESH ongoing sample before declaring the drain
        # complete: the pre-drain cached value predates the routers
        # learning this replica left the broadcast — and an in-flight
        # probe fired pre-drain would repopulate it, so drop that too.
        self.last_load = None
        self._load_ref = None

    def offer_kv_migration(self, dest: "ReplicaWrapper"):
        """Drain handoff: offer this (DRAINING) replica's hot KV pages
        to a surviving replica before teardown.  The origin serves a
        manifest (pull address + hottest cached prefixes, still
        referenced by its radix tree); the SURVIVOR pulls the pages
        over the transfer plane.  Copies, not moves — the origin's
        pages stay intact until its normal teardown, so an un-drain
        mid-flight cannot double-count anything, and a non-KV
        deployment simply fails the manifest RPC (swallowed here).
        The manifest fetch is bounded (2s); the pull itself is
        fire-and-forget on the survivor."""
        if self._actor is None or dest._actor is None:
            return
        try:
            manifest = ray_tpu.get(
                self._actor.handle_request.remote(
                    "kv_drain_manifest", (), {}), timeout=2)
        except Exception:
            return
        if not manifest:
            return
        _tracing.event("serve", "serve.drain_migrate",
                       args={"origin": self.replica_tag,
                             "dest": dest.replica_tag,
                             "prefixes":
                                 len(manifest.get("prefixes", ()))})
        logger.info("drain: offering %d hot prefixes of %s to %s",
                    len(manifest.get("prefixes", ())),
                    self.replica_tag, dest.replica_tag)
        dest._actor.handle_request.options(num_returns=0).remote(
            "kv_pull_from", (manifest,), {})

    def confirmed_idle(self, now: float) -> bool:
        """A FRESH post-drain sample confirms zero in-flight work.  The
        ≥1s age floor covers the window in which a router that has not
        yet seen the membership change can still assign work — the ONE
        idle-confirmation rule, shared by drain completion and the
        un-drain gate (both would oversubscribe on a stale sample)."""
        load = self.poll_load(now)
        return (now - self._drain_started >= 1.0
                and load is not None and load.get("ongoing", 1) == 0)

    def drain_complete(self, now: float) -> bool:
        """True once the replica reports zero in-flight requests (or
        the drain deadline passed — a wedged stream must not pin a
        retired replica forever)."""
        if now >= self._drain_deadline:
            logger.warning("replica %s drain timed out; stopping with "
                           "work in flight", self.replica_tag)
            return True
        return self.confirmed_idle(now)

    _health_ref = None
    _health_sent_at = 0.0

    def poll_health(self, now: float) -> bool:
        """Non-blocking health tracking: fire a probe, poll it on later
        ticks.  Returns False when the replica must be replaced (probe
        errored or outlived health_check_timeout_s).  One hung replica
        must never stall the control loop (reference tracks health the
        same way: deployment_state.py check_started/health polling)."""
        if self._actor is None:
            return False
        if self._health_ref is None:
            self._health_ref = self._actor.check_health.remote()
            self._health_sent_at = now
            return True
        done, _ = ray_tpu.wait([self._health_ref], num_returns=1, timeout=0)
        if not done:
            if now - self._health_sent_at \
                    > self._config.health_check_timeout_s:
                return False
            return True
        try:
            ray_tpu.get(self._health_ref, timeout=1)
            self._health_ref = None
            return True
        except Exception:
            return False


class DeploymentState:
    """Reconciles one deployment (reference: deployment_state.py:897)."""

    def __init__(self, name: str, long_poll_host):
        self.name = name
        self._long_poll = long_poll_host
        self.target_config: Optional[DeploymentConfig] = None
        self.target_replica_config: Optional[ReplicaConfig] = None
        self.target_version: Optional[str] = None
        self.target_num_replicas = 0
        self.deleting = False
        self.replicas: List[ReplicaWrapper] = []
        self._last_health_check = 0.0
        self._last_broadcast: Any = None
        self._digest_fp: Any = None
        self._digest_fp_t = 0.0
        self._start_failures = 0
        self.deploy_failed = False

    # ------------------------------------------------------------- target
    def deploy(self, config: DeploymentConfig,
               replica_config: ReplicaConfig, version: str):
        self.target_config = config
        self.target_replica_config = replica_config
        self.target_version = version
        self.deleting = False
        self._start_failures = 0
        self.deploy_failed = False
        if config.autoscaling_config is not None:
            lo = config.autoscaling_config.min_replicas
            hi = config.autoscaling_config.max_replicas
            self.target_num_replicas = min(
                max(self.target_num_replicas or lo, lo), hi)
        else:
            self.target_num_replicas = config.num_replicas

    def delete(self):
        self.deleting = True
        self.target_num_replicas = 0

    def set_target_num_replicas(self, n: int):
        """Autoscaler entry point."""
        self.target_num_replicas = n

    # ---------------------------------------------------------- reconcile
    def update(self) -> bool:
        """One reconciliation tick.  Returns True while work is pending."""
        cfg = self.target_config
        if cfg is None:
            return False
        # 1. Promote replicas that finished starting; drop failed ones.
        for r in list(self.replicas):
            if r.state == STARTING:
                ready = r.check_ready()
                if ready is False:
                    self.replicas.remove(r)
                    self._start_failures += 1
                    if self._start_failures >= 3:
                        # Constructor keeps failing: stop respawning 10x/s
                        # forever (reference: DEPLOY_FAILED after bounded
                        # attempts, deployment_state.py).
                        self.deploy_failed = True
                        logger.error(
                            "deployment %s marked DEPLOY_FAILED after %d "
                            "consecutive replica start failures",
                            self.name, self._start_failures)
                elif ready is True:
                    self._start_failures = 0
            elif r.state == STOPPING:
                if r.check_stopped():
                    self.replicas.remove(r)
            elif r.state == DRAINING:
                # A delete arriving mid-drain downgrades the drain to a
                # plain graceful stop — teardown must not wait out the
                # (much longer) drain window.
                if self.deleting \
                        or r.drain_complete(time.monotonic()):
                    r.begin_stop(cfg.graceful_shutdown_timeout_s)

        running = [r for r in self.replicas if r.state == RUNNING]
        starting = [r for r in self.replicas if r.state == STARTING]

        # 2. Version rollout: light config change (user_config only) is
        # applied in place; a code/version change replaces replicas, new
        # before old (zero downtime).
        stale = [r for r in running if r.version != self.target_version]
        fresh = [r for r in running + starting
                 if r.version == self.target_version]
        # Start new-version replicas up to the target count — but first
        # UN-DRAIN: a same-version replica mid-drain still has a warm
        # model resident; re-admitting it is strictly cheaper than
        # paying a cold start because the autoscaler flapped.
        want_new = 0 if self.deploy_failed \
            else self.target_num_replicas - len(fresh)
        if want_new > 0:
            now_ud = time.monotonic()
            for r in self.replicas:
                if want_new <= 0:
                    break
                if r.state == DRAINING \
                        and r.version == self.target_version:
                    # Only un-drain a replica CONFIRMED idle: routers
                    # reset a re-broadcast replica's in-flight count to
                    # zero, so re-admitting one with live streams would
                    # oversubscribe it past max_concurrent_queries.
                    if not r.confirmed_idle(now_ud):
                        continue
                    _tracing.event("serve", "serve.undrain",
                                   args={"replica": r.replica_tag})
                    logger.info("un-draining replica %s (target rose "
                                "back)", r.replica_tag)
                    r.state = RUNNING
                    want_new -= 1
        for _ in range(max(0, want_new)):
            r = ReplicaWrapper(self.name, self.target_version, cfg,
                               self.target_replica_config)
            r.start()
            self.replicas.append(r)
        # Stop stale replicas only when enough fresh ones are RUNNING to
        # keep capacity (rolling).
        fresh_running = [r for r in running
                         if r.version == self.target_version]
        allow_stop = min(len(stale),
                         max(0, len(fresh_running) + len(stale)
                             - self.target_num_replicas))
        for r in stale[:allow_stop]:
            r.begin_stop(cfg.graceful_shutdown_timeout_s)

        # 3. Scale down surplus same-version replicas: DRAIN, don't
        # kill — the replica leaves the router broadcast immediately
        # (no new admissions) but finishes its in-flight requests and
        # streams before retirement.  Least-loaded replicas drain
        # first so the fewest streams ride out a drain window.
        now = time.monotonic()
        fresh_running = [r for r in self.replicas
                         if r.state == RUNNING
                         and r.version == self.target_version]
        excess = len(fresh_running) - self.target_num_replicas
        if excess > 0:
            if self.deleting:
                # Deployment deletion: the owner asked for it to go —
                # graceful stop (bounded by graceful_shutdown_timeout_s)
                # rather than a long admission-less drain.
                for r in fresh_running[:excess]:
                    r.begin_stop(cfg.graceful_shutdown_timeout_s)
            else:
                def _load_key(r):
                    load = r.poll_load(now)
                    return load.get("ongoing", 0) if load else 0
                victims = sorted(fresh_running, key=_load_key)[:excess]
                survivors = [r for r in fresh_running
                             if r not in victims]
                for r in victims:
                    r.begin_drain(now, cfg.drain_timeout_s)
                    if survivors and _cfg.serve_affinity:
                        # Re-home the drained replica's hot KV pages on
                        # the least-loaded survivor so its cached
                        # prefixes outlive the scale-down.
                        r.offer_kv_migration(
                            min(survivors, key=_load_key))

        # 4. Health checks on running replicas (periodic, non-blocking).
        now = time.monotonic()
        if now - self._last_health_check > cfg.health_check_period_s:
            self._last_health_check = now
            # DRAINING replicas are health-checked too: one that DIES
            # mid-drain must be reaped now, not after the full drain
            # timeout expires against a corpse.
            for r in [x for x in self.replicas
                      if x.state in (RUNNING, DRAINING)]:
                if not r.poll_health(now):
                    logger.warning("replica %s unhealthy; replacing",
                                   r.replica_tag)
                    r.state = STOPPING
                    r.check_stopped()
                    if r in self.replicas:
                        self.replicas.remove(r)

        # The affinity digest rides the load sample the AUTOSCALER
        # polls — but a fixed-replica deployment has no autoscaler, so
        # poll here too or its digests would never leave the replicas.
        # Non-blocking with at most one outstanding probe per replica,
        # same cost profile as the autoscale path.
        if _cfg.serve_affinity:
            for r in self.replicas:
                if r.state == RUNNING:
                    r.poll_load(now)

        # 5. Broadcast the running-replica set on change (a DRAINING
        # replica's exclusion here IS the "stop admitting" edge).
        DRAINING_GAUGE.set(
            sum(r.state == DRAINING for r in self.replicas),
            tags={"deployment": self.name})
        infos = [r.running_info() for r in self.replicas
                 if r.state == RUNNING]
        fingerprint: Any = sorted((i["replica_tag"], i["version"])
                                  for i in infos)
        # The affinity digests ride the same broadcast, but re-notifying
        # every router each time any replica touches any prefix would
        # turn the long-poll into a firehose: fold the digests into the
        # fingerprint at most once per serve_affinity_refresh_s —
        # membership changes still broadcast instantly, digest drift is
        # batched (stale affinity only costs a suboptimal pick).
        if _cfg.serve_affinity:
            now_b = time.monotonic()
            if now_b - self._digest_fp_t >= _cfg.serve_affinity_refresh_s:
                self._digest_fp_t = now_b
                self._digest_fp = sorted(
                    (i["replica_tag"],
                     tuple(sorted(
                         r.get("fp", "") for r in
                         (i.get("kv_digest") or {}).get("roots", ()))))
                    for i in infos)
            fingerprint = (fingerprint, self._digest_fp)
        if fingerprint != self._last_broadcast:
            self._last_broadcast = fingerprint
            self._long_poll.notify_changed(
                f"replicas::{self.name}", infos)

        pending = bool(
            [r for r in self.replicas
             if r.state != RUNNING]) or self.target_num_replicas != len(
            [r for r in self.replicas if r.state == RUNNING])
        return pending

    def curr_status(self) -> Dict:
        by_state: Dict[str, int] = {}
        for r in self.replicas:
            by_state[r.state] = by_state.get(r.state, 0) + 1
        healthy = (not self.deleting
                   and by_state.get(RUNNING, 0) == self.target_num_replicas
                   and by_state.get(STARTING, 0) == 0
                   and by_state.get(DRAINING, 0) == 0
                   and by_state.get(STOPPING, 0) == 0)
        status = "HEALTHY" if healthy else \
            ("DELETING" if self.deleting else "UPDATING")
        if self.deploy_failed:
            status = "DEPLOY_FAILED"
        return {"name": self.name, "version": self.target_version,
                "target_num_replicas": self.target_num_replicas,
                "replica_states": by_state,
                "status": status}


class DeploymentStateManager:
    """All deployments (reference: deployment_state.py:1567)."""

    def __init__(self, long_poll_host):
        self._long_poll = long_poll_host
        self._deployments: Dict[str, DeploymentState] = {}

    def deploy(self, name: str, config: DeploymentConfig,
               replica_config: ReplicaConfig, version: str,
               route_prefix: str = None):
        ds = self._deployments.get(name)
        if ds is None:
            ds = self._deployments[name] = DeploymentState(
                name, self._long_poll)
        ds.route_prefix = route_prefix or f"/{name}"
        ds.deploy(config, replica_config, version)
        self._broadcast_routes()

    def delete(self, name: str):
        ds = self._deployments.get(name)
        if ds is not None:
            ds.delete()
        self._broadcast_routes()

    def _broadcast_routes(self):
        # Route table: URL prefix -> deployment (reference: the proxy's
        # route_prefix matching).
        self._long_poll.notify_changed(
            "routes", {getattr(ds, "route_prefix", f"/{name}"): name
                       for name, ds in self._deployments.items()
                       if not ds.deleting})

    def update(self) -> bool:
        pending = False
        for name, ds in list(self._deployments.items()):
            pending |= ds.update()
            if ds.deleting and not ds.replicas:
                del self._deployments[name]
        return pending

    def get(self, name: str) -> Optional[DeploymentState]:
        return self._deployments.get(name)

    def statuses(self) -> List[Dict]:
        return [ds.curr_status() for ds in self._deployments.values()]
