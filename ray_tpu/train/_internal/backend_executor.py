"""BackendExecutor: drives the WorkerGroup through a training run.

Reference: python/ray/train/_internal/backend_executor.py:42 (start :92,
start_training :274) — create the gang, run Backend setup hooks, launch
the user loop everywhere, then stream per-round results back.

Elastic mode (ScalingConfig.elastic): a member death observed here (or
a resize request) triggers an IN-PLACE re-formation through
train/elastic.py — survivors rendezvous a fresh collective group at
the new world size, re-shard in-memory state over the collective data
plane, and the result pump resumes against the re-formed gang.  A cold
gang restart (``restart``) remains the fallback when survivors drop
below quorum or the re-shard itself fails; only cold restarts consume
FailureConfig.max_failures.
"""

from __future__ import annotations

import logging
import os
import random
import time
from typing import Callable, List, Optional, Tuple

import ray_tpu
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as cfg
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train.backend import BackendConfig
from ray_tpu.train._internal.worker_group import WorkerGroup
from ray_tpu.util.metrics import Counter

logger = logging.getLogger(__name__)

# Elastic in-place recoveries vs cold gang restarts: distinct budgets,
# distinct counters (satellite: FailureConfig.max_failures counts only
# the cold path).
ELASTIC_RESIZES = Counter(
    "train_elastic_resizes_total",
    "Successful in-place elastic gang re-formations (member death "
    "absorbed or resize grant applied without a trial restart)")
GANG_RESTARTS = Counter(
    "train_gang_restarts_total",
    "Cold gang restarts from the last checkpoint (worker death without "
    "elastic mode, quorum loss, or a failed re-shard)")


class TrainingResult:
    def __init__(self, metrics: dict, checkpoint: Optional[Checkpoint]):
        self.metrics = metrics
        self.checkpoint = checkpoint


class TrainingFailedError(RuntimeError):
    pass


class TrainingWorkerError(TrainingFailedError):
    """A gang worker died from a SYSTEM fault (actor/node death), not a
    user-code exception — the gang can be restarted from the last
    checkpoint (reference: backend_executor.py:274 catching
    RayActorError into TrainingWorkerError for the retry loop)."""


class _ResizeRequested(Exception):
    """Internal: an elastic resize grant interrupted the result pump."""


def _is_worker_death(e: BaseException) -> bool:
    from ray_tpu._private import protocol
    from ray_tpu import exceptions as rexc
    from ray_tpu.util.collective.types import CollectiveGroupError
    if isinstance(e, CollectiveGroupError):
        # A surviving rank's collective op failed because the GANG
        # broke (member death aborts the group) — restartable, exactly
        # like observing the dead actor directly.  Checked before the
        # TaskError clause: remote errors multi-inherit both.
        return True
    if isinstance(e, rexc.TaskError):
        # A USER exception re-raised from the train loop (remote errors
        # multi-inherit TaskError + the original type) — even if the
        # original type is e.g. ConnectionError, restarts won't help.
        return False
    return isinstance(e, (rexc.ActorDiedError, rexc.ActorUnavailableError,
                          rexc.WorkerCrashedError, rexc.ObjectLostError,
                          protocol.ConnectionLost, ConnectionError))


class BackendExecutor:
    def __init__(self, backend_config: BackendConfig,
                 scaling_config: ScalingConfig):
        self.backend_config = backend_config
        self.backend = backend_config.backend_cls()
        self.scaling_config = scaling_config
        self.worker_group: Optional[WorkerGroup] = None
        self._pg = None
        self._collective_group: Optional[str] = None
        self._elastic = bool(getattr(scaling_config, "elastic", False)) \
            and scaling_config.num_workers > 1
        self._elastic_coord = None
        self._elastic_coord_name: Optional[str] = None
        self._gen = 0
        # Per-worker in-flight next_result refs: elasticity needs the
        # pump to know exactly which refs are outstanding so a
        # recovery can discard the interrupted round (a re-issued ref
        # would double-consume a survivor's report queue).
        self._pending: Optional[List[Tuple[object, object]]] = None
        self._joiners: List[Tuple[str, object, int]] = []
        self._resize_target: Optional[int] = None
        self._train_args: Optional[tuple] = None
        # PG bundle indices handed back to the cluster by an elastic
        # shrink; a later grow re-reserves them (two-phase, via GCS)
        # before spawning joiners into them.
        self._released_bundles: set = set()
        # Cluster-autopilot registration (one gang == one broker
        # workload): a daemon agent reports size/demand every
        # autopilot_report_period_s and applies broker-initiated
        # resize grants through request_elastic_resize — the same
        # entry point the driver and `rt resize` use.
        gname = getattr(scaling_config, "name", None) \
            or f"gang-{os.urandom(3).hex()}"
        self._gang_name = gname
        self._autopilot_wid = f"train:{gname}"
        self._autopilot_thread = None
        self._autopilot_stop = None
        # True while the broker (not a member death) shrank us: only
        # then does a restored grant auto-grow the gang back — a death
        # never triggers a surprise self-heal grow.
        self._broker_shrunk = False
        # An explicit operator directive (rt resize) pins the reported
        # demand at its target; otherwise the grow-back logic would
        # treat the broker's still-full grant as a signal to undo the
        # operator's shrink on the very next report.
        self._want_override: Optional[int] = None

    _placement_group = None

    def start(self, placement_group=None):
        """Idempotent: a retried start after a partial failure reuses the
        placement group and replaces any partially-created gang."""
        sc = self.scaling_config
        if self._placement_group is None:
            if placement_group is None:
                pgf = sc.as_placement_group_factory()
                self._pg = pgf.create()
                ok = ray_tpu.wait_placement_group_ready(self._pg,
                                                        timeout=120)
                if not ok:
                    raise TrainingFailedError(
                        "train worker gang PG not ready")
                placement_group = self._pg
            self._placement_group = placement_group
        if self.worker_group is not None:
            self.worker_group.shutdown()
            self.worker_group = None
        self._start_workers()

    # ------------------------------------------------------ gcs helpers
    def _pg_id(self):
        return getattr(self._placement_group, "id", None)

    @staticmethod
    def _gcs(method: str, body: dict):
        from ray_tpu._private.worker import global_worker
        return global_worker.gcs_call(method, body)

    def _start_workers(self):
        from ray_tpu.train import elastic as _elastic
        sc = self.scaling_config
        if self._released_bundles and self._pg_id() is not None:
            # Cold restart after a shrink: the full-size gang respawns
            # into bundles 0..N-1, so released ones must be re-reserved
            # first (best effort — a failed reacquire surfaces as the
            # restart's own placement failure).
            try:
                self._gcs("reacquire_bundles", {
                    "pg_id": self._pg_id(),
                    "indices": sorted(self._released_bundles)})
            except Exception:
                pass
            self._released_bundles.clear()
        self._destroy_collective_group()
        _elastic.kill_elastic_coordinator(self._elastic_coord_name)
        self._elastic_coord = self._elastic_coord_name = None
        self._gen = 0
        self._pending = None
        self._joiners = []
        self._resize_target = None
        self._want_override = None
        # One root a gang's start: every worker's creation task is
        # submitted under it, so the raylet's wait for each worker
        # (raylet.worker_start), the worker's boot and its chip's
        # opening (jax.backend_init) link here.
        with _tracing.span("train", "train.worker_group_start",
                           args={"workers": sc.num_workers}, root=True):
            self._start_gang(sc)

    def _start_gang(self, sc):
        self.worker_group = WorkerGroup(
            sc.num_workers, sc._resources, self._placement_group)
        # A gang-wide host collective group for data-parallel gradient
        # / histogram sync (util.collective on the transfer plane).
        # Named per incarnation so a gang restart gets a fresh
        # coordinator instead of colliding with the dead one's name.
        group = None
        if sc.num_workers > 1:
            group = f"train_dp_{os.urandom(4).hex()}"
        try:
            # Rank/world env everywhere (reference: rank env wiring in
            # backend_executor._setup_gang).  All workers in flight at
            # once; a per-worker get() would serialize N round trips.
            env = {
                "RT_TRAIN_WORLD_SIZE": sc.num_workers,
            }
            if group is not None:
                env["RT_TRAIN_COLLECTIVE_GROUP"] = group
            if self._elastic:
                name, coord = _elastic.create_elastic_coordinator()
                self._elastic_coord_name, self._elastic_coord = \
                    name, coord
                env["RT_TRAIN_ELASTIC_COORD"] = name
            ray_tpu.get(
                [w.set_env.remote(dict(env, RT_TRAIN_WORLD_RANK=rank,
                                       RT_TRAIN_LOCAL_RANK=rank))
                 for rank, w in enumerate(self.worker_group.workers)],
                timeout=120)
            if group is not None:
                from ray_tpu.util import collective as col
                col.create_collective_group(
                    self.worker_group.workers, sc.num_workers,
                    list(range(sc.num_workers)), group_name=group)
                self._collective_group = group
            self.backend.on_start(self.worker_group, self.backend_config)
        except Exception as e:
            if _is_worker_death(e):
                raise TrainingWorkerError(str(e)) from e
            raise

    def _destroy_collective_group(self):
        if self._collective_group is None:
            return
        try:
            from ray_tpu.util import collective as col
            col.destroy_collective_group(self._collective_group)
        except Exception:
            pass
        self._collective_group = None

    def restart(self):
        """Gang-level COLD fault recovery: tear the (partially dead)
        gang down and start a fresh one in the same placement group.
        The backend's on_start runs again on the new incarnation, so
        the jax coordination service re-initializes with a fresh
        coordinator (SURVEY hard-part #4: collective rendezvous
        lifecycle tied to actor restarts).  Reference: backend_executor
        start/shutdown around worker failures.  This is the path that
        consumes FailureConfig.max_failures; elastic re-forms do not
        pass through here."""
        GANG_RESTARTS.inc()
        if self.worker_group is not None:
            self.worker_group.shutdown()
            self.worker_group = None
        self._start_workers()

    def start_training(self, train_fn: Callable, config: dict,
                       checkpoint: Optional[Checkpoint] = None,
                       trial_name: str = "", trial_id: str = ""):
        self.backend.on_training_start(self.worker_group,
                                       self.backend_config)
        mesh_builder = getattr(self.backend, "mesh_builder", lambda: None)()
        # Joiners spawned by an elastic resize re-run the same entry
        # point (their rank/shards come from the reform instructions).
        self._train_args = (train_fn, config, checkpoint, trial_name,
                            trial_id, mesh_builder)
        refs = [
            w.start_training.remote(
                train_fn, config, checkpoint, trial_name, trial_id,
                mesh_builder)
            for w in self.worker_group.workers
        ]
        try:
            ray_tpu.get(refs, timeout=cfg.train_start_timeout_s)
        except Exception as e:
            if _is_worker_death(e):
                raise TrainingWorkerError(str(e)) from e
            raise
        self._start_autopilot_agent()

    # ------------------------------------------------- autopilot agent
    def _autopilot_decl(self, live: int) -> dict:
        sc = self.scaling_config
        return {"kind": "train",
                "priority": int(getattr(sc, "priority", 50)),
                "min_units": self._quorum() if self._elastic else live,
                "max_units": (self.worker_group.capacity
                              if self.worker_group is not None
                              else sc.num_workers),
                "elastic": self._elastic}

    def _start_autopilot_agent(self):
        import threading
        if self._autopilot_thread is not None:
            return
        self._autopilot_stop = threading.Event()
        self._autopilot_thread = threading.Thread(
            target=self._autopilot_agent_loop, daemon=True,
            name=f"rt-gang-agent-{self._gang_name}")
        self._autopilot_thread.start()

    def _autopilot_agent_loop(self):
        """Report the gang to the GCS broker and apply its resize
        grants.  Trains always *want* their full declared size back, so
        a grant moving away from the live size is the broker speaking:
        below live = reclaim (shrink through the re-form path), back
        above live = the spike drained (grow, but ONLY when the broker
        itself did the shrinking — a member death never triggers a
        surprise self-heal grow from here).  Explicit `rt resize`
        directives ride the same reply and always apply."""
        stop = self._autopilot_stop
        while not stop.wait(cfg.autopilot_report_period_s):
            try:
                wg = self.worker_group
                if wg is None or not wg.workers:
                    continue
                live = len(wg.workers)
                want = (self._want_override
                        if self._want_override is not None
                        else wg.capacity)
                reply = self._gcs("arbiter_report", {
                    "wid": self._autopilot_wid,
                    "want": want, "units_now": live,
                    "decl": self._autopilot_decl(live)})
                if not isinstance(reply, dict) or not reply.get("ok"):
                    continue
                target = reply.get("directive")
                from_directive = target is not None
                if target is None and self._elastic:
                    granted = int(reply.get("granted", live))
                    if granted < live:
                        target = granted
                    elif granted > live and self._broker_shrunk:
                        target = min(granted, wg.capacity)
                if target is None:
                    continue
                target = int(target)
                if (not self._elastic or target == live
                        or self._train_args is None
                        or self._resize_target is not None
                        or target < self._quorum()
                        or target > wg.capacity):
                    continue
                self.request_elastic_resize(target)
                if from_directive:
                    self._want_override = (target
                                           if target < wg.capacity
                                           else None)
                else:
                    # Still below full declared size => the broker owns
                    # the deficit and a later grant may grow us further.
                    # (`target < live` would clear the flag on a PARTIAL
                    # grow — e.g. 2 -> 3 of 4 while serve releases nodes
                    # one cooldown at a time — stranding the gang below
                    # capacity with no one willing to grow it.)
                    self._broker_shrunk = target < wg.capacity
            except Exception:
                logger.debug("autopilot gang agent iteration failed",
                             exc_info=True)

    def _stop_autopilot_agent(self):
        if self._autopilot_stop is not None:
            self._autopilot_stop.set()
        if self._autopilot_thread is not None:
            self._autopilot_thread.join(timeout=2.0)
            self._autopilot_thread = None
        try:
            self._gcs("arbiter_unregister", {"wid": self._autopilot_wid})
        except Exception:
            pass

    # ------------------------------------------------------- result pump
    def _get_refs(self, refs, deadline):
        """Blocking get.  Elastic mode waits in short slices so a
        resize request (posted from another thread) interrupts the
        pump instead of riding out the full round deadline."""
        if not self._elastic:
            return ray_tpu.get(refs, timeout=cfg.train_result_timeout_s)
        while True:
            if self._resize_target is not None:
                raise _ResizeRequested()
            remain = deadline - time.monotonic()
            if remain <= 0:
                raise ray_tpu.exceptions.GetTimeoutError(
                    "train report round timed out")
            try:
                return ray_tpu.get(refs, timeout=min(1.0, remain))
            except ray_tpu.exceptions.GetTimeoutError:
                continue

    @staticmethod
    def _is_flush(item) -> bool:
        from ray_tpu.train import elastic
        return (isinstance(item, (tuple, list)) and len(item) == 2
                and item[0] == elastic.FLUSH)

    def _acquire_round(self):
        """One full round of next_result values, with post-reform flush
        markers (elastic.FLUSH) skipped: a marker slot re-polls that
        worker alone, so the real reports stay aligned across ranks."""
        deadline = time.monotonic() + cfg.train_result_timeout_s
        raw = list(self._get_refs([r for _, r in self._pending],
                                  deadline))
        i = 0
        while i < len(raw):
            if self._is_flush(raw[i]):
                w, _ = self._pending[i]
                nref = w.next_result.remote()
                self._pending[i] = (w, nref)
                raw[i] = self._get_refs([nref], deadline)[0]
            else:
                i += 1
        return raw

    def get_next_results(self) -> Optional[List[TrainingResult]]:
        """One report round from every rank; None when the loop finished.
        All ranks must report the same number of times (reference enforces
        the same invariant)."""
        while True:
            if self._pending is None:
                self._pending = [(w, w.next_result.remote())
                                 for w in self.worker_group.workers]
            try:
                raw = self._acquire_round()
            except _ResizeRequested:
                self._elastic_recover(None)
                continue
            except Exception as e:
                if self._elastic and _is_worker_death(e):
                    # In-place re-formation: survivors rendezvous the
                    # new world size; the interrupted round is
                    # discarded (every rank re-reports from the
                    # authoritative step after the re-shard).  Raises
                    # TrainingWorkerError itself when the re-form
                    # can't complete (quorum, deadline, re-shard
                    # failure) — the cold-restart path.
                    self._elastic_recover(e)
                    continue
                if _is_worker_death(e):
                    raise TrainingWorkerError(str(e)) from e
                raise TrainingFailedError(str(e)) from e
            self._pending = None
            finished = [r is None for r in raw]
            if all(finished):
                return None
            if any(finished):
                raise TrainingFailedError(
                    "ranks reported unevenly (some finished, some "
                    "reported)")
            return [TrainingResult(m, c) for (m, c) in raw]

    # --------------------------------------------------- elastic re-form
    def request_elastic_resize(self, target_world_size: int):
        """Resize the gang to ``target_world_size`` in place.  The
        driver, `rt resize <gang> <n>`, and the autopilot broker all
        land here.

        Grow: spawn joiners into free placement-group bundles
        (re-reserving any a previous shrink released), then break the
        current incarnation so survivors and joiners rendezvous the new
        world size together; joiners receive the authoritative state
        over the collective plane like any recovering member.

        Shrink: mark the target and break the incarnation — the re-form
        path retires the highest ranks (clean StopIteration exit, no
        failure budget consumed), kills their actors, and releases
        their bundles so the freed nodes really return to the cluster.
        Thread-safe against a pump blocked in get_next_results."""
        if not self._elastic:
            raise RuntimeError("elastic resize requires "
                               "ScalingConfig(elastic=True)")
        wg = self.worker_group
        if wg is None or self._train_args is None:
            raise RuntimeError("no running gang to resize")
        live = len(wg.workers)
        target_world_size = int(target_world_size)
        if target_world_size == live:
            raise ValueError(f"gang is already at world size {live}")
        if target_world_size < live:
            if target_world_size < self._quorum():
                raise ValueError(
                    f"target world size {target_world_size} is below "
                    f"the elastic quorum floor {self._quorum()}")
            self._resize_target = target_world_size
            if self._collective_group is not None:
                from ray_tpu.util import collective as col
                col.abort_collective_group(self._collective_group,
                                           "elastic shrink")
            return
        free = [i for i in range(wg.capacity)
                if i not in wg.bundle_indices]
        need = target_world_size - live
        if need > len(free):
            raise ValueError(
                f"resize to {target_world_size} needs {need} bundles "
                f"but only {len(free)} are free (gang capacity "
                f"{wg.capacity})")
        reacquire = [i for i in free[:need]
                     if i in self._released_bundles]
        if reacquire and self._pg_id() is not None:
            try:
                r = self._gcs("reacquire_bundles", {
                    "pg_id": self._pg_id(), "indices": reacquire})
            except Exception as e:
                raise ValueError(
                    f"cannot grow to {target_world_size}: bundle "
                    f"re-reservation RPC failed ({e})") from e
            got = set(r.get("reacquired", ())) if isinstance(r, dict) \
                else set()
            self._released_bundles -= got
            missing = [i for i in reacquire if i not in got]
            if missing:
                raise ValueError(
                    f"cannot grow to {target_world_size}: released "
                    f"bundles {missing} could not be re-reserved "
                    f"(capacity taken by another workload; retry on a "
                    f"later grant)")
        (train_fn, config, checkpoint, trial_name, trial_id,
         mesh_builder) = self._train_args
        # The joiner handshake must stay bounded well below the
        # broker's stale-report window: this path runs on the autopilot
        # agent thread, and a wedged joiner that blocks it past the
        # window gets the gang's registration GC'd out from under a
        # live gang (its budget returns to the pool and data soaks the
        # slots).  On any failure kill everything spawned this attempt
        # so the next grant retries from a clean slate.
        spawned = []
        try:
            for k in range(need):
                w = wg._spawn(live + k, free[k], target_world_size)
                spawned.append(("j" + os.urandom(3).hex(), w, free[k]))
                env = {"RT_TRAIN_ELASTIC_COORD":
                       self._elastic_coord_name,
                       "RT_TRAIN_ELASTIC_TOKEN": spawned[-1][0],
                       "RT_TRAIN_ELASTIC_GEN": self._gen,
                       "RT_TRAIN_WORLD_SIZE": target_world_size,
                       "RT_TRAIN_WORLD_RANK": live + k,
                       "RT_TRAIN_LOCAL_RANK": live + k}
                ray_tpu.get(w.set_env.remote(env),  # noqa: RTL001
                            timeout=10)
                ray_tpu.get(  # noqa: RTL001
                    w.start_training.remote(train_fn, config,
                                            checkpoint, trial_name,
                                            trial_id, mesh_builder,
                                            True),
                    timeout=min(10.0, cfg.train_start_timeout_s))
        except Exception as e:
            for (_, w, _) in spawned:
                try:
                    ray_tpu.kill(w)
                except Exception:
                    pass
            raise ValueError(
                f"cannot grow to {target_world_size}: joiner "
                f"handshake failed ({e}); retry on a later "
                f"grant") from e
        self._joiners.extend(spawned)
        self._resize_target = target_world_size
        # Break the running incarnation: every survivor's next
        # collective op (or parked report, via the worker agents) drops
        # into the rejoin path.
        if self._collective_group is not None:
            from ray_tpu.util import collective as col
            col.abort_collective_group(self._collective_group,
                                       "elastic resize")

    def _quorum(self) -> int:
        sc = self.scaling_config
        q = getattr(sc, "elastic_min_workers", None)
        if q is None:
            q = cfg.train_elastic_min_workers
        return max(1, int(q))

    def _reform_fail(self, msg: str, err):
        # Release workers parked in wait_reform before falling back.
        try:
            ray_tpu.get(self._elastic_coord.post_reform.remote(
                {"gen": self._gen + 1, "action": "abort",
                 "reason": msg}), timeout=10)
        except Exception:
            pass
        logger.warning("elastic re-form failed (%s); falling back to "
                       "cold checkpoint restart", msg)
        e = TrainingWorkerError(f"elastic re-form failed: {msg}")
        if err is not None:
            raise e from err
        raise e

    def _elastic_recover(self, err):
        """Driver side of one re-formation (train/elastic.py protocol).
        On success the pump continues against the re-formed gang; on
        quorum loss / deadline / re-shard failure raises
        TrainingWorkerError so the trainer's cold-restart loop takes
        over."""
        from ray_tpu.util import collective as col
        wg = self.worker_group
        old_workers = list(wg.workers)
        old_bundles = list(wg.bundle_indices)
        old_world = len(old_workers)
        gen = self._gen
        coord = self._elastic_coord
        timeout = cfg.train_reform_timeout_s
        deadline = time.monotonic() + timeout + random.uniform(
            0.0, max(0.0, cfg.train_reform_jitter_s))
        self._pending = None  # discard the interrupted round
        logger.warning(
            "train gang broke (%s); attempting elastic re-form "
            "(generation %s)", err, gen + 1)

        # Make sure every survivor breaks: abort the old group
        # (idempotent when the death watch already killed it) and
        # announce the recovery so worker agents unwind report-blocked
        # loops.
        if self._collective_group is not None:
            col.abort_collective_group(
                self._collective_group,
                "elastic re-form" if err is None else str(err))
        try:
            ray_tpu.get(coord.begin_recovery.remote(gen + 1), timeout=30)
        except Exception as e:
            self._reform_fail(f"elastic coordinator unreachable: {e}",
                              err)

        # Collect survivor breaks under the bounded deadline; a settle
        # window separates "everyone who can report has" from "one
        # straggler is still unwinding".
        settle = min(2.0, timeout / 5.0)
        last: dict = {}
        stable_since = time.monotonic()
        while True:
            now = time.monotonic()
            if now >= deadline:
                break
            try:
                b = ray_tpu.get(coord.breaks.remote(gen),  # noqa: RTL001
                                timeout=30)
            except Exception as e:
                self._reform_fail(f"break collection failed: {e}", err)
            if b != last:
                last, stable_since = b, now
            elif last and now - stable_since >= settle:
                break
            time.sleep(0.2)
        survivors = sorted(int(r) for r in last)
        joiners = list(self._joiners)
        if len(survivors) < self._quorum():
            self._reform_fail(
                f"{len(survivors)} survivors of {old_world} < quorum "
                f"{self._quorum()}", err)
        # Broker/driver shrink: retire the HIGHEST old ranks down to
        # the requested size (clamped to quorum — a resize directive
        # can never push the gang below its floor, even racing a
        # member death that already shrank the survivor set).
        retired: List[int] = []
        resize = self._resize_target
        if resize is not None:
            want = max(int(resize), self._quorum())
            if len(survivors) + len(joiners) > want:
                keep = max(want - len(joiners), 0)
                retired = survivors[keep:]
                survivors = survivors[:keep]
        new_world = len(survivors) + len(joiners)

        # Compact new ranks: survivors in old-rank order, then joiners.
        group = f"train_dp_{os.urandom(4).hex()}"
        gcoord = col.ensure_coordinator(group, new_world)
        ranks: dict = {}
        joiner_ranks: dict = {}
        mapping: dict = {}
        new_workers, new_bundles = [], []
        for new_rank, old_rank in enumerate(survivors):
            w = old_workers[old_rank]
            ranks[str(old_rank)] = new_rank
            new_workers.append(w)
            new_bundles.append(old_bundles[old_rank])
            aid = getattr(w, "_actor_id", None)
            if aid is not None:
                mapping[aid.hex()] = new_rank
        for k, (token, w, bidx) in enumerate(joiners):
            rank = len(survivors) + k
            joiner_ranks[token] = rank
            new_workers.append(w)
            new_bundles.append(bidx)
            aid = getattr(w, "_actor_id", None)
            if aid is not None:
                mapping[aid.hex()] = rank
        # Death watch BEFORE members register: a member dying
        # mid-re-shard aborts the new group fast (clean fallback, never
        # a torn state).
        try:
            ray_tpu.get(gcoord.watch.remote(mapping), timeout=60)
        except Exception:
            logger.warning("could not arm death watch for re-formed "
                           "group '%s'", group, exc_info=True)
        instr = {"gen": gen + 1, "group": group,
                 "world_size": new_world, "ranks": ranks,
                 "joiners": joiner_ranks,
                 "retired": retired,
                 "dead_ranks": [r for r in range(old_world)
                                if r not in survivors
                                and r not in retired],
                 "old_world": old_world}
        try:
            ray_tpu.get(coord.post_reform.remote(instr), timeout=30)
        except Exception as e:
            self._reform_fail(f"posting reform failed: {e}", err)

        # Await every member's re-shard ack under its own window.
        done_deadline = time.monotonic() + timeout
        detail = "re-shard deadline expired"
        ok = False
        while time.monotonic() < done_deadline:
            try:
                st = ray_tpu.get(  # noqa: RTL001
                    coord.reform_status.remote(gen + 1), timeout=30)
            except Exception as e:
                detail = f"reform status poll failed: {e}"
                break
            bad = [f"rank {r}: {v[1]}" for r, v in st.items()
                   if not v[0]]
            if bad:
                detail = "; ".join(bad)
                break
            if len(st) == new_world:
                ok = True
                break
            time.sleep(0.2)
        if not ok:
            col.abort_collective_group(group, "re-form failed")
            self._reform_fail(detail, err)

        old_group, self._collective_group = \
            self._collective_group, group
        if old_group is not None:
            # Reap the broken incarnation's coordinator actor (members
            # already dropped their local halves during rejoin).
            try:
                col.destroy_collective_group(old_group)
            except Exception:
                pass
        wg.apply_reform(new_workers, new_bundles)
        self._joiners = []
        self._resize_target = None
        self._gen = gen + 1
        ELASTIC_RESIZES.inc()
        if retired:
            # Retired members exited their loops cleanly
            # (StopIteration in rejoin); reap the actors and hand
            # their bundles back so the freed CPU leaves the gang's
            # reservation and returns to the cluster pool.
            rel = []
            for old_rank in retired:
                try:
                    ray_tpu.kill(old_workers[old_rank])
                except Exception:
                    pass
                rel.append(old_bundles[old_rank])
            if self._pg_id() is not None:
                try:
                    r = self._gcs("release_bundles", {
                        "pg_id": self._pg_id(), "indices": rel})
                    if isinstance(r, dict):
                        self._released_bundles.update(
                            r.get("released", ()))
                except Exception:
                    logger.warning("bundle release after elastic "
                                   "shrink failed", exc_info=True)
        logger.warning(
            "elastic re-form complete: world %s -> %s (generation %s, "
            "dead ranks %s, %s joiners, %s retired)", old_world,
            new_world, gen + 1, instr["dead_ranks"],
            len(joiner_ranks), len(retired))

    def finish_training(self):
        if self.worker_group is not None:
            # Submit every shutdown first so they overlap; then drain
            # one by one to keep the per-worker exception isolation
            # (submission itself can raise during driver teardown).
            refs = []
            for w in self.worker_group.workers:
                try:
                    refs.append(w.shutdown_training.remote())
                except Exception:
                    pass
            for ref in refs:
                try:
                    ray_tpu.get(ref, timeout=30)
                except Exception:
                    pass

    def shutdown(self):
        from ray_tpu.train import elastic as _elastic
        self._stop_autopilot_agent()
        try:
            self.backend.on_shutdown(self.worker_group, self.backend_config)
        except Exception:
            pass
        self._destroy_collective_group()
        _elastic.kill_elastic_coordinator(self._elastic_coord_name)
        self._elastic_coord = self._elastic_coord_name = None
        if self.worker_group is not None:
            self.worker_group.shutdown()
            self.worker_group = None
        if self._pg is not None:
            try:
                from ray_tpu.util.placement_group import (
                    remove_placement_group)
                remove_placement_group(self._pg)
            except Exception:
                pass
            self._pg = None
