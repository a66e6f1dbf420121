"""Router: assigns queries to replicas, honoring max_concurrent_queries.

Reference: python/ray/serve/_private/router.py — Router (:262) +
ReplicaSet.assign_replica (:222): pick a replica with a free slot
(in-flight < max_concurrent_queries); if all are saturated, queue the
query until one frees.  Replica membership arrives via long poll.

Robustness layer (the multi-replica serving contract):

  * STREAM FAILOVER — every stream records resumable state (deployment,
    args, items delivered).  When the serving replica dies mid-stream
    the router re-submits on a healthy replica: resumable deployments
    (serve.resumable) get the delivered prefix passed back so only the
    REMAINING items are produced (greedy parity preserved; the prefix
    cache makes re-prefill cheap), non-resumable streams restart only
    if zero items were delivered.  Anything else fails fast with a
    structured StreamInterrupted carrying a resume cursor — never a
    silent hang (every stream RPC is deadline-bounded).
  * UNARY RETRY — a replica that dies before its first response is
    retried once on a DIFFERENT replica (zero bytes were delivered, so
    the retry is prefix-safe) instead of surfacing a raw
    ActorDiedError.
  * PER-TENANT QoS — with a TenantQoS policy installed, admission runs
    a per-tenant token bucket + queue cap (overload sheds with
    TenantThrottled → HTTP 429) and saturated-capacity waiting is
    weighted-fair across tenants instead of a free-for-all.

Saturation is observable: queue depth and in-flight counts are exported
as util.metrics gauges (serve_router_queue_depth / serve_router_in_flight
/ serve_replica_in_flight) so a saturated deployment shows up next to
the engine metrics instead of manifesting only as latency; failovers and
interruptions count in serve_stream_failovers_total /
serve_stream_interrupted_total.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import os
import random
import time
from typing import Any, AsyncIterator, Deque, Dict, List, Optional

from ray_tpu._private import failpoints
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
from ray_tpu.serve._private.long_poll import LongPollClient
from ray_tpu.serve._private.qos import DEFAULT_TENANT, TenantQoS
from ray_tpu.serve.exceptions import StreamInterrupted
from ray_tpu.util import metrics as _metrics

logger = logging.getLogger(__name__)

_worker_mod = None
_death_errs = None


def _core_worker():
    """The process's CoreWorker, with the module resolved once (lazy to
    dodge import cycles, cached to keep it off the per-request path)."""
    global _worker_mod
    if _worker_mod is None:
        from ray_tpu._private import worker as worker_mod
        _worker_mod = worker_mod
    return _worker_mod.global_worker


def _death_errors() -> tuple:
    """Exception types that mean THE REPLICA is gone (vs the request
    failing inside healthy user code): actor death/unavailability and
    transport loss.  Resolved lazily to dodge import cycles."""
    global _death_errs
    if _death_errs is None:
        from ray_tpu import exceptions as rexc
        from ray_tpu._private import protocol
        _death_errs = (rexc.ActorDiedError, rexc.ActorUnavailableError,
                       protocol.ConnectionLost)
    return _death_errs


QUEUE_DEPTH_GAUGE = _metrics.Gauge(
    "serve_router_queue_depth",
    "Queries waiting in this process's router for a free replica slot",
    tag_keys=("deployment",))
IN_FLIGHT_GAUGE = _metrics.Gauge(
    "serve_router_in_flight",
    "Queries this process's router has in flight across all replicas",
    tag_keys=("deployment",))
REPLICA_IN_FLIGHT_GAUGE = _metrics.Gauge(
    "serve_replica_in_flight",
    "Queries this process's router has in flight per replica",
    tag_keys=("deployment", "replica"))
FAILOVER_COUNTER = _metrics.Counter(
    "serve_stream_failovers_total",
    "Streams re-submitted on a healthy replica after their replica died",
    tag_keys=("deployment",))
INTERRUPTED_COUNTER = _metrics.Counter(
    "serve_stream_interrupted_total",
    "Streams that failed structured (StreamInterrupted) after replica "
    "death with failover unavailable",
    tag_keys=("deployment",))
UNARY_RETRY_COUNTER = _metrics.Counter(
    "serve_unary_retries_total",
    "Unary calls retried on a different replica after actor death "
    "before first response",
    tag_keys=("deployment",))
AFFINITY_HITS_COUNTER = _metrics.Counter(
    "serve_kv_affinity_hits_total",
    "Assignments routed to a replica already holding a prefix of the "
    "request (prefix-affinity override of the load-based pick)",
    tag_keys=("deployment",))
AFFINITY_SCORE_GAUGE = _metrics.Gauge(
    "serve_router_affinity_score",
    "Blended affinity score of the last affinity-scored assignment "
    "(blend * hit-depth - (1-blend) * load; negative = load dominated)",
    tag_keys=("deployment",))

_QOS_FROM_ENV = "__env__"


class _UnaryResult:
    """Wrapper yielded (once) by assign_replica_stream(unary_fallback=
    True) when the target turned out not to stream: the deployment ran
    exactly once and this is its whole answer — the proxy formats it as
    a plain HTTP response instead of SSE."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value


class _Waiter:
    """One queued acquisition under QoS: resolved with the chosen
    replica info dict by the WFQ dispatcher."""

    __slots__ = ("fut", "tenant", "exclude", "tag", "hint")

    def __init__(self, fut, tenant: str, exclude: tuple, tag: float,
                 hint: Optional[Dict] = None):
        self.fut = fut
        self.tenant = tenant
        self.exclude = exclude
        self.tag = tag
        self.hint = hint


class ReplicaSet:
    """The live replicas of one deployment, with in-flight accounting.

    Hot-path detail: the saturation gauges are written through
    pre-resolved series handles (`Metric.series`) — one dict store per
    update instead of a tag merge + lock per call — and the unary call
    path resolves replica replies via the CoreWorker's ready-future
    fast path (no per-call coroutine on the IO loop, reply deserialized
    on this router's own thread)."""

    def __init__(self, deployment_name: str, loop,
                 qos: Any = _QOS_FROM_ENV):
        self.deployment_name = deployment_name
        self._loop = loop
        self._replicas: List[Dict] = []
        self._in_flight: Dict[str, int] = {}
        self._slot_freed = asyncio.Event()
        self.num_queued = 0
        self._g_queued = QUEUE_DEPTH_GAUGE.series(
            {"deployment": deployment_name})
        self._g_in_flight = IN_FLIGHT_GAUGE.series(
            {"deployment": deployment_name})
        self._g_replica: Dict[str, object] = {}
        self._num_in_flight = 0
        self._qos: Optional[TenantQoS] = (
            TenantQoS.from_env() if qos is _QOS_FROM_ENV else qos)
        self._waiters: Dict[str, Deque[_Waiter]] = {}
        env = os.environ.get
        self._stream_failover = env("RT_SERVE_STREAM_FAILOVER",
                                    "1") != "0"
        self._max_failovers = int(env("RT_SERVE_STREAM_MAX_FAILOVERS",
                                      "2"))
        self._unary_retry = env("RT_SERVE_UNARY_RETRY", "1") != "0"
        self._stream_poll_timeout = float(
            env("RT_SERVE_STREAM_POLL_TIMEOUT_S", "60"))
        self._suppress_ttl = float(
            env("RT_SERVE_REPLICA_SUPPRESS_S", "10"))
        self._suppressed: Dict[str, float] = {}
        self._late_cancels: set = set()   # _cancel_once_started's tasks
        # KV pull addresses this router has OBSERVED in the membership
        # broadcast: current members, plus recently-departed ones kept
        # for a grace window (a dead replica leaves the broadcast
        # before its client's resume retry arrives).  Client-replayed
        # kv_origin cursors are validated against these — see
        # _trusted_rdv.
        self._member_rdv: set = set()
        self._recent_rdv: Dict[tuple, float] = {}

    def _replica_series(self, tag: str):
        s = self._g_replica.get(tag)
        if s is None:
            s = self._g_replica[tag] = REPLICA_IN_FLIGHT_GAUGE.series(
                {"deployment": self.deployment_name, "replica": tag})
        return s

    @staticmethod
    def _rdv_key(rdv) -> Optional[tuple]:
        """Canonical (host, port, engine) key of a kv_rdv dict, or None
        when it isn't one (missing fields, junk types)."""
        try:
            return (str(rdv["host"]), int(rdv["port"]),
                    str(rdv.get("engine", "default")))
        except (TypeError, KeyError, ValueError):
            return None

    def _trusted_rdv(self, rdv) -> Optional[Dict]:
        """Validate a CLIENT-supplied kv_origin (x-rt-resume rides in
        from the open HTTP surface): only pull addresses this router has
        itself seen in the controller's membership broadcast — live now,
        or departed within serve_kv_rdv_grace_s — are honored, and the
        returned dict is rebuilt from the canonical key (no smuggled
        fields).  Anything else is dropped: a forged cursor must not be
        able to point a replica's migration pull at an attacker-chosen
        endpoint (SSRF) or seed the shared prefix cache from bytes an
        attacker serves (cache poisoning).  Dropping is safe — the
        resume simply re-prefills."""
        key = self._rdv_key(rdv) if isinstance(rdv, dict) else None
        if key is None:
            return None
        if key in self._member_rdv or \
                self._recent_rdv.get(key, 0.0) > time.monotonic():
            return {"host": key[0], "port": key[1], "engine": key[2]}
        logger.warning(
            "dropping kv_origin %s:%s from resume cursor: not a pull "
            "address this router observed in %s's membership",
            rdv.get("host"), rdv.get("port"), self.deployment_name)
        return None

    def update_replicas(self, infos: List[Dict]):
        self._replicas = list(infos)
        now = time.monotonic()
        member = set()
        for i in infos:
            key = self._rdv_key(i.get("kv_rdv"))
            if key is not None:
                member.add(key)
        for gone in self._member_rdv - member:
            self._recent_rdv[gone] = now + _cfg.serve_kv_rdv_grace_s
        for key, deadline in list(self._recent_rdv.items()):
            if deadline <= now or key in member:
                del self._recent_rdv[key]
        self._member_rdv = member
        tags = {i["replica_tag"] for i in infos}
        for gone in set(self._in_flight) - tags:
            # Zero the departed replica's series: its finally-block
            # decrement is skipped once the tag is dropped, and a
            # stale nonzero gauge would misreport saturation forever.
            self._replica_series(gone).set(0)
            self._g_replica.pop(gone, None)
        self._in_flight = {t: self._in_flight.get(t, 0) for t in tags}
        self._num_in_flight = sum(self._in_flight.values())
        self._g_in_flight.set(self._num_in_flight)
        self._slot_freed.set()  # membership change may free capacity
        self._dispatch_waiters()

    def _drop_replica(self, tag: str):
        """Suppress a replica the router just observed dying so no new
        work lands on it during the window before the controller's
        membership broadcast confirms the death.  Suppression is a
        bounded TTL, not removal: the long-poll only re-delivers
        membership when the controller's fingerprint CHANGES, so
        removing a replica the controller still considers RUNNING
        (death mis-classified — a transient stall or injected fault)
        would shrink this router's capacity forever.  A really-dead
        replica leaves the broadcast within the health-check period,
        well inside the TTL renewal from its next failed call."""
        self._suppressed[tag] = \
            asyncio.get_event_loop().time() + self._suppress_ttl
        logger.warning(
            "replica %s of %s suppressed in local view for %.0fs "
            "(died mid-call); awaiting controller broadcast",
            tag, self.deployment_name, self._suppress_ttl)

    def _set_queued(self, delta: int):
        self.num_queued += delta
        self._g_queued.set(self.num_queued)

    def _track_in_flight(self, tag: str, delta: int):
        n = self._in_flight[tag] = self._in_flight.get(tag, 0) + delta
        self._num_in_flight += delta
        self._g_in_flight.set(self._num_in_flight)
        self._replica_series(tag).set(n)

    def _release(self, tag: str):
        """Give back one in-flight unit and wake whoever is waiting for
        capacity (the legacy event loop AND the QoS dispatcher).
        Floor at zero: a replica that left and re-entered the broadcast
        (drain -> un-drain) had its count reset while old streams still
        held slots; their releases must not drive the count negative
        and mint phantom capacity forever."""
        if self._in_flight.get(tag, 0) > 0:
            self._track_in_flight(tag, -1)
        self._slot_freed.set()
        self._dispatch_waiters()

    # -------------------------------------------------- slot acquisition
    async def _acquire(self, timeout_s: float, tenant: str = None,
                       exclude: tuple = (), admit: bool = True,
                       hint: Optional[Dict] = None) -> Dict:
        """Wait (bounded) for a replica with a free slot; the caller owns
        one in-flight unit on the returned replica and must release it
        via _release(tag).  With a QoS policy installed, admission runs
        the per-tenant token bucket + queue cap and waiting is
        weighted-fair across tenants.  `admit=False` skips the
        admission gate (WFQ ordering still applies): retries and
        failovers of an ALREADY-ADMITTED request must neither burn a
        second bucket token nor convert a replica death into a 429."""
        t0 = time.time()
        if self._qos is not None:
            choice = await self._acquire_qos(timeout_s, tenant, exclude,
                                             admit, hint)
            self._record_wait(t0, time.time(), tenant, choice)
            return choice
        deadline = time.monotonic() + timeout_s
        self._set_queued(+1)
        try:
            while True:
                choice = self._pick(exclude, hint)
                if choice is not None:
                    break
                remain = deadline - time.monotonic()
                if remain <= 0:
                    raise RuntimeError(
                        f"no available replica for deployment "
                        f"{self.deployment_name!r} within {timeout_s}s")
                self._slot_freed.clear()
                try:
                    await asyncio.wait_for(self._slot_freed.wait(),
                                           timeout=min(remain, 5.0))
                except asyncio.TimeoutError:
                    pass  # re-check membership; maybe replicas arrived
        finally:
            self._set_queued(-1)
        self._track_in_flight(choice["replica_tag"], +1)
        self._record_wait(t0, time.time(), tenant, choice)
        return choice

    def _record_wait(self, t0: float, t1: float, tenant, choice):
        """serve.qos_wait span: time a request spent waiting for a
        replica slot (QoS admission + WFQ, or the legacy capacity
        wait).  Linked under the caller's span (the proxy's
        serve.request or a handle caller's context)."""
        _tracing.record("serve", "serve.qos_wait", t0, t1 - t0,
                        trace=_tracing.child_span(),
                        args={"deployment": self.deployment_name,
                              "tenant": tenant or "default",
                              "replica": choice["replica_tag"]})

    async def _acquire_qos(self, timeout_s: float, tenant: str,
                           exclude: tuple, admit: bool = True,
                           hint: Optional[Dict] = None) -> Dict:
        tenant = tenant or DEFAULT_TENANT
        dq = self._waiters.get(tenant)
        if dq:
            while dq and dq[0].fut.done():
                dq.popleft()
        if admit:
            # Count only LIVE waiters toward the cap: a timed-out/
            # cancelled waiter stranded mid-deque (behind a live head)
            # must not shed new requests with a phantom queue_full.
            queued_now = sum(1 for x in dq
                             if not x.fut.done()) if dq else 0
            self._qos.admit(self.deployment_name, tenant, queued_now)
        loop = asyncio.get_running_loop()
        w = _Waiter(loop.create_future(), tenant, tuple(exclude or ()),
                    self._qos.start_tag(tenant), hint)
        self._waiters.setdefault(
            tenant, collections.deque()).append(w)
        self._set_queued(+1)
        loop_time = loop.time
        deadline = loop_time() + timeout_s
        try:
            self._dispatch_waiters()
            while True:
                remain = deadline - loop_time()
                if remain <= 0:
                    self._abandon_waiter(w)
                    raise RuntimeError(
                        f"no available replica for deployment "
                        f"{self.deployment_name!r} within {timeout_s}s")
                try:
                    # Shielded sub-waits (<=5s): the periodic wake
                    # re-runs the dispatcher because capacity can
                    # reappear WITHOUT any release/broadcast event —
                    # e.g. a replica's death-suppression TTL expiring.
                    return await asyncio.wait_for(
                        asyncio.shield(w.fut), min(remain, 5.0))
                except asyncio.TimeoutError:
                    self._dispatch_waiters()
                except BaseException:
                    # Caller cancelled / generator closed (GeneratorExit
                    # reaches here too) — propagate, but never leave a
                    # live waiter behind for the dispatcher to hand a
                    # slot nobody will consume, and never leak a slot
                    # assigned in the race.
                    self._abandon_waiter(w)
                    raise
        finally:
            self._set_queued(-1)

    def _abandon_waiter(self, w: "_Waiter"):
        """A waiter whose wait died (deadline, cancellation, generator
        close) may ALREADY have been handed a slot by the dispatcher in
        the same loop tick — hand it straight back instead of leaking
        it against max_concurrent_queries forever.  A still-pending
        waiter is cancelled so the dispatcher prunes it instead of
        assigning a slot nobody will consume."""
        if w.fut.done() and not w.fut.cancelled() \
                and w.fut.exception() is None:
            self._release(w.fut.result()["replica_tag"])
        elif not w.fut.done():
            w.fut.cancel()

    def _dispatch_waiters(self):
        """Match queued waiters to free replica slots in WFQ order
        (smallest virtual finish tag first).  Runs on the router loop
        whenever capacity may have appeared."""
        if self._qos is None or not self._waiters:
            return
        while True:
            heads: List[_Waiter] = []
            for tenant in list(self._waiters):
                dq = self._waiters[tenant]
                while dq and dq[0].fut.done():
                    dq.popleft()
                if not dq:
                    del self._waiters[tenant]
                else:
                    heads.append(dq[0])
            if not heads:
                return
            heads.sort(key=lambda x: x.tag)
            placed = False
            for w in heads:
                choice = self._pick(w.exclude, w.hint)
                if choice is None:
                    continue  # only excluded replicas free; try others
                dq = self._waiters.get(w.tenant)
                dq.popleft()
                if not dq:
                    del self._waiters[w.tenant]
                self._qos.dispatched(w.tag)
                self._track_in_flight(choice["replica_tag"], +1)
                w.fut.set_result(choice)
                placed = True
                break
            if not placed:
                return

    # ------------------------------------------------------- stream RPCs
    async def _stream_rpc(self, ref):
        """Await one streaming-transport RPC with a bounded deadline:
        a reply that outlives the bound (replica wedged behind a
        partition the keepalive hasn't condemned yet) is classified as
        replica unavailability, so the stream fails over or interrupts
        structured instead of hanging."""
        fut = asyncio.wrap_future(ref.future())
        if self._stream_poll_timeout <= 0:
            return await fut
        try:
            return await asyncio.wait_for(fut,
                                          self._stream_poll_timeout)
        except asyncio.TimeoutError:
            from ray_tpu import exceptions as rexc
            raise rexc.ActorUnavailableError(
                None, f"stream RPC gave no reply within "
                      f"{self._stream_poll_timeout}s") from None

    def _cancel_once_started(self, actor, start) -> None:
        """A stream whose consumer left while its start RPC was in
        flight: the reply that names the stream arrives after anyone
        waits for it, and a replica that never hears of the close runs
        the generator to its end for nobody (an engine request that
        holds a decode slot and its pages).  Wait for the reply off to
        the side and cancel the stream by the id it brings."""
        async def _cancel():
            try:
                started = await self._stream_rpc(start)
            except Exception:
                return      # never started, or its replica is gone
            if "stream_id" in started:
                actor.stream_cancel.options(num_returns=0).remote(
                    started["stream_id"])
        task = asyncio.ensure_future(_cancel())
        self._late_cancels.add(task)    # the loop keeps a weak ref only
        task.add_done_callback(self._late_cancels.discard)

    @staticmethod
    def _check_stream_failpoint():
        """`serve.stream_next` failpoint: deterministic chaos on the
        router→replica streaming leg (delay = slow link; error/
        disconnect = transport loss, which exercises the failover
        path)."""
        if not failpoints.ACTIVE:
            return None
        act = failpoints.check("serve.stream_next")
        if act is None:
            return None
        if act.kind == "delay":
            return act.delay_s
        from ray_tpu import exceptions as rexc
        raise rexc.ActorUnavailableError(
            None, f"failpoint: injected stream_next {act.kind}")

    async def assign_replica(self, method_name: str, args: tuple,
                             kwargs: dict,
                             timeout_s: float = 120.0,
                             tenant: str = None,
                             affinity: Optional[Dict] = None) -> Any:
        """Pick a replica (power-of-two-choices among free ones), send the
        query, and release the slot when it completes.  Bounded: a request
        that can't be assigned within timeout_s (no replicas — deployment
        deleted or all crashed) errors instead of hanging forever.  A
        replica that dies before its first response is retried ONCE on a
        different replica (zero bytes were delivered, so re-running is
        prefix-safe) instead of leaking a raw ActorDiedError.  NB this
        makes unary serve calls at-least-once across replica death —
        the replica may have executed before the connection died (same
        trade the task layer makes across restarts); deployments with
        non-idempotent side effects can opt out via
        RT_SERVE_UNARY_RETRY=0."""
        exclude: tuple = ()
        attempt = 0
        while True:
            choice = await self._acquire(timeout_s, tenant=tenant,
                                         exclude=exclude,
                                         admit=attempt == 0,
                                         hint=affinity)
            tag = choice["replica_tag"]
            span_args = {"deployment": self.deployment_name,
                         "replica": tag, "attempt": attempt}
            if choice.get("_affinity"):
                span_args["affinity"] = choice["_affinity"]
            try:
                try:
                    with _tracing.span(
                            "serve", "serve.assign", args=span_args):
                        return await self._call_unary(
                            choice, method_name, args, kwargs)
                except _death_errors() as e:
                    self._drop_replica(tag)
                    if attempt == 0 and self._unary_retry:
                        attempt = 1
                        exclude = (tag,)
                        UNARY_RETRY_COUNTER.inc(
                            tags={"deployment": self.deployment_name})
                        logger.warning(
                            "replica %s died before replying to %s.%s; "
                            "retrying once on a different replica (%s)",
                            tag, self.deployment_name,
                            method_name or "__call__", e)
                        continue
                    raise
            finally:
                self._release(tag)

    async def _call_unary(self, choice: Dict, method_name: str,
                          args: tuple, kwargs: dict) -> Any:
        actor = choice["actor"]
        ref = actor.handle_request.remote(method_name, args, kwargs)
        # Fast path: wait on the owned entry's ready-future (fired
        # straight from the reply handler — no per-call coroutine on
        # the CoreWorker loop) and deserialize HERE, on the router's
        # thread.  In-store/borrowed replies fall back to the full
        # get() path, which also rides the IO loop safely from any
        # thread (the router often runs on its own loop).
        w = _core_worker()
        ready_future = getattr(w, "ready_future", None)
        if ready_future is None:  # e.g. local-mode worker
            return await asyncio.wrap_future(ref.future())
        fut = ready_future(ref)
        if not fut.done():
            await asyncio.wrap_future(fut)
        ok, value = w.try_take_local_value(ref)
        if ok:
            return value
        return await asyncio.wrap_future(ref.future())

    async def assign_replica_stream(self, method_name: str, args: tuple,
                                    kwargs: dict,
                                    timeout_s: float = 120.0,
                                    unary_fallback: bool = False,
                                    tenant: str = None,
                                    affinity: Optional[Dict] = None,
                                    resume: Optional[Dict] = None
                                    ) -> AsyncIterator:
        """Streaming twin of assign_replica: starts a generator-valued
        call on one replica and returns an async iterator over its
        items.  The replica's in-flight slot is held for the LIFETIME of
        the stream (a generating request occupies engine capacity, so it
        must count against max_concurrent_queries the whole time);
        closing the iterator early cancels the remote stream.

        Failure contract: if the serving replica dies mid-stream the
        router fails the stream OVER to a healthy replica — resumable
        targets (serve.resumable) receive the delivered prefix and
        continue from the cursor; non-resumable targets restart only if
        nothing was delivered yet.  When failover is off/exhausted/
        unsafe the consumer gets a structured StreamInterrupted with
        the resume cursor, within the stream-RPC deadline — never a
        silent hang, and never a duplicated item.

        A target that turns out NOT to stream ran exactly once on the
        replica; with unary_fallback the iterator yields its value
        wrapped in _UnaryResult (proxy path — degrade to a plain
        response), otherwise it raises TypeError (handle.stream() on a
        unary method is caller error).

        `affinity` is the request's routing hint ({"tokens": [...]} or
        {"fps": [...]}); `resume` seeds the stream from a CLIENT-HELD
        cursor (x-rt-resume: the items a previous, interrupted stream
        already delivered, plus the dead origin's kv_origin pull
        address) — the first replica call then behaves exactly like an
        internal failover re-submission."""

        async def _gen():
            # Everything — INCLUDING slot acquisition — happens inside
            # the generator body: a stream that is closed (or dropped)
            # before its first iteration never starts this body, and an
            # unstarted generator's finally never runs, so acquiring
            # out here would leak the in-flight slot forever.
            delivered_n = 0
            # Items retained ONLY while a resume could still replay
            # them (resumable target, failover budget left) — a
            # long-lived non-resumable SSE stream must not mirror hours
            # of items in router memory for nothing.
            delivered: List[Any] = []
            exclude: tuple = ()
            failovers = 0
            resumable = False
            origin_rdv = None
            last_page = 0
            # Durable-session id: survives the whole failover chain in
            # cursors so a resumed stream can resurrect its KV pages
            # from the store even when the origin replica is long dead.
            session = (resume or {}).get("session") \
                or (affinity or {}).get("session")

            def _cursor_extras() -> Dict:
                """KV extras for an outgoing StreamInterrupted cursor:
                the origin's pull address and the request's prefix
                fingerprints (at the last-seen replica's page size), so
                a client resuming through a DIFFERENT proxy re-enters
                with affinity and can still migrate the pages."""
                out: Dict[str, Any] = {}
                if origin_rdv:
                    out["kv_origin"] = origin_rdv
                fps = (affinity or {}).get("fps")
                if not fps and affinity and affinity.get("tokens") \
                        and last_page:
                    from ray_tpu.serve.llm.paging import \
                        prefix_fingerprints
                    fps = prefix_fingerprints(
                        affinity["tokens"], last_page,
                        _cfg.serve_affinity_digest_depth)
                if fps:
                    out["digest"] = list(fps)
                if session:
                    out["session"] = session
                return out

            if resume:
                # Client-held cursor: only its UNDELIVERED suffix flows
                # from here on — delivered_n/items count as if this
                # router had streamed them itself.  The cursor's
                # kv_origin is honored only when it names a pull
                # address this router observed in the membership
                # broadcast (forged origins are SSRF/cache-poisoning
                # vectors; see _trusted_rdv).
                delivered = list(resume.get("items") or [])
                delivered_n = int(resume.get("delivered")
                                  or len(delivered))
                origin_rdv = self._trusted_rdv(resume.get("kv_origin"))
            while True:
                try:
                    choice = await self._acquire(timeout_s,
                                                 tenant=tenant,
                                                 exclude=exclude,
                                                 admit=failovers == 0,
                                                 hint=affinity)
                except Exception as e:
                    if failovers == 0:
                        raise
                    # Failover could not even PLACE the stream (no
                    # replica within the deadline): the contract is
                    # still a structured cursor, not a raw assignment
                    # error.
                    INTERRUPTED_COUNTER.inc(
                        tags={"deployment": self.deployment_name})
                    raise StreamInterrupted(
                        f"stream on {self.deployment_name}."
                        f"{method_name or '__call__'} interrupted "
                        f"after {delivered_n} items (failover could "
                        f"not place the stream: {e})",
                        deployment=self.deployment_name,
                        method=method_name, delivered=delivered_n,
                        resumable=resumable, cause=repr(e),
                        **_cursor_extras()) from e
                tag = choice["replica_tag"]
                actor = choice["actor"]
                last_page = int((choice.get("kv_digest") or {})
                                .get("page") or 0)
                finished = False
                stream_id = None
                try:
                    try:
                        resume_state = None
                        if delivered_n:
                            resume_state = {"delivered": delivered_n,
                                            "items": list(delivered)}
                        if origin_rdv \
                                and origin_rdv != choice.get("kv_rdv"):
                            # The dead origin's pull address rides the
                            # cursor: the resuming replica can MIGRATE
                            # the committed pages instead of
                            # re-prefilling the whole prefix.  Forwarded
                            # even at delivered=0 — an interruption
                            # before the first item still left the
                            # origin's PROMPT pages worth shipping.
                            resume_state = resume_state or \
                                {"delivered": 0, "items": []}
                            resume_state["kv_origin"] = origin_rdv
                        if session:
                            # Replica-side api.stream reads the session
                            # id out of _resume and resurrects the
                            # conversation's KV pages from the store
                            # before admission.  Forwarded even at
                            # delivered=0: a client reconnecting
                            # minutes later holds a cursor with no
                            # undelivered items but a session worth
                            # resurrecting.
                            resume_state = resume_state or \
                                {"delivered": 0, "items": []}
                            resume_state["session"] = session
                        t_assign = time.time()
                        start = actor.handle_request_streaming.remote(
                            method_name, args, kwargs, resume_state)
                        try:
                            started = await self._stream_rpc(start)
                        except asyncio.CancelledError:
                            # Closed while the replica was still
                            # starting it: the finally below has no
                            # stream id to cancel by.
                            self._cancel_once_started(actor, start)
                            raise
                        # serve.assign: replica chosen → stream started
                        # (the replica-side admission RPC round trip).
                        assign_args = {"deployment":
                                       self.deployment_name,
                                       "replica": tag,
                                       "failover": failovers,
                                       "resumed": delivered_n}
                        if choice.get("_affinity"):
                            assign_args["affinity"] = \
                                choice["_affinity"]
                        if resume_state \
                                and resume_state.get("kv_origin"):
                            assign_args["kv_origin"] = \
                                f"{origin_rdv.get('host')}:" \
                                f"{origin_rdv.get('port')}"
                        _tracing.record(
                            "serve", "serve.assign", t_assign,
                            time.time() - t_assign,
                            trace=_tracing.child_span(),
                            args=assign_args)
                        if "stream_id" not in started:
                            finished = True
                            if not unary_fallback:
                                raise TypeError(
                                    f"{self.deployment_name}."
                                    f"{method_name or '__call__'} "
                                    "returned a non-streaming result; "
                                    "use handle.remote() for unary "
                                    "calls")
                            yield _UnaryResult(started["unary"])
                            return
                        stream_id = started["stream_id"]
                        resumable = bool(started.get("resumable"))
                        keep_prefix = (self._stream_failover
                                       and resumable
                                       and failovers
                                       < self._max_failovers)
                        if not keep_prefix:
                            delivered = []
                        cursor = 0
                        while True:
                            delay = self._check_stream_failpoint()
                            if delay:
                                await asyncio.sleep(delay)
                            out = await self._stream_rpc(
                                actor.stream_next.remote(stream_id,
                                                         cursor))
                            for item in out["items"]:
                                delivered_n += 1
                                if keep_prefix:
                                    delivered.append(item)
                                yield item
                            cursor += len(out["items"])
                            if out["done"]:
                                finished = True
                                if out.get("error") is not None:
                                    raise out["error"]
                                return
                    except _death_errors() as e:
                        # Leave `finished` False: if the failure was a
                        # transport/injected fault and the replica is
                        # actually alive, the finally's fire-and-forget
                        # stream_cancel stops it generating into a
                        # stream nobody will poll again (a truly dead
                        # actor just drops the cancel).
                        self._drop_replica(tag)
                        # Remember where the dead replica's KV pages
                        # can be pulled from — the HOST may be alive
                        # even when the replica's actor transport is
                        # not (injected faults, wedged streams), and a
                        # dead process just makes the pull fail fast
                        # into re-prefill.
                        origin_rdv = choice.get("kv_rdv") or origin_rdv
                        can_failover = (
                            self._stream_failover
                            and failovers < self._max_failovers
                            and (resumable or not delivered_n))
                        if can_failover:
                            failovers += 1
                            # Annotation in the request's trace: the
                            # resumed stream keeps the SAME trace id,
                            # so the waterfall shows one request whose
                            # spans hop replicas at this marker.
                            _tracing.event(
                                "serve", "serve.failover",
                                args={"deployment":
                                      self.deployment_name,
                                      "replica_died": tag,
                                      "delivered": delivered_n,
                                      "failover": failovers,
                                      "resumable": resumable})
                            # Accumulate: this stream must NEVER retry
                            # a replica it watched die, even after the
                            # local-view suppression TTL expires (a
                            # slow controller must not cost a second
                            # failover against the same corpse).
                            exclude = tuple(set(exclude) | {tag})
                            FAILOVER_COUNTER.inc(
                                tags={"deployment":
                                      self.deployment_name})
                            logger.warning(
                                "stream on replica %s of %s died after "
                                "%d items (%s); %s on a healthy "
                                "replica (failover %d/%d)",
                                tag, self.deployment_name,
                                delivered_n, e,
                                "resuming" if delivered_n
                                else "restarting",
                                failovers, self._max_failovers)
                            continue
                        INTERRUPTED_COUNTER.inc(
                            tags={"deployment": self.deployment_name})
                        _tracing.event(
                            "serve", "serve.stream_interrupted",
                            args={"deployment": self.deployment_name,
                                  "replica_died": tag,
                                  "delivered": delivered_n})
                        raise StreamInterrupted(
                            f"stream on {self.deployment_name}."
                            f"{method_name or '__call__'} interrupted "
                            f"after {delivered_n} items "
                            f"(replica {tag} died; failover "
                            f"{'exhausted' if failovers else 'unavailable'}): {e}",
                            deployment=self.deployment_name,
                            method=method_name,
                            delivered=delivered_n,
                            resumable=resumable,
                            cause=repr(e),
                            **_cursor_extras()) from e
                finally:
                    if stream_id is not None and not finished:
                        # Early close / client gone: free the replica-
                        # side stream (and whatever slot it holds in an
                        # engine).
                        actor.stream_cancel.options(
                            num_returns=0).remote(stream_id)
                    self._release(tag)

        # Bind the CREATOR's trace context to every step: the consumer
        # may drive this generator from another task/loop (handle
        # streams), where the ambient context is empty — the replica
        # calls (and failover re-submissions) must keep linking under
        # the caller's span, one trace id for the stream's whole life.
        ctx = _tracing.current()
        gen = _gen()
        return _tracing.bind_agen(gen, ctx) if ctx is not None else gen

    def _pick(self, exclude: tuple = (),
              hint: Optional[Dict] = None) -> Optional[Dict]:
        if self._suppressed:
            now = asyncio.get_event_loop().time()
            for t, dl in list(self._suppressed.items()):
                if dl <= now:
                    del self._suppressed[t]
        free = [r for r in self._replicas
                if r["replica_tag"] not in exclude
                and r["replica_tag"] not in self._suppressed
                and self._in_flight.get(r["replica_tag"], 0)
                < r["max_concurrent_queries"]]
        if not free:
            return None
        if hint and _cfg.serve_affinity \
                and (hint.get("tokens") or hint.get("fps")):
            choice = self._pick_affinity(free, hint)
            if choice is not None:
                return choice
        if len(free) == 1:
            return free[0]
        # Power of two choices: least-loaded of two random candidates.
        a, b = random.sample(free, 2)
        return a if (self._in_flight.get(a["replica_tag"], 0)
                     <= self._in_flight.get(b["replica_tag"], 0)) else b

    def _load_norm(self, r: Dict) -> float:
        return (self._in_flight.get(r["replica_tag"], 0)
                / max(1, r["max_concurrent_queries"]))

    def _hint_fps(self, hint: Dict, page: int,
                  cache: Dict[int, List[str]]) -> List[str]:
        """The request's prefix fingerprint chain at a replica's page
        size.  Token hints are re-fingerprinted per distinct page size
        seen (cached per pick); a raw-fps hint (x-rt-affinity, resume
        cursor) only matches replicas with the page size it was minted
        at — the chained digests simply never collide otherwise."""
        tokens = hint.get("tokens")
        if tokens and page > 0:
            fps = cache.get(page)
            if fps is None:
                from ray_tpu.serve.llm.paging import prefix_fingerprints
                fps = cache[page] = prefix_fingerprints(
                    tokens, page, _cfg.serve_affinity_digest_depth)
            return fps
        return hint.get("fps") or []

    def _pick_affinity(self, free: List[Dict],
                       hint: Dict) -> Optional[Dict]:
        """Prefix-affinity scoring: per candidate,
        ``score = blend * hit_depth/chain_len - (1-blend) * load`` where
        hit_depth is the DEEPEST request fingerprint in the replica's
        published digest (fingerprints chain, so depth d present implies
        the whole d-page prefix is cached).  Returns None — falling back
        to the load-based power-of-two pick — when no candidate holds
        any prefix, or when the winner is past the hotspot bound: a
        viral prefix concentrates hits on one replica, and affinity must
        lose to overload there rather than starve it."""
        blend = _cfg.serve_affinity_blend
        fps_cache: Dict[int, List[str]] = {}
        best = best_meta = best_key = None
        for r in free:
            dig = r.get("kv_digest") or {}
            fps = self._hint_fps(hint, int(dig.get("page") or 0),
                                 fps_cache)
            if not fps:
                continue
            have = {x.get("fp"): int(x.get("t") or 0)
                    for x in (dig.get("roots") or ())}
            hits, hit_tier = 0, 0
            for d, fp in enumerate(fps, 1):
                if fp in have:
                    hits, hit_tier = d, have[fp]
            load = self._load_norm(r)
            # A tiered hit (digest entry's worst tier > T0) still saves
            # the prefill, but the replica must promote the pages back
            # into the decode pool first — weigh it below an
            # equally-deep hot hit so T0 holders win ties.
            weight = 1.0 if hit_tier == 0 else max(
                0.0, min(1.0,
                         float(_cfg.serve_affinity_tier_discount)))
            score = blend * weight * (hits / len(fps)) \
                - (1.0 - blend) * load
            key = (score, -load)
            if best_key is None or key > best_key:
                best, best_key = r, key
                best_meta = {"hits": hits, "chain": len(fps),
                             "tier": hit_tier,
                             "score": round(score, 4),
                             "load": round(load, 4)}
        if best is None or not best_meta["hits"]:
            return None
        AFFINITY_SCORE_GAUGE.set(best_meta["score"],
                                 tags={"deployment":
                                       self.deployment_name})
        if best_meta["load"] >= _cfg.serve_affinity_hotspot_bound:
            _tracing.event("serve", "serve.affinity_diverted",
                           args={"deployment": self.deployment_name,
                                 "replica": best["replica_tag"],
                                 **best_meta})
            return None
        AFFINITY_HITS_COUNTER.inc(
            tags={"deployment": self.deployment_name})
        # A shallow copy so the decision can ride to the serve.assign
        # span without mutating the shared membership info dict.
        choice = dict(best)
        choice["_affinity"] = best_meta
        return choice

    def stats(self) -> Dict:
        return {"queued": self.num_queued,
                "in_flight": sum(self._in_flight.values()),
                "num_replicas": len(self._replicas)}


class Router:
    """One per handle-holding process (proxy, driver, or other actor)."""

    def __init__(self, controller_handle, deployment_name: str,
                 loop: Optional[asyncio.AbstractEventLoop] = None,
                 qos: Any = _QOS_FROM_ENV):
        loop = loop or asyncio.get_event_loop()
        self.deployment_name = deployment_name
        self.replica_set = ReplicaSet(deployment_name, loop, qos=qos)
        self._long_poll = LongPollClient(
            controller_handle,
            {f"replicas::{deployment_name}":
                self.replica_set.update_replicas},
            loop=loop)

    async def assign_request(self, method_name: str, args: tuple,
                             kwargs: dict, tenant: str = None,
                             affinity: Optional[Dict] = None):
        return await self.replica_set.assign_replica(
            method_name, args, kwargs, tenant=tenant, affinity=affinity)

    async def assign_request_stream(self, method_name: str, args: tuple,
                                    kwargs: dict, tenant: str = None,
                                    affinity: Optional[Dict] = None,
                                    resume: Optional[Dict] = None):
        return await self.replica_set.assign_replica_stream(
            method_name, args, kwargs, tenant=tenant, affinity=affinity,
            resume=resume)

    def stop(self):
        self._long_poll.stop()
