"""Shared cross-plane span runtime: one bounded ring per process.

Reference: the reference's observability stack spans every plane —
`ray timeline` dumps chrome-trace events aggregated from per-process
profilers (src/ray/core_worker/profiling.h), the dashboard's metrics
pipeline relays them, and OpenTelemetry spans ride TaskSpecs
(python/ray/util/tracing/tracing_helper.py).  Before this module, our
coverage stopped at task/actor submit+execute in `_private/worker.py`:
the transfer plane, collectives, control-plane pubsub/scheduling, serve
request lifecycles, and the data executor were tracing black holes.

Design:

* **One ring per process** (`TraceRing`): a bounded deque of
  chrome-trace events with drop-oldest semantics and a drop counter —
  cheap enough to leave always on (an append is one dict build + one
  deque append; the disabled fast path is a single bool check).  The
  capacity / enablement knobs are ``RT_TRACE_*`` (see
  config.py).
* **Trace context** rides a contextvar, propagated inside TaskSpecs
  (worker.py) and adopted at execution with a fresh span id, so spans
  link parent→child across processes.  Cross-process edges additionally
  emit chrome flow events (``ph:"s"`` at the submit/request site,
  ``ph:"f"`` at the serving site, same ``id``) so the waterfall
  connects in the chrome trace viewer.
* **Pull, not push, is authoritative**: every worker/raylet/GCS serves
  a ``dump_trace`` RPC draining this ring on demand
  (`ray_tpu.cluster_trace()`, ``rt timeline --cluster``,
  ``rt trace <id>``).  The periodic telemetry KV push keeps feeding
  ``ray_tpu.timeline()`` as a stale convenience view — it truncates to
  the freshest events and lags by the push period.
* **Assembly** (`assemble`, `format_trace`): given a merged event list
  and a trace id, build the span tree (parent_id links) and derive a
  per-stage latency breakdown — for serve requests the TTFT decomposes
  into queue / prefill / first-tick from the engine's span taxonomy.

Span taxonomy (cat.name — see README "Observability"):
  task.*            submit flows + task/actor execution (worker.py)
  transfer.*        pull/push windows, chunk retries, source deaths
  collective.*      per-op spans (rendezvous→bulk→fold), buckets
  gcs.*             scheduling decisions, pubsub batch flushes
  rpc.slow          any RPC handler over cfg.trace_rpc_slow_ms
  serve.*           proxy request, router assign/QoS wait, failover
  engine.*          queue / prefill / first_tick per request; tier_sweep
                    per sweep that moved pages (per-turn time is in
                    the engine's stats() counters, never in the ring)
  jax.compile       one per backend compile or cache load (jax_utils)
  data.*            streaming execute + shuffle exchange

A start's spans, once a process or once a replica, one trace id a
replica start ("A start's books" below; README "Observability"):
  rt.init               driver: GCS, raylet and the driver's worker up
  serve.start           driver: the controller (and proxy) asked for
  serve.replica_start   controller, the root: the decision to start a
                        replica -> the controller knows it is ready
                        (deployment, replica_tag, ok)
  raylet.worker_start   raylet: an actor's lease asked -> its worker
                        registered (how = zygote / cold / reused, kind)
  worker.boot           worker: the process's own start (its fork,
                        under a zygote) -> ready for its first task
                        (import_s); recorded with the first actor
  jax.backend_init      worker: jax_utils.open_backend, the first
                        jax.devices() (a TPU worker opens its chips)
  task.create_actor     worker: the actor-creation task (class)
  serve.replica_init    replica: RTServeReplica.__init__
  serve.replica_unpickle  replica: the deployment's definition
                        unpickled, so its modules imported
  llm.load_model        replica: LLMServer's model_loader()
                        (param_bytes)
  engine.build          replica: GenerationEngine.__init__
                        (cache_bytes)
  engine.warm           engine thread: _warm_kernels (programs,
                        after_ready_s), one child a program:
                        engine.warm.tick / .verify / .chunk
  train.worker_group_start  trainer: a gang's workers asked for ->
                        the backend's on_start returned (the runtime's
                        three spans link under it)
"""

from __future__ import annotations

import contextvars
import itertools
import os
import threading
import time
from collections import deque
from contextlib import contextmanager

from ray_tpu._private import locksan
from ray_tpu._private.config import GLOBAL_CONFIG as cfg

# ---------------------------------------------------------------- context

# Distributed trace context (trace_id, span_id) | None.  Reference:
# util/tracing/tracing_helper.py — otel context rides the TaskSpec; here
# the span tree lands in the per-process ring and ray_tpu.timeline().
_TRACE: contextvars.ContextVar = contextvars.ContextVar(
    "rt_trace", default=None)

# Fresh ids: a per-process random base + counter instead of one
# os.urandom syscall per span (urandom is painfully expensive on
# syscall-filtered hosts; uniqueness only needs process entropy once).
_ID_BASE = os.urandom(5).hex()
_id_counter = itertools.count(1).__next__
# getpid() is a real syscall on every call (glibc stopped caching it);
# under this container's syscall-filtered sandbox that is measurable on
# the per-event path — cache it, refresh at fork.
_PID = os.getpid()
# Live OTel export bridge: poked by util.tracing.enable/disable_tracing
# so the record() hot path pays ONE identity check, not a module lookup
# + probe per event.
_LIVE_EXPORT = None
# When this process began, where the process itself saw it: its fork
# (a zygote's child), else None and process_start() asks the kernel.
_T_FORK = None
_T_IMPORT = time.time()


def _reseed_id_base():
    """At-fork hook: zygote-forked workers must not mint the parent's
    id stream (same rationale as ids._reseed_id_bases), nor keep its
    start time or its start's books."""
    global _ID_BASE, _id_counter, _PID, _T_FORK, _BOOKS_LINK
    _ID_BASE = os.urandom(5).hex()
    _id_counter = itertools.count(1).__next__
    _PID = os.getpid()
    _T_FORK = time.time()
    _BOOKS.clear()
    _BOOKS_LINK = None


os.register_at_fork(after_in_child=_reseed_id_base)


def fresh_id() -> str:
    return f"{_ID_BASE}{_id_counter():06x}"


def current():
    """(trace_id, span_id) of the active span, or None."""
    return _TRACE.get()


def current_dict():
    """Active context as the wire shape ({"trace_id","parent_id"})
    propagated in task specs / plane RPC bodies, or None."""
    ctx = _TRACE.get()
    if ctx is None:
        return None
    return {"trace_id": ctx[0], "parent_id": ctx[1]}


def set_current(trace_id: str, span_id: str):
    """Install a context; returns the reset token."""
    return _TRACE.set((trace_id, span_id))


def reset_current(token):
    _TRACE.reset(token)


def child_span() -> dict | None:
    """A span-linkage dict (fresh span id) parented under the ACTIVE
    span — for call sites that measure t0/dur themselves (record())
    instead of wrapping a with-block in span()."""
    ctx = _TRACE.get()
    if ctx is None:
        return None
    return {"trace_id": ctx[0], "span_id": fresh_id(),
            "parent_id": ctx[1]}


def trace_for_submit() -> dict:
    """Current (or fresh) trace context to stamp on an outgoing task —
    plus, when a span is ACTIVE, a flow id connecting the submit site
    to the execution span (chrome ``ph:"s"``/``"f"`` pair).  An
    un-spanned submit gets no flow id: there is no submit-side span to
    connect from, and the two extra ring events per call are exactly
    the always-on overhead the <=5% bench gate polices."""
    ctx = _TRACE.get()
    if ctx is None:
        return {"trace_id": fresh_id(), "parent_id": None}
    return {"trace_id": ctx[0], "parent_id": ctx[1], "flow": fresh_id()}


def adopt(trace, cat: str = "task"):
    """Adopt a submitter's trace context with a fresh span id so work
    submitted from here links as children; emits the closing flow event
    when the context carries a flow id.  Returns the span dict to stamp
    on the recorded event (or None)."""
    if not trace:
        return None
    span = {"trace_id": trace["trace_id"], "span_id": fresh_id(),
            "parent_id": trace.get("parent_id")}
    _TRACE.set((span["trace_id"], span["span_id"]))
    flow = trace.get("flow")
    if flow is not None:
        flow_end(flow, cat)
        span["flow"] = flow
    return span


async def bind_agen(agen, ctx):
    """Re-install ``ctx`` (a (trace_id, span_id) pair) around EVERY
    step of ``agen``: async-generator frames execute in the driving
    task's context, so a stream created under a span but consumed from
    another thread/loop (serve handles hop to the router loop) would
    otherwise lose its trace — and every actor call it makes would mint
    a fresh root instead of linking under the caller.  Closing the
    wrapper closes the inner generator (its finally blocks run)."""
    try:
        while True:
            token = _TRACE.set(ctx)
            try:
                item = await agen.__anext__()
            except StopAsyncIteration:
                return
            finally:
                _TRACE.reset(token)
            yield item
    finally:
        await agen.aclose()


# ------------------------------------------------------------------- ring

class TraceRing:
    """Bounded ring of chrome-trace events: drop-oldest + drop counter.

    Appends are one ``deque.append`` (thread-safe under the GIL); the
    drop counter tolerates racy increments — it feeds a monitoring
    counter, not an invariant."""

    def __init__(self, capacity: int | None = None):
        cap = capacity if capacity is not None \
            else max(64, cfg.trace_ring_capacity)
        self.capacity = cap
        self._q: deque = deque(maxlen=cap)
        self.dropped = 0

    def append(self, event: dict) -> None:
        if len(self._q) >= self.capacity:
            self.dropped += 1
        self._q.append(event)

    def __len__(self):
        return len(self._q)

    def tail(self, n: int) -> list:
        q = self._q
        if len(q) <= n:
            return list(q)
        return list(q)[-n:]

    def snapshot(self, clear: bool = False) -> list:
        out = list(self._q)
        if clear:
            self._q.clear()
        return out

    def stats(self) -> dict:
        q = self._q
        ts_min = ts_max = None
        if q:
            try:
                ts_min = q[0].get("ts")
                ts_max = q[-1].get("ts")
            except IndexError:  # racing append/clear; stats stay best-effort
                pass
        return {"depth": len(q), "capacity": self.capacity,
                "dropped": self.dropped,
                "ts_min": ts_min, "ts_max": ts_max}


_RING = TraceRing()
_ENABLED = bool(cfg.trace_enabled)
# Drops already surfaced through the prometheus counter (export_metrics
# incs by the delta so the counter is monotonic across snapshots).
_exported_drops = 0
_export_lock = locksan.make_lock("tracing._export_lock")
_metrics = None  # (drop Counter, depth Gauge) once built


def ring() -> TraceRing:
    return _RING


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Runtime switch (benches / tests); processes normally inherit
    RT_TRACE_ENABLED through the environment."""
    global _ENABLED
    _ENABLED = bool(on)


# ---------------------------------------------------------------- record

def record(cat: str, name: str, t0: float, dur_s: float,
           trace: dict | None = None, args: dict | None = None) -> None:
    """One chrome-trace complete event ({ts,dur} in us since epoch).
    ``trace`` carries the span linkage (trace_id/span_id/parent_id);
    ``args`` any extra annotations.  Events shorter than
    cfg.trace_min_dur_us are skipped UNLESS they carry span linkage —
    dropping linked spans would hole the tree."""
    if not _ENABLED:
        return
    dur_us = dur_s * 1e6
    if trace is None and dur_us < cfg.trace_min_dur_us:
        return
    event = {
        "cat": cat, "name": name, "ph": "X",
        "pid": _PID,
        "tid": threading.get_ident() & 0xFFFF,
        "ts": t0 * 1e6, "dur": dur_us,
    }
    a = {}
    if trace:
        a.update(trace)
    if args:
        a.update(args)
    if a:
        event["args"] = a
    _RING.append(event)
    if _LIVE_EXPORT is not None:
        _maybe_export(event)


def event(cat: str, name: str, args: dict | None = None) -> None:
    """Instant event (ph "i"), stamped with the current trace context —
    annotations like a transfer source death or a serve failover."""
    if not _ENABLED:
        return
    ev = {"cat": cat, "name": name, "ph": "i", "s": "p",
          "pid": _PID, "tid": threading.get_ident() & 0xFFFF,
          "ts": time.time() * 1e6}
    a = dict(args or ())
    ctx = _TRACE.get()
    if ctx is not None:
        a.setdefault("trace_id", ctx[0])
        a.setdefault("parent_id", ctx[1])
    if a:
        ev["args"] = a
    _RING.append(ev)


def flow_start(flow_id: str, cat: str = "task") -> None:
    """Chrome flow-start (ph "s") at the requesting site of a
    cross-process edge."""
    if not _ENABLED:
        return
    _RING.append({"cat": cat, "name": f"{cat}.flow", "ph": "s",
                  "id": flow_id, "pid": _PID,
                  "tid": threading.get_ident() & 0xFFFF,
                  "ts": time.time() * 1e6})


def flow_end(flow_id: str, cat: str = "task") -> None:
    """Chrome flow-finish (ph "f", bp "e") at the serving site."""
    if not _ENABLED:
        return
    _RING.append({"cat": cat, "name": f"{cat}.flow", "ph": "f",
                  "bp": "e", "id": flow_id, "pid": _PID,
                  "tid": threading.get_ident() & 0xFFFF,
                  "ts": time.time() * 1e6})


class _SpanHandle:
    """Yielded by span(): lets the body annotate (``h.args[...]``) and
    read the ids (the proxy returns h.trace_id to the client)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "args")

    def __init__(self, trace_id, span_id, parent_id):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.args = {}


@contextmanager
def span(cat: str, name: str, args: dict | None = None,
         root: bool = False, _record=None):
    """Record a complete event covering the with-body, as a child of
    the active span (or a fresh root when none is active or
    ``root=True``).  The context is installed for the body, so nested
    spans / submitted tasks / plane RPCs link as children — including
    across processes.  Always manages context even when recording is
    disabled (continuity is semantic, the ring is observability).
    ``_record`` (start_span's) is called with (cat, name, t0, t1,
    trace=, args=) in place of record(), enabled or not."""
    ctx = None if root else _TRACE.get()
    trace_id = fresh_id() if ctx is None else ctx[0]
    parent_id = None if ctx is None else ctx[1]
    span_id = fresh_id()
    token = _TRACE.set((trace_id, span_id))
    h = _SpanHandle(trace_id, span_id, parent_id)
    if args:
        h.args.update(args)
    t0 = time.time()
    try:
        yield h
    finally:
        _TRACE.reset(token)
        if _record is not None or _ENABLED:
            link = {"trace_id": trace_id, "span_id": span_id,
                    "parent_id": parent_id}
            t1 = time.time()
            if _record is not None:
                _record(cat, name, t0, t1, trace=link, args=h.args or None)
            else:
                record(cat, name, t0, t1 - t0, trace=link,
                       args=h.args or None)


# ------------------------------------------------------ a start's books

# The spans of a start (taxonomy above) and the phase each one is in the
# books.  A replica's ring is full within a benchmark window and drops
# its oldest events first, a start's; so the (t0, t1) every such span
# was made from is also kept here, a dozen entries a process, and
# LLMServer.replica_info() hands them out as seconds under "start".
_BOOK_PHASES = {"serve.replica_start": "root",
                 "raylet.worker_start": "spawn",
                 "worker.boot": "boot",
                 "jax.backend_init": "backend_init",
                 "serve.replica_init": "init",
                 "serve.replica_unpickle": "unpickle",
                 "llm.load_model": "load",
                 "engine.build": "build",
                 "engine.warm": "warm"}
_BOOKS: dict = {}      # phase -> (t0, t1) epoch seconds; "trace_id", "how"
_BOOKS_LINK = None     # (trace_id, worker.boot's span id) once linked


def process_start() -> float:
    """When this process began, on the epoch clock: its fork for a
    zygote's child, else what the kernel says (to a clock tick), else
    this module's import."""
    if _T_FORK is not None:
        return _T_FORK
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            age = float(f.read().split()[0]) \
                - ticks / os.sysconf("SC_CLK_TCK")
        t = time.time() - age
        if 0.0 <= age and t <= _T_IMPORT:
            return t
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def start_note(phase: str, value) -> None:
    """Keep in this process's books what another process measured (the
    raylet's lease, the controller's root: their (t0, t1)) or a fact
    of the start ("trace_id", "how")."""
    _BOOKS[phase] = value


def start_ready() -> None:
    """The boot that worker_main noted ends now, unless it has ended."""
    boot = _BOOKS.get("boot")
    if boot is not None and boot[1] is None:
        _BOOKS["boot"] = (boot[0], time.time())


def start_noted(phase: str):
    """What the books hold for `phase`, or None."""
    return _BOOKS.get(phase)


def start_record(cat: str, name: str, t0: float, t1: float,
                 trace: dict | None = None,
                 args: dict | None = None) -> None:
    """One span of a start from its two timestamps, and the same two
    numbers in the books under the phase the name stands for: one
    source for both, whether or not the ring records."""
    phase = _BOOK_PHASES.get(name)
    if phase is not None:
        _BOOKS[phase] = (t0, t1)
    record(cat, name, t0, t1 - t0, trace=trace, args=args)


@contextmanager
def start_span(cat: str, name: str, args: dict | None = None):
    """span() for a phase of a start: made by start_record, so the
    books hold its two timestamps whether or not the ring records; a
    body that raises leaves its error on the span."""
    with span(cat, name, args, _record=start_record) as h:
        try:
            yield h
        except BaseException as e:
            h.args["error"] = repr(e)[:200]
            raise


def start_begin(trace: dict | None, lease: dict | None) -> None:
    """An actor's creation reached this worker: open its start's books
    (whatever an earlier actor of a reused worker left goes, the boot
    stays), note what the raylet measured (`lease`: how, t0, t1 and the
    span id of its raylet.worker_start) and, once a process, record the
    worker.boot span that worker_main could only note: under the span
    that waited for this worker."""
    global _BOOKS_LINK
    start_ready()
    for key in [k for k in _BOOKS if k not in ("boot", "import_s")]:
        del _BOOKS[key]
    if not trace:
        return
    _BOOKS["trace_id"] = trace["trace_id"]
    lease = lease or {}
    if "t0" in lease:
        _BOOKS["how"] = lease.get("how")
        _BOOKS["spawn"] = (lease["t0"], lease["t1"])
    boot = _BOOKS.get("boot")
    if _BOOKS_LINK is not None or boot is None:
        return
    span_id = fresh_id()
    _BOOKS_LINK = (trace["trace_id"], span_id)
    start_record("worker", "worker.boot", boot[0], boot[1],
                 trace={"trace_id": trace["trace_id"], "span_id": span_id,
                        "parent_id": lease.get("span_id",
                                               trace.get("parent_id"))},
                 args={"import_s": round(_BOOKS.get("import_s", 0.0), 6)})


def start_link() -> dict | None:
    """Linkage for a span of this process's start that no with-block
    made (jax.backend_init): a child of the active span where that is
    of the start's own trace (an LLMServer opens its chip inside
    llm.load_model), else of worker.boot (a train worker opens it in a
    later task of another trace)."""
    ctx = _TRACE.get()
    if ctx is not None and (_BOOKS_LINK is None
                            or ctx[0] == _BOOKS_LINK[0]):
        return child_span()
    if _BOOKS_LINK is None:
        return None
    return {"trace_id": _BOOKS_LINK[0], "span_id": fresh_id(),
            "parent_id": _BOOKS_LINK[1]}


def start_seconds(iv: dict) -> dict:
    """A start's phases in seconds from their (t0, t1).  `spawn`, `boot`,
    `unpickle`, `load` and `build` do not overlap and lie inside the
    root, so with `unaccounted` (the root's length less those five: its
    self time) they add up to the root: `boot` is the part of
    worker.boot inside raylet.worker_start (all of a fresh worker's;
    none of a reused one's, which booted before the lease was asked),
    `spawn` the rest of raylet.worker_start.  `warm` runs on the
    engine's thread beside the root's tail and past it:
    `warm_after_ready` is how long after serve.replica_init ended it
    ended; `backend_init` is the chip's
    opening, part of whichever phase made the process's first jax call
    (`load` for an LLMServer).  A phase not (yet) known reads None."""
    def _len(key):
        v = iv.get(key)
        return None if v is None or v[1] is None else v[1] - v[0]

    out = dict.fromkeys(("spawn", "boot", "unpickle", "load", "build",
                         "warm", "warm_after_ready", "unaccounted",
                         "backend_init"))
    for key in ("boot", "unpickle", "load", "build", "warm",
                "backend_init"):
        out[key] = _len(key)
    spawn, boot = iv.get("spawn"), iv.get("boot")
    if spawn is not None:
        out["boot"] = 0.0 if boot is None else max(
            0.0, min(boot[1], spawn[1]) - max(boot[0], spawn[0]))
        out["spawn"] = spawn[1] - spawn[0] - out["boot"]
    if iv.get("warm") is not None and iv.get("init") is not None:
        out["warm_after_ready"] = max(0.0, iv["warm"][1] - iv["init"][1])
    if iv.get("root") is not None:
        out["root"] = _len("root")
        out["unaccounted"] = out["root"] - sum(
            out[k] or 0.0
            for k in ("spawn", "boot", "unpickle", "load", "build"))
    return {k: v if v is None else round(v, 6) for k, v in out.items()}


def start_books() -> dict:
    """This process's start as LLMServer.replica_info() returns it:
    the trace id, how the worker came to be, and start_seconds of the
    timestamps kept here."""
    return {"trace_id": _BOOKS.get("trace_id"), "how": _BOOKS.get("how"),
            **start_seconds(_BOOKS)}


def _maybe_export(ev: dict) -> None:
    """Bridge to util.tracing's optional live tracer (OTel), lazily —
    the bridge is a no-op unless enable_tracing() ran here."""
    try:
        from ray_tpu.util import tracing as _ut
        if _ut.is_enabled():
            _ut.maybe_export(ev)
    except Exception:
        pass


# ------------------------------------------------------------- dump/pull

def dump(stats_only: bool = False, clear: bool = False) -> dict:
    """The ``dump_trace`` RPC payload: this process's ring, stats
    first.  The pull path is authoritative — unlike the telemetry KV
    push it delivers the WHOLE ring, with its drop counter and coverage
    window, at the moment of the call."""
    out = {"pid": _PID, "ring_id": _ID_BASE, **_RING.stats()}
    if not stats_only:
        out["events"] = _RING.snapshot(clear=clear)
    return out


def meta_event(stats: dict | None = None) -> dict:
    """Self-description for a (possibly truncated) trace dump: an
    instant event recording this process's drop count and ring coverage
    window, so a reader knows what the ring could NOT retain."""
    s = stats or _RING.stats()
    return {"cat": "trace", "name": "trace.ring_meta", "ph": "i",
            "s": "p", "pid": s.get("pid", os.getpid()), "tid": 0,
            "ts": (s.get("ts_max") or time.time() * 1e6),
            "args": {"events_dropped": s["dropped"],
                     "ring_depth": s["depth"],
                     "ring_capacity": s["capacity"],
                     "window_start_ts": s["ts_min"],
                     "window_end_ts": s["ts_max"]}}


def export_metrics() -> None:
    """Update the prometheus-facing series (rides the telemetry push):
    ``tracing_events_dropped_total`` (monotonic counter; nonzero only
    when the ring actually overflowed) and ``tracing_ring_depth``."""
    global _metrics, _exported_drops
    try:
        from ray_tpu.util.metrics import Counter, Gauge
        with _export_lock:
            if _metrics is None:
                _metrics = (
                    Counter("tracing_events_dropped_total",
                            "Span events dropped from this process's "
                            "trace ring (drop-oldest overflow)"),
                    Gauge("tracing_ring_depth",
                          "Events currently held in this process's "
                          "trace ring"))
            delta = _RING.dropped - _exported_drops
            if delta > 0:
                _metrics[0].inc(delta)
                _exported_drops += delta
            _metrics[1].set(float(len(_RING)))
    except Exception:
        pass


# ------------------------------------------------------------- assembly

def trace_events(events: list, trace_id: str) -> list:
    """Events belonging to one trace (span + instant events carrying
    the id in args)."""
    out = []
    for e in events:
        a = e.get("args")
        if a and a.get("trace_id") == trace_id:
            out.append(e)
    return out


def trace_ids(events: list) -> dict:
    """{trace_id: (n_events, first_ts, root_name)} — newest-first
    listing for ``rt trace`` without an id."""
    acc: dict = {}
    for e in events:
        a = e.get("args")
        tid = a.get("trace_id") if a else None
        if tid is None:
            continue
        n, ts, name = acc.get(tid, (0, None, None))
        ets = e.get("ts")
        if ts is None or (ets is not None and ets < ts):
            ts = ets
            if e.get("ph") == "X":
                name = e.get("name")
        acc[tid] = (n + 1, ts, name or e.get("name"))
    return acc


def assemble(events: list, trace_id: str) -> dict:
    """Build one request's span tree.

    Returns {"trace_id", "spans": [span...], "roots": [span...],
    "processes": sorted pids, "annotations": [instant events],
    "breakdown": derived per-stage latencies (TTFT decomposition when
    engine spans are present)}.  Each span dict: name/cat/pid/ts/dur/
    span_id/parent_id/args/children."""
    mine = trace_events(events, trace_id)
    spans = []
    notes = []
    by_id = {}
    for e in mine:
        if e.get("ph") != "X":
            if e.get("ph") == "i":
                notes.append(e)
            continue
        a = e.get("args") or {}
        s = {"name": e.get("name"), "cat": e.get("cat"),
             "pid": e.get("pid"), "tid": e.get("tid"),
             "ts": e.get("ts", 0.0),
             "dur": e.get("dur", 0.0),
             "span_id": a.get("span_id"),
             "parent_id": a.get("parent_id"),
             "args": {k: v for k, v in a.items()
                      if k not in ("trace_id", "span_id", "parent_id",
                                   "flow")},
             "children": []}
        spans.append(s)
        if s["span_id"]:
            by_id[s["span_id"]] = s
    spans.extend(_adopt_compiles(events, spans))
    roots = []
    for s in spans:
        parent = by_id.get(s["parent_id"]) if s["parent_id"] else None
        if parent is not None and parent is not s:
            parent["children"].append(s)
        else:
            roots.append(s)
    for s in spans:
        s["children"].sort(key=lambda c: c["ts"])
    roots.sort(key=lambda s: s["ts"])
    # Attach annotations to their parent span where possible.
    for n in notes:
        a = n.get("args") or {}
        parent = by_id.get(a.get("parent_id"))
        if parent is not None:
            parent.setdefault("events", []).append(
                {"name": n.get("name"), "ts": n.get("ts"),
                 "args": {k: v for k, v in a.items()
                          if k not in ("trace_id", "parent_id")}})
    return {"trace_id": trace_id, "spans": spans, "roots": roots,
            "processes": sorted({s["pid"] for s in spans}),
            "annotations": notes,
            "breakdown": _breakdown(spans)}


def _adopt_compiles(events: list, spans: list) -> list:
    """`jax.compile` events carry no trace (jax's listener has no
    context): each one that lies inside a span of this trace, by time
    and on the span's own thread, joins it as a child of the shortest
    such span, so a start's tree says which program was compiled, or
    loaded from the persistent cache, inside which phase."""
    out = []
    for e in events:
        a = e.get("args") or {}
        if e.get("name") != "jax.compile" or a.get("trace_id"):
            continue
        t0, t1 = e.get("ts", 0.0), e.get("ts", 0.0) + e.get("dur", 0.0)
        inside = [s for s in spans
                  if s["pid"] == e.get("pid") and s["tid"] == e.get("tid")
                  and s["span_id"] and s["ts"] <= t0
                  and t1 <= s["ts"] + s["dur"]]
        if inside:
            parent = min(inside, key=lambda s: s["dur"])
            out.append({"name": "jax.compile", "cat": e.get("cat"),
                        "pid": e.get("pid"), "tid": e.get("tid"),
                        "ts": t0, "dur": e.get("dur", 0.0),
                        "span_id": None,
                        "parent_id": parent["span_id"],
                        "args": dict(a), "children": []})
    return out


def _breakdown(spans: list) -> dict:
    """Per-stage latency breakdown.  Stages are keyed by span name;
    the serve taxonomy additionally derives the TTFT decomposition
    (queue vs prefill vs first tick) as dedicated fields."""
    stages: dict = {}
    for s in spans:
        ms = s["dur"] / 1000.0
        agg = stages.setdefault(s["name"], {"count": 0, "total_ms": 0.0,
                                            "max_ms": 0.0})
        agg["count"] += 1
        agg["total_ms"] += ms
        if ms > agg["max_ms"]:
            agg["max_ms"] = ms
    out = {"stages": {k: {kk: round(vv, 3) if isinstance(vv, float)
                          else vv for kk, vv in v.items()}
                      for k, v in sorted(stages.items())}}
    # TTFT decomposes the FIRST engine submission only: a trace can
    # hold several engine requests (sequential streams, a failover
    # resume), and summing every triple would report their total as
    # one request's TTFT.  Engine spans carry request_id for grouping.
    engine = [s for s in spans
              if s["name"] in ("engine.queue", "engine.prefill",
                               "engine.first_tick")]
    if engine:
        rid = min(engine, key=lambda s: s["ts"])["args"].get("request_id")
        sel = [s for s in engine if s["args"].get("request_id") == rid]

        def _ms(name):
            return sum(s["dur"] for s in sel
                       if s["name"] == name) / 1000.0
        q, p, f = (_ms("engine.queue"), _ms("engine.prefill"),
                   _ms("engine.first_tick"))
        out["ttft"] = {"queue_ms": round(q, 3),
                       "prefill_ms": round(p, 3),
                       "first_tick_ms": round(f, 3),
                       "ttft_ms": round(q + p + f, 3)}
        if rid is not None:
            out["ttft"]["request_id"] = rid
    # A replica's start (the root is the controller's span): the
    # phases' seconds as the replica's own books have them
    # (start_seconds), and what was compiled or loaded inside which.
    if any(s["name"] == "serve.replica_start" for s in spans):
        iv, by_id = {}, {}
        for s in spans:
            by_id[s["span_id"]] = s
            phase = _BOOK_PHASES.get(s["name"])
            if phase is not None and phase not in iv:
                iv[phase] = (s["ts"] / 1e6, (s["ts"] + s["dur"]) / 1e6)
        out["start"] = {
            k + "_s": v for k, v in start_seconds(iv).items()}
        out["start"]["compiles"] = [
            {"fun_name": s["args"].get("fun_name"),
             "from_cache": bool(s["args"].get("from_cache")),
             "s": round(s["dur"] / 1e6, 6),
             "inside": by_id.get(s["parent_id"], {}).get("name")}
            for s in sorted(spans, key=lambda s: s["ts"])
            if s["name"] == "jax.compile"]
    return out


def format_trace(tree: dict) -> str:
    """Human-readable rendering of assemble()'s result for
    ``rt trace``: indented span tree (name, duration, pid,
    annotations) + the per-stage breakdown."""
    lines = [f"trace {tree['trace_id']}: {len(tree['spans'])} spans "
             f"across {len(tree['processes'])} process(es) "
             f"{tree['processes']}"]

    def _fmt(s, depth):
        args = s["args"]
        extra = ""
        if args:
            kv = ", ".join(f"{k}={v}" for k, v in sorted(args.items()))
            extra = f"  [{kv}]"
        lines.append(f"{'  ' * depth}{s['name']} "
                     f"{s['dur'] / 1000.0:.2f}ms  pid={s['pid']}{extra}")
        for n in s.get("events", ()):
            nkv = ", ".join(f"{k}={v}" for k, v in
                            sorted((n.get("args") or {}).items())
                            if k != "parent_id")
            lines.append(f"{'  ' * (depth + 1)}* {n['name']}"
                         + (f"  [{nkv}]" if nkv else ""))
        for c in s["children"]:
            _fmt(c, depth + 1)

    for r in tree["roots"]:
        _fmt(r, 1)
    bd = tree["breakdown"]
    if bd.get("ttft"):
        t = bd["ttft"]
        lines.append(f"  TTFT {t['ttft_ms']}ms = queue {t['queue_ms']}ms"
                     f" + prefill {t['prefill_ms']}ms + first tick "
                     f"{t['first_tick_ms']}ms")
    if bd.get("start"):
        t = bd["start"]

        def _s(key):
            v = t.get(key + "_s")
            return "?" if v is None else f"{v:.3f}s"
        lines.append(
            f"  start {_s('root')} = spawn {_s('spawn')} + boot "
            f"{_s('boot')} + unpickle {_s('unpickle')} + load "
            f"{_s('load')} + build {_s('build')} + unaccounted "
            f"{_s('unaccounted')}; warm {_s('warm')}, ending "
            f"{_s('warm_after_ready')} after ready; the chip's opening "
            f"{_s('backend_init')}")
        for c in t["compiles"]:
            lines.append(
                f"    {'loaded  ' if c['from_cache'] else 'compiled'} "
                f"{c['s']:.3f}s  {c['fun_name']}  ({c['inside']})")
    lines.append("  stages:")
    for name, agg in bd["stages"].items():
        lines.append(f"    {name}: n={agg['count']} "
                     f"total={agg['total_ms']}ms max={agg['max_ms']}ms")
    return "\n".join(lines)
