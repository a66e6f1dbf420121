"""The replica actor: hosts one copy of the user's deployment.

Reference: python/ray/serve/_private/replica.py — RayServeReplica (:231)
wrapping the user callable (:57 create_replica_wrapper), handle_request
dispatch, reconfigure(user_config), health checks.  TPU-native detail:
replicas that request TPU resources are leased TPU workers, so jax inits
the chip inside the replica process and models stay resident in HBM
between requests.
"""

from __future__ import annotations

import asyncio
import inspect
import os
import pickle
import time
from typing import Any, Dict, Optional

import cloudpickle

from ray_tpu._private import tracing as _tracing


class Request:
    """Minimal HTTP-ish request container handed to deployments reached
    through the proxy (reference passes a starlette Request)."""

    __slots__ = ("method", "path", "query", "body", "headers")

    def __init__(self, method: str = "GET", path: str = "/",
                 query: Optional[Dict[str, str]] = None, body: bytes = b"",
                 headers: Optional[Dict[str, str]] = None):
        self.method = method
        self.path = path
        self.query = query or {}
        self.body = body
        self.headers = headers or {}

    def json(self):
        import json
        return json.loads(self.body or b"null")

    def __reduce__(self):
        return (Request, (self.method, self.path, self.query, self.body,
                          self.headers))


class RTServeReplica:
    """Actor class for one replica (created by the controller)."""

    def __init__(self, deployment_name: str, replica_tag: str,
                 serialized_def: bytes, init_args: tuple,
                 init_kwargs: dict, user_config: Any, version: str):
        with _tracing.start_span("serve", "serve.replica_init"):
            self._init(deployment_name, replica_tag, serialized_def,
                       init_args, init_kwargs, user_config, version)

    def _init(self, deployment_name, replica_tag, serialized_def,
              init_args, init_kwargs, user_config, version):
        self.deployment_name = deployment_name
        self.replica_tag = replica_tag
        self.version = version
        self._num_ongoing = 0
        self._num_processed = 0
        self._streams: Dict[str, Dict[str, Any]] = {}
        self._stream_seq = 0
        # method name -> (target, is_async): the per-request getattr +
        # inspect.iscoroutinefunction probes are paid once per method,
        # not once per call (the unary fast path).
        self._target_cache: Dict[str, tuple] = {}
        from concurrent.futures import ThreadPoolExecutor
        self._sync_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix=f"replica-{replica_tag}")
        # Unpickling the definition imports its modules (for an
        # LLMServer the engine, the models and what they import of
        # jax): seconds of a start that are nobody's else.
        with _tracing.start_span("serve", "serve.replica_unpickle"):
            body = cloudpickle.loads(serialized_def)
        # Publish the replica context BEFORE user __init__ runs, so the
        # constructor itself can call serve.get_replica_context()
        # (reference: replica.py sets it in create_replica_wrapper).
        from ray_tpu.serve import context as _serve_ctx
        _serve_ctx._set_internal_replica_context(
            deployment_name, replica_tag)
        if inspect.isclass(body):
            self.callable = body(*init_args, **init_kwargs)
        else:
            self.callable = body
        _serve_ctx._set_internal_replica_context(
            deployment_name, replica_tag, servable_object=self.callable)
        if user_config is not None:
            self._reconfigure_sync(user_config)

    def start_acknowledged(self, t0: float, t1: float):
        """The controller's word that it knows this replica is ready:
        the root span's two timestamps, for the start's books."""
        _tracing.start_note("root", (t0, t1))

    def _reconfigure_sync(self, user_config):
        rc = getattr(self.callable, "reconfigure", None)
        if rc is None:
            raise ValueError(
                f"{self.deployment_name}: user_config set but deployment "
                "has no reconfigure(user_config) method")
        rc(user_config)

    def reconfigure(self, user_config, version: str):
        if user_config is not None:
            self._reconfigure_sync(user_config)
        self.version = version
        self._target_cache.clear()
        return True

    def check_health(self):
        hc = getattr(self.callable, "check_health", None)
        if hc is not None:
            hc()
        return True

    def _resolve_cached(self, method_name: str) -> tuple:
        """(target, is_async) with the inspect probes paid once per
        method name instead of once per call."""
        hit = self._target_cache.get(method_name)
        if hit is None:
            target = self._resolve_target(method_name)
            is_async = inspect.iscoroutinefunction(target) or (
                not inspect.isfunction(target)
                and not inspect.ismethod(target)
                and inspect.iscoroutinefunction(
                    getattr(target, "__call__", None)))
            hit = self._target_cache[method_name] = (target, is_async)
        return hit

    async def handle_request(self, method_name: str, args: tuple,
                             kwargs: dict):
        """One query.  `method_name` '' means call the deployment itself
        (function deployment or __call__)."""
        self._num_ongoing += 1
        try:
            target, is_async = self._resolve_cached(method_name)
            if is_async:
                return await target(*args, **kwargs)
            return await self._call_sync_target(target, args, kwargs)
        finally:
            self._num_ongoing -= 1
            self._num_processed += 1

    async def _call_target(self, target, args, kwargs):
        """Invoke a resolved target with the loop-protection rule shared
        by the unary and streaming paths: sync user code must not block
        the replica's event loop (health checks, metrics, and concurrent
        queries up to max_concurrent_queries ride the same loop)."""
        if inspect.iscoroutinefunction(target) or (
                not inspect.isfunction(target)
                and not inspect.ismethod(target)
                and inspect.iscoroutinefunction(
                    getattr(target, "__call__", None))):
            return await target(*args, **kwargs)
        return await self._call_sync_target(target, args, kwargs)

    async def _call_sync_target(self, target, args, kwargs):
        loop = asyncio.get_running_loop()
        result = await loop.run_in_executor(
            self._sync_pool, lambda: target(*args, **kwargs))
        if inspect.iscoroutine(result):
            result = await result
        return result

    # -- streaming calls ------------------------------------------------
    #
    # Async generators can't ride a single actor-call result, so a
    # streaming request is split into (1) handle_request_streaming,
    # which starts the generator, pumps it into a buffer, and returns a
    # stream id, then (2) a cursor-based stream_next long-poll that
    # drains NEW items as soon as any exist.  One long-poll returns
    # every item produced since the last poll, so a fast producer is
    # amortized (many tokens per RPC) while a slow one still delivers
    # each token the moment it appears.

    def _resolve_target(self, method_name: str):
        target = self.callable
        if method_name:
            target = getattr(self.callable, method_name)
        elif not callable(target):
            target = self.callable.__call__
        return target

    async def handle_request_streaming(self, method_name: str,
                                       args: tuple, kwargs: dict,
                                       resume: Optional[Dict] = None
                                       ) -> Dict:
        """Start a streaming query.  If the target produces an async
        generator (an `async def ... yield` method, or a coroutine
        returning an async iterable) -> {"stream_id": sid, "resumable":
        bool} to poll with stream_next.  Otherwise the call has ALREADY
        run to completion and its value rides back as {"unary": result}
        — one invocation either way, so the caller (proxy) can fall
        back to a normal response without re-running side effects.

        `resume` is the router's failover cursor ({"delivered": n,
        "items": [...]}): targets marked serve.resumable receive it as
        the `_resume` keyword and must yield only what comes AFTER the
        delivered prefix."""
        self._sweep_stale_streams()
        self._ensure_stream_sweeper()
        target = self._resolve_target(method_name)
        resumable = bool(getattr(target, "__serve_resumable__", False))
        if not resumable:
            # Proxy path resolves a callable INSTANCE (method_name ""),
            # so the marker lives on its __call__, not on the instance.
            resumable = bool(getattr(
                getattr(target, "__call__", None),
                "__serve_resumable__", False))
        if resume is not None:
            if resumable:
                kwargs = {**kwargs, "_resume": resume}
            elif resume.get("delivered") or resume.get("items"):
                raise TypeError(
                    f"{self.deployment_name}.{method_name or '__call__'}"
                    " is not resumable (mark it with @serve.resumable "
                    "to accept a failover cursor)")
            # else: a hint-only cursor (kv_origin at delivered=0) has
            # nothing to replay — dropped, the stream runs whole.
        if inspect.isasyncgenfunction(target):
            ait = target(*args, **kwargs)
        else:
            self._num_ongoing += 1
            try:
                result = await self._call_target(target, args, kwargs)
            finally:
                self._num_ongoing -= 1
            if inspect.isgenerator(result):
                # Plain `def ... yield` deployment: drive it from the
                # sync pool so a blocking body can't stall the
                # replica's event loop (and a generator must never be
                # pickled into a unary reply).
                result = self._drive_sync_generator(result)
            if not hasattr(result, "__aiter__"):
                self._num_processed += 1
                return {"unary": result}
            ait = result
        self._stream_seq += 1
        stream_id = f"{self.replica_tag}:{self._stream_seq}"
        state = {"buf": [], "done": False, "error": None,
                 "event": asyncio.Event(), "task": None,
                 "last_poll": time.monotonic()}
        self._streams[stream_id] = state
        self._num_ongoing += 1  # the slot stays held while streaming
        state["task"] = asyncio.get_running_loop().create_task(
            self._pump_stream(stream_id, ait.__aiter__()))
        return {"stream_id": stream_id, "resumable": resumable}

    # A consumer that vanishes (handle process killed, or a cancel RPC
    # lost in flight) stops polling without ever sending stream_cancel;
    # its buffered tokens would otherwise sit in _streams forever — and,
    # worse, the underlying generator would keep producing into a dead
    # buffer (an engine request burning KV pages and decode slots).
    # Any stream unpolled for this long is torn down, both at the next
    # streaming admission and by a periodic sweeper, and the teardown
    # AWAITS the pump task so the generator's finally runs (the engine
    # request is cancelled, its pages/slots reclaimed).
    STREAM_IDLE_TTL_S = float(os.environ.get("RT_SERVE_STREAM_TTL_S",
                                             "300"))
    STREAM_SWEEP_PERIOD_S = float(os.environ.get(
        "RT_SERVE_STREAM_SWEEP_S", "30"))

    _sweep_task = None

    def _ensure_stream_sweeper(self):
        """Periodic sweep: a replica whose streaming consumers all
        vanished sees no further admissions, so sweeping only on
        admission would leak the abandoned engine requests forever."""
        if self._sweep_task is None or self._sweep_task.done():
            self._sweep_task = asyncio.get_running_loop().create_task(
                self._sweep_loop())

    async def _sweep_loop(self):
        while True:
            await asyncio.sleep(self.STREAM_SWEEP_PERIOD_S)
            self._sweep_stale_streams()

    def _sweep_stale_streams(self):
        now = time.monotonic()
        stale = [sid for sid, st in self._streams.items()
                 if now - st["last_poll"] > self.STREAM_IDLE_TTL_S]
        for sid in stale:
            state = self._streams.pop(sid, None)
            if state is None:
                continue
            task = state["task"]
            if task is not None and not task.done():
                task.cancel()
                # Reap in the background: awaiting confirms the user
                # generator unwound (its finally cancels the engine
                # request, freeing KV pages + the decode slot) instead
                # of trusting a fire-and-forget cancel.
                asyncio.get_running_loop().create_task(self._reap(task))

    @staticmethod
    async def _reap(task):
        try:
            await task
        except BaseException:
            pass

    async def _drive_sync_generator(self, gen):
        """Adapt a sync generator to async: each next() runs on the
        replica's sync pool."""
        sentinel = object()
        cfut = None
        try:
            while True:
                cfut = self._sync_pool.submit(
                    lambda: next(gen, sentinel))
                item = await asyncio.wrap_future(cfut)
                cfut = None  # consumed; safe to close directly
                if item is sentinel:
                    return
                yield item
        finally:
            # On cancellation the pool thread may still be INSIDE
            # next(gen) — closing a generator mid-execution raises
            # "generator already executing" and skips its cleanup.
            # Chain the close behind the in-flight call instead.
            def _close():
                try:
                    gen.close()
                except Exception:
                    pass
            if cfut is not None and not cfut.done():
                cfut.add_done_callback(
                    lambda _f: self._sync_pool.submit(_close))
            else:
                _close()

    async def _pump_stream(self, stream_id: str, ait):
        state = self._streams[stream_id]
        t0 = time.time()
        n = 0
        try:
            async for item in ait:
                state["buf"].append(item)
                state["event"].set()
                n += 1
        except asyncio.CancelledError:
            raise
        except Exception as e:
            state["error"] = e
        finally:
            state["done"] = True
            state["event"].set()
            self._num_ongoing -= 1
            self._num_processed += 1
            # Stream-lifetime span in the REPLICA process: the pump
            # task inherited the actor-task trace context, so engine
            # stage spans and this one land in the request's trace.
            _tracing.record("serve", "serve.replica_stream", t0,
                            time.time() - t0,
                            trace=_tracing.child_span(),
                            args={"stream_id": stream_id, "items": n,
                                  "deployment": self.deployment_name})

    async def stream_next(self, stream_id: str, cursor: int,
                          timeout_s: float = 10.0) -> Dict:
        """Long-poll items[cursor:]: returns as soon as at least one new
        item exists (or the stream ends / timeout_s elapses).  The
        cursor makes polls idempotent — a retried RPC re-reads instead
        of skipping.  {"items": [...], "done": bool, "error": exc|None};
        the terminal poll (done=True with all items consumed) drops the
        server-side state."""
        state = self._streams.get(stream_id)
        if state is None:
            raise KeyError(f"unknown stream {stream_id!r} (already "
                           "finished, cancelled, or never started)")
        state["last_poll"] = time.monotonic()
        deadline = time.monotonic() + timeout_s
        while len(state["buf"]) <= cursor and not state["done"]:
            remain = deadline - time.monotonic()
            if remain <= 0:
                return {"items": [], "done": False, "error": None}
            state["event"].clear()
            try:
                await asyncio.wait_for(state["event"].wait(),
                                       timeout=remain)
            except asyncio.TimeoutError:
                return {"items": [], "done": False, "error": None}
        items = state["buf"][cursor:]
        done = state["done"]
        out = {"items": items, "done": done,
               "error": state["error"] if done else None}
        if done:
            self._streams.pop(stream_id, None)
        return out

    async def stream_cancel(self, stream_id: str) -> bool:
        """Tear a stream down early (client disconnected): cancels the
        pump task, which closes the user generator (its finally blocks
        run — e.g. the engine frees the request's slot)."""
        state = self._streams.pop(stream_id, None)
        if state is None:
            return False
        task = state["task"]
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
        return True

    def get_metadata(self) -> Dict:
        return {"deployment": self.deployment_name,
                "replica_tag": self.replica_tag,
                "version": self.version}

    def num_ongoing_requests(self) -> int:
        return self._num_ongoing

    def get_autoscale_metrics(self) -> Dict:
        """Load signals for the controller's autoscaler: the in-flight
        count always, plus whatever the deployment itself publishes via
        an `autoscale_metrics()` method (the LLM engine exposes queue
        depth, slot occupancy, and KV free pages this way) — the
        controller scales on REAL saturation gauges, not just the
        request count."""
        out: Dict[str, Any] = {"ongoing": self._num_ongoing}
        am = getattr(self.callable, "autoscale_metrics", None)
        if am is not None:
            try:
                extra = am()
                if isinstance(extra, dict):
                    out.update(extra)
            except Exception:
                pass  # a broken gauge must not break autoscaling
        return out

    async def prepare_for_shutdown(self, timeout_s: float = 10.0):
        """Drain: wait for in-flight requests to finish (reference:
        replica.py graceful shutdown loop)."""
        deadline = time.monotonic() + timeout_s
        while self._num_ongoing > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        return True
