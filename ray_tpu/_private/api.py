"""Public API: init/shutdown/remote/get/put/wait and cluster introspection.

Reference: python/ray/_private/worker.py — init :1024, connect :1846,
get :2188, remote decorator overloads :122-366.
"""

from __future__ import annotations

import asyncio
import atexit
import inspect
import os
import threading
import time

from ray_tpu import exceptions as rexc
from ray_tpu._private import protocol
from ray_tpu._private import tracing as _tracing
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.config import apply_system_config
from ray_tpu._private.node import InProcessNode, new_session_dir
from ray_tpu._private.worker import CoreWorker, MODE_DRIVER

_state_lock = threading.RLock()
_head_node: InProcessNode | None = None
_loop = None
_loop_thread = None


def _ensure_loop():
    global _loop, _loop_thread
    if _loop is not None and _loop_thread.is_alive():
        return _loop
    ready = threading.Event()

    def _main():
        global _loop
        _loop = asyncio.new_event_loop()
        asyncio.set_event_loop(_loop)
        protocol.enable_eager_tasks(_loop)
        ready.set()
        _loop.run_forever()

    _loop_thread = threading.Thread(target=_main, name="ray_tpu-io",
                                    daemon=True)
    _loop_thread.start()
    ready.wait(30)
    return _loop


def init(address: str | None = None, *, num_cpus=None, num_tpus=None,
         num_gpus=None, resources=None, object_store_memory=None,
         namespace: str = "default", ignore_reinit_error: bool = False,
         local_mode: bool = False,
         _system_config: dict | None = None, log_to_driver: bool = True,
         runtime_env=None, **kwargs):
    """Start a cluster on this machine (address=None) or connect to one
    ("host:gcs_port").  local_mode=True runs everything inline in this
    process (reference: ray.init(local_mode=True)) — no workers, no
    store; for debugging and runtime-free unit tests."""
    global _head_node
    t_init = time.time()
    with _state_lock:
        if worker_mod.global_worker is not None and \
                worker_mod.global_worker.connected:
            if ignore_reinit_error:
                return worker_mod.global_worker
            raise RuntimeError("ray_tpu.init() called twice "
                               "(use ignore_reinit_error=True)")
        if _system_config:
            apply_system_config(_system_config)
        if local_mode:
            from ray_tpu._private.local_mode import LocalModeWorker
            w = LocalModeWorker(namespace=namespace)
            worker_mod.global_worker = w
            atexit.register(shutdown)
            return w
        if num_tpus is None:
            num_tpus = num_gpus
        loop = _ensure_loop()
        if address is None:
            _head_node = InProcessNode(
                loop, head=True, num_cpus=num_cpus, num_tpus=num_tpus,
                resources=resources, object_store_memory=object_store_memory,
                session_dir=new_session_dir()).start()
            gcs_addr = _head_node.gcs_addr
            raylet_addr = _head_node.raylet_addr
            store_path = _head_node.raylet.store_path
            store_cap = _head_node.raylet.store_capacity
            driver_host = "127.0.0.1"
        else:
            host, port = address.split(":")
            gcs_addr = (host, int(port))
            raylet_addr, store_path, store_cap = _discover_local_raylet(
                loop, gcs_addr)
            # Advertise the LOCAL RAYLET's address: it registered with
            # the cluster-reachable --node-ip, so peers can dial the
            # driver back on it (owner protocol).  Multi-NIC machines
            # may route to the GCS on a different interface than the
            # cluster data network, so the route-to-GCS guess is only
            # the fallback when the raylet is loopback-bound.
            if raylet_addr[0] not in ("127.0.0.1", "localhost"):
                driver_host = raylet_addr[0]
            else:
                driver_host = _routable_host(gcs_addr[0])
        cw = CoreWorker(MODE_DRIVER, gcs_addr, raylet_addr=raylet_addr,
                        store_path=store_path, store_cap=store_cap,
                        host=driver_host)
        cw.loop = loop
        fut = asyncio.run_coroutine_threadsafe(cw._connect(), loop)
        fut.result(60)
        cw.connected = True
        worker_mod.global_worker = cw
        from ray_tpu._private import usage
        try:
            usage.on_init(
                _head_node.session_dir if _head_node is not None else None,
                os.path.basename(
                    _head_node.session_dir) if _head_node is not None
                else f"client-{os.getpid()}")
        except Exception:
            pass  # usage stats must never block init
        atexit.register(shutdown)
        # What precedes any deploy or job, once a driver: GCS, raylet
        # and this worker up (a trace of its own).
        _tracing.record(
            "rt", "rt.init", t_init, time.time() - t_init,
            trace={"trace_id": _tracing.fresh_id(),
                   "span_id": _tracing.fresh_id(), "parent_id": None},
            args={"head": address is None})
        return cw


def _routable_host(peer_host: str) -> str:
    """The local interface address that routes to `peer_host` —
    what this process should ADVERTISE so that host can dial back."""
    if peer_host in ("127.0.0.1", "localhost"):
        return "127.0.0.1"
    import socket
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect((peer_host, 1))  # no packets; just picks a route
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def _discover_local_raylet(loop, gcs_addr):
    """Connecting to an existing cluster: find this machine's raylet."""
    from ray_tpu._private import protocol

    async def _find():
        conn = await protocol.Connection.connect(gcs_addr[0], gcs_addr[1],
                                                 name="probe")
        nodes = await conn.request("get_nodes", {})
        await conn.close()
        return nodes

    nodes = asyncio.run_coroutine_threadsafe(_find(), loop).result(30)
    import socket

    def _is_local(host: str) -> bool:
        # An address is local iff this machine can BIND to it — covers
        # loopback, the hostname, AND routable interface addresses
        # (multi-host nodes advertise --node-ip, not 127.0.0.1).
        if host in ("0.0.0.0", "::"):
            # Wildcards bind anywhere; a node advertising one is
            # misconfigured, never "local".
            return False
        if host in ("127.0.0.1", "localhost", socket.gethostname()):
            return True
        try:
            with socket.socket() as s:
                s.bind((host, 0))
            return True
        except OSError:
            return False

    for n in nodes:
        if n["alive"] and _is_local(n["addr"][0]):
            # store path/capacity arrive in the raylet's register_worker
            # reply (see CoreWorker._connect)
            return tuple(n["addr"]), None, None
    raise RuntimeError("no alive raylet found on this machine")


def shutdown():
    global _head_node
    from ray_tpu._private import usage
    usage.on_shutdown()
    with _state_lock:
        cw = worker_mod.global_worker
        if cw is not None:
            cw.shutdown()
            worker_mod.global_worker = None
        if _head_node is not None:
            _head_node.kill()
            _head_node = None


def is_initialized() -> bool:
    return (worker_mod.global_worker is not None
            and worker_mod.global_worker.connected)


def remote(*args, **kwargs):
    """@ray_tpu.remote decorator for functions and classes (reference:
    python/ray/_private/worker.py:122-366)."""
    from ray_tpu.actor import ActorClass
    from ray_tpu.remote_function import RemoteFunction

    def _make(target, opts):
        if inspect.isclass(target):
            return ActorClass(target, **opts)
        return RemoteFunction(target, **opts)

    if len(args) == 1 and not kwargs and callable(args[0]):
        # Any callable works bare: python/builtin functions, classes,
        # functools.partial, callables with __call__.
        return _make(args[0], {})
    if args:
        raise TypeError("@remote takes keyword options only, e.g. "
                        "@remote(num_cpus=2)")

    def decorator(target):
        return _make(target, kwargs)
    return decorator


def _worker() -> CoreWorker:
    cw = worker_mod.global_worker
    if cw is None or not cw.connected:
        raise RuntimeError("ray_tpu.init() must be called first")
    return cw


def _gcs():
    """Typed GCS accessor facade for the connected driver (reference:
    gcs/gcs_client/accessor.h via global_state_accessor.h)."""
    from ray_tpu._private.gcs_client import global_gcs_client
    return global_gcs_client()


def get(refs, *, timeout=None):
    return _worker().get(refs, timeout=timeout)


def put(value) -> "ObjectRef":
    return _worker().put(value)


def wait(refs, *, num_returns=1, timeout=None, fetch_local=True):
    if not isinstance(refs, list):
        raise TypeError("ray_tpu.wait() expects a list of ObjectRefs")
    return _worker().wait(refs, num_returns=num_returns, timeout=timeout,
                          fetch_local=fetch_local)


def cancel(ref, *, force: bool = False) -> bool:
    """Cancel a task (reference: ray.cancel worker.py): True if the task
    was stopped (dequeued, or its worker killed with force=True)."""
    return _worker().cancel_task(ref, force=force)


def kill(actor, *, no_restart=True):
    from ray_tpu.actor import ActorHandle
    if not isinstance(actor, ActorHandle):
        raise TypeError("ray_tpu.kill() expects an actor handle")
    w = _worker()
    if getattr(w, "mode", None) == "local":
        w.kill_actor_local(actor._ray_actor_id)
        return
    _gcs().actors.kill(actor._ray_actor_id, no_restart=no_restart)


def get_actor(name: str, namespace: str = "default"):
    from ray_tpu.actor import ActorHandle
    w = _worker()
    if getattr(w, "mode", None) == "local":
        view = w.get_named_actor(name, namespace)
    else:
        view = _gcs().actors.get_by_name(name, namespace)
    if view is None:
        raise ValueError(f"no actor named '{name}'")
    return ActorHandle(view["actor_id"], view.get("class_name", ""),
                       addr=tuple(view["addr"]) if view.get("addr") else None)


def nodes():
    out = []
    for v in _gcs().nodes.get_all():
        out.append({
            "NodeID": v["node_id"].hex(),
            "Alive": v["alive"],
            "NodeManagerAddress": v["addr"][0],
            "NodeManagerPort": v["addr"][1],
            "Resources": v["resources"],
            "Available": v.get("available", {}),
            "Labels": v.get("labels", {}),
        })
    return out


def cluster_resources():
    return _gcs().nodes.cluster_resources()["total"]


def available_resources():
    return _gcs().nodes.cluster_resources()["available"]


def wait_placement_group_ready(pg, timeout: float = 60.0) -> bool:
    view = _gcs().placement_groups.wait_ready(pg.id, timeout=timeout)
    return view is not None and view["state"] == "CREATED"


class RuntimeContext:
    def __init__(self, worker: CoreWorker):
        self._worker = worker

    @property
    def job_id(self):
        return self._worker.job_id

    @property
    def node_id(self):
        return self._worker.node_id

    @property
    def actor_id(self):
        return self._worker.actor_id

    @property
    def task_id(self):
        return self._worker.exec_ctx.task_id

    def get_job_id(self):
        return self.job_id.hex()

    def get_node_id(self):
        return self.node_id.hex() if self.node_id else None

    def get_actor_id(self):
        return self.actor_id.hex() if self.actor_id else None

    def get_tpu_ids(self):
        return get_tpu_ids()


def get_runtime_context() -> RuntimeContext:
    return RuntimeContext(_worker())


def get_tpu_ids() -> list:
    """Chip indices the raylet granted to THIS task/actor's lease
    (reference: ray.get_gpu_ids over GPU resource instances).  Empty in
    the driver or for leases without a TPU resource."""
    w = _worker()
    ids = list(getattr(w.exec_ctx, "tpu_ids", []) or [])
    if ids:
        return ids
    return list(getattr(w, "_actor_tpu_ids", []) or [])


def get_gpu_ids() -> list:
    """Reference-compatible alias of get_tpu_ids (ray.get_gpu_ids):
    scripts written against the reference keep working; on this
    framework the accelerator resource is TPU chips."""
    return get_tpu_ids()


def timeline(filename: str | None = None):
    """Chrome-trace events for every process in the cluster (reference:
    `ray timeline`, python/ray/_private/state.py chrome_tracing_dump —
    events aggregated from the per-process telemetry pushed to the GCS
    KV).

    STALE CONVENIENCE VIEW: each process's KV push carries only the
    freshest ring tail and lags by the push period; the authoritative
    path is ``cluster_trace()`` (the ``dump_trace`` RPC pull, whole
    rings on demand).  Truncation is self-describing: every process
    contributes a ``trace.ring_meta`` instant event recording its drop
    count and ring coverage window."""
    import json
    import pickle

    from ray_tpu._private import tracing as _tracing
    w = _worker()
    keys = w._run(w._gcs_request("kv_keys",
                                 {"ns": "telemetry", "prefix": b""}))["keys"]
    events = []
    for key in keys:
        blob = w._run(w._gcs_request("kv_get",
                                     {"ns": "telemetry",
                                      "key": key}))["value"]
        if blob is None:
            continue
        try:
            payload = pickle.loads(blob)
            events.extend(payload.get("profile", []))
            stats = payload.get("trace_stats")
            if stats is not None:
                stats = dict(stats, pid=payload.get("pid"))
                events.append(_tracing.meta_event(stats))
        except Exception:
            continue
    # The driver's own events never round-trip through the KV push delay.
    events.extend(w._profile_events)
    events.append(_tracing.meta_event())
    events.sort(key=lambda e: e.get("ts", 0))
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


def cluster_trace(stats_only: bool = False,
                  filename: str | None = None):
    """Pull every process's span ring NOW (the authoritative trace
    path): the driver's own ring, the GCS's, and — via one
    ``dump_trace`` RPC per raylet, fanned out to its registered
    workers — every node process.  Returns
    ``{"processes": [per-process dump], "events": merged chrome-trace
    list}`` (events omitted with stats_only); each process contributes
    a ``trace.ring_meta`` event so truncation is visible.  Backs
    ``rt timeline --cluster`` and ``rt trace <id>``."""
    import asyncio
    import json

    from ray_tpu._private import protocol
    from ray_tpu._private import tracing as _tracing
    w = _worker()

    async def _collect():
        procs = []
        try:
            d = await w._gcs_request("dump_trace",
                                     {"stats_only": stats_only})
            procs.append(d)
        except Exception as e:
            procs.append({"role": "gcs",
                          "error": f"{type(e).__name__}: {e}"})
        nodes = await w._gcs_request("get_nodes", {})

        async def _one(view):
            try:
                conn = await protocol.Connection.connect(
                    view["addr"][0], view["addr"][1],
                    name="trace-pull", timeout=10)
                try:
                    return await conn.request(
                        "dump_trace", {"stats_only": stats_only,
                                       "include_workers": True},
                        timeout=30.0)
                finally:
                    await conn.close()
            except Exception as e:
                return {"role": "raylet",
                        "node_id": view["node_id"].hex(),
                        "error": f"{type(e).__name__}: {e}"}

        replies = await asyncio.gather(
            *[_one(v) for v in nodes if v.get("alive")])
        for r in replies:
            if "processes" in r:
                procs.extend(r["processes"])
            else:
                procs.append(r)
        return procs

    procs = w._run(_collect())
    procs.append(dict(_tracing.dump(stats_only=stats_only),
                      role="driver"))
    # One ring can be reached through several doors (the GCS, every
    # in-process raylet, and the driver itself may SHARE a process in
    # test clusters): keep one dump per ring — the largest, so a
    # stats_only stub never shadows a full dump.  The key is the ring's
    # per-process random id, NOT the bare OS pid: two containerized
    # nodes routinely hold workers with the same pid, and deduping on
    # pid would silently discard one node's whole ring.
    by_ring: dict = {}
    for p in procs:
        # Error stubs carry no ring_id; their worker/node id is still
        # unique cluster-wide, unlike a containerized pid.
        key = (p.get("ring_id") or p.get("worker_id")
               or p.get("node_id") or p.get("pid"))
        if key is None:
            by_ring[object()] = p
            continue
        cur = by_ring.get(key)
        if cur is None or len(p.get("events", ())) > \
                len(cur.get("events", ())):
            by_ring[key] = p
    procs = list(by_ring.values())
    out = {"processes": [
        {k: v for k, v in p.items() if k != "events"} for p in procs]}
    if not stats_only:
        events = []
        for p in procs:
            events.extend(p.get("events", ()))
            if "depth" in p:
                events.append(_tracing.meta_event(p))
        events.sort(key=lambda e: e.get("ts", 0))
        out["events"] = events
        if filename:
            with open(filename, "w") as f:
                json.dump(events, f)
    return out


def get_trace(trace_id: str):
    """Assemble ONE request's span tree from a cluster-wide ring pull:
    ``cluster_trace()`` merged events filtered to ``trace_id``, linked
    parent→child (cross-process via the propagated span ids), with the
    derived per-stage latency breakdown (TTFT decomposition when the
    serve/engine taxonomy is present).  Backs ``rt trace <id>``."""
    from ray_tpu._private import tracing as _tracing
    events = cluster_trace()["events"]
    return _tracing.assemble(events, trace_id)
