"""Serve public API.

Reference: python/ray/serve/api.py — @serve.deployment (deployment.py),
serve.start, serve.run (:428), serve.delete, serve.shutdown,
serve.get_deployment_handle.  The controller is a detached named actor so
deployments outlive the driver that created them.
"""

from __future__ import annotations

import inspect
import logging
import uuid
from typing import Any, Callable, Dict, List, Optional, Union

import cloudpickle

import ray_tpu
from ray_tpu._private import tracing as _tracing
from ray_tpu.serve.config import (AutoscalingConfig, DeploymentConfig,
                                  ReplicaConfig)
from ray_tpu.serve.handle import DeploymentHandle
from ray_tpu.serve._private.replica import Request
from ray_tpu.serve._private.controller import (CONTROLLER_NAME,
                                               ServeController)

logger = logging.getLogger(__name__)

_http_proxy_info: Optional[Dict] = None


def start(detached: bool = True, http_options: Optional[Dict] = None,
          _start_proxy: bool = False):
    """Start (or connect to) the Serve instance: the controller actor and,
    optionally, the HTTP proxy."""
    # Once a driver; the controller's (and a proxy's) own start — the
    # raylet's wait for a worker, the worker's boot, the creation task —
    # links under it, and may outlast it: the controller is asked for
    # here, not waited for.
    with _tracing.span("serve", "serve.start"):
        controller = _get_or_create_controller()
        if _start_proxy:
            _ensure_http_proxy(controller, http_options or {})
    return controller


def _get_or_create_controller():
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        pass
    cls = ray_tpu.remote(ServeController)
    controller = cls.options(
        name=CONTROLLER_NAME, lifetime="detached", num_cpus=0.1,
        max_concurrency=1000).remote()
    # Kick the reconciliation loop (runs forever inside the actor).
    controller.run_control_loop.options(num_returns=0).remote()
    return controller


_http_proxy_addrs: List[Dict] = []


def _start_one_proxy(name: str, http_options: Dict, strategy=None) -> Dict:
    from ray_tpu.serve._private.http_proxy import HTTPProxyActor
    try:
        proxy = ray_tpu.get_actor(name)
    except Exception:
        cls = ray_tpu.remote(HTTPProxyActor)
        opts = dict(name=name, lifetime="detached", num_cpus=0.1,
                    max_concurrency=1000)
        if strategy is not None:
            opts["scheduling_strategy"] = strategy
        proxy = cls.options(**opts).remote(
            http_options.get("host", "127.0.0.1"),
            http_options.get("port", 0), CONTROLLER_NAME,
            http_options.get("access_log", True))
        proxy.run.options(num_returns=0).remote()
    return ray_tpu.get(proxy.ready.remote(), timeout=60)


def _ensure_http_proxy(controller, http_options: Dict) -> Dict:
    """Start ingress: one proxy by default, or one per node with
    location="EveryNode" (reference: per-node HTTPProxyActors managed by
    http_state.py)."""
    global _http_proxy_info, _http_proxy_addrs
    if _http_proxy_info is not None:
        return _http_proxy_info
    if http_options.get("location") == "EveryNode":
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        addrs = []
        for node in ray_tpu.nodes():
            if not node.get("Alive", True):
                continue
            nid = node["NodeID"]
            addrs.append(_start_one_proxy(
                f"SERVE_PROXY::{nid[:8]}", http_options,
                NodeAffinitySchedulingStrategy(node_id=nid)))
        _http_proxy_addrs = addrs
        _http_proxy_info = addrs[0]
        return _http_proxy_info
    _http_proxy_info = _start_one_proxy("SERVE_PROXY", http_options)
    _http_proxy_addrs = [_http_proxy_info]
    return _http_proxy_info


def get_proxy_addresses() -> List[Dict]:
    """All ingress endpoints (one per node with location=EveryNode)."""
    return list(_http_proxy_addrs)


class Deployment:
    """The declarative unit: a class/function + target config.  Immutable;
    .options() returns a copy (reference: serve/deployment.py)."""

    def __init__(self, body: Union[Callable, type], name: str,
                 config: DeploymentConfig, init_args: tuple = (),
                 init_kwargs: Optional[Dict] = None,
                 ray_actor_options: Optional[Dict] = None,
                 version: Optional[str] = None,
                 route_prefix: Optional[str] = None):
        self._body = body
        self.name = name
        self.route_prefix = route_prefix
        self.config = config
        self.init_args = init_args
        self.init_kwargs = init_kwargs or {}
        self.ray_actor_options = ray_actor_options or {}
        self.version = version

    def options(self, **kwargs) -> "Deployment":
        new = Deployment(self._body, kwargs.pop("name", self.name),
                         DeploymentConfig.from_dict(self.config.to_dict()),
                         self.init_args, dict(self.init_kwargs),
                         dict(self.ray_actor_options), self.version,
                         kwargs.pop("route_prefix", self.route_prefix))
        for k in ("num_replicas", "max_concurrent_queries", "user_config",
                  "graceful_shutdown_timeout_s", "health_check_period_s",
                  "health_check_timeout_s", "drain_timeout_s"):
            if k in kwargs:
                setattr(new.config, k, kwargs.pop(k))
        if "autoscaling_config" in kwargs:
            ac = kwargs.pop("autoscaling_config")
            new.config.autoscaling_config = (
                ac if isinstance(ac, (AutoscalingConfig, type(None)))
                else AutoscalingConfig(**ac))
        if "ray_actor_options" in kwargs:
            new.ray_actor_options = kwargs.pop("ray_actor_options") or {}
        if "init_args" in kwargs:
            new.init_args = tuple(kwargs.pop("init_args"))
        if "init_kwargs" in kwargs:
            new.init_kwargs = dict(kwargs.pop("init_kwargs"))
        if "version" in kwargs:
            new.version = kwargs.pop("version")
        if kwargs:
            raise TypeError(f"unknown deployment options: {list(kwargs)}")
        return new

    def bind(self, *args, **kwargs) -> "Deployment":
        """Deployment-graph style binding of init args."""
        return self.options(init_args=args, init_kwargs=kwargs)

    def _default_version(self) -> str:
        """Content-derived version: re-deploying unchanged code is a
        reconcile no-op instead of a forced rolling restart (matters for
        composed graphs, where deploy() recurses into children)."""
        import hashlib
        try:
            blob = cloudpickle.dumps(
                (self._body, self.init_args, self.init_kwargs,
                 self.config.to_dict(), self.ray_actor_options))
            return hashlib.sha1(blob).hexdigest()[:8]
        except Exception:
            return uuid.uuid4().hex[:8]

    def deploy(self, _blocking: bool = True) -> DeploymentHandle:
        controller = _get_or_create_controller()
        version = self.version or self._default_version()
        # Model composition (reference: serve deployment graphs,
        # _private/deployment_graph_build.py:34): Deployment-typed init
        # args deploy first and arrive as handles, so an ingress class
        # can `await self.child.remote(x)` its children.
        def _resolve(v):
            if isinstance(v, Deployment):
                return v.deploy(_blocking=_blocking)
            return v

        init_args = tuple(_resolve(a) for a in self.init_args)
        init_kwargs = {k: _resolve(v) for k, v in self.init_kwargs.items()}
        rc = ReplicaConfig(
            deployment_def=cloudpickle.dumps(self._body),
            init_args=init_args, init_kwargs=init_kwargs,
            ray_actor_options=self.ray_actor_options)
        ray_tpu.get(controller.deploy.remote(
            self.name, self.config.to_dict(), rc, version,
            self.route_prefix or f"/{self.name}"), timeout=60)
        if _blocking:
            ok = ray_tpu.get(controller.wait_deployments_healthy.remote(
                [self.name]), timeout=180)
            if not ok:
                statuses = ray_tpu.get(
                    controller.get_deployment_statuses.remote(), timeout=30)
                raise RuntimeError(
                    f"deployment {self.name} failed to become healthy: "
                    f"{statuses}")
        return DeploymentHandle(self.name, controller)

    def get_handle(self) -> DeploymentHandle:
        return DeploymentHandle(self.name, _get_or_create_controller())


def deployment(_body=None, *, name: Optional[str] = None,
               num_replicas: int = 1, max_concurrent_queries: int = 100,
               user_config: Any = None,
               autoscaling_config: Optional[Union[Dict,
                                                  AutoscalingConfig]] = None,
               ray_actor_options: Optional[Dict] = None,
               version: Optional[str] = None,
               route_prefix: Optional[str] = None,
               graceful_shutdown_timeout_s: float = 10.0,
               health_check_period_s: float = 5.0):
    """@serve.deployment decorator (reference: serve/api.py deployment)."""

    def _wrap(body):
        cfg = DeploymentConfig(
            num_replicas=num_replicas,
            max_concurrent_queries=max_concurrent_queries,
            user_config=user_config,
            graceful_shutdown_timeout_s=graceful_shutdown_timeout_s,
            health_check_period_s=health_check_period_s)
        if autoscaling_config is not None:
            cfg.autoscaling_config = (
                autoscaling_config
                if isinstance(autoscaling_config, AutoscalingConfig)
                else AutoscalingConfig(**autoscaling_config))
        return Deployment(body, name or body.__name__, cfg,
                          ray_actor_options=ray_actor_options,
                          version=version, route_prefix=route_prefix)

    if _body is not None:
        return _wrap(_body)
    return _wrap


def run(target: Deployment, *, host: str = "127.0.0.1", port: int = 0,
        _start_proxy: bool = True) -> DeploymentHandle:
    """Deploy and (by default) expose over HTTP; returns a handle
    (reference: serve.run api.py:428)."""
    if not isinstance(target, Deployment):
        raise TypeError("serve.run expects a Deployment "
                        "(made with @serve.deployment)")
    controller = start(_start_proxy=_start_proxy,
                       http_options={"host": host, "port": port})
    return target.deploy()


def get_deployment_handle(name: str) -> DeploymentHandle:
    return DeploymentHandle(name, _get_or_create_controller())


def _deployment_from_info(info: Dict) -> Deployment:
    return Deployment(
        cloudpickle.loads(info["deployment_def"]), info["name"],
        DeploymentConfig.from_dict(info["config"]),
        init_args=tuple(info["init_args"]),
        init_kwargs=dict(info["init_kwargs"]),
        ray_actor_options=dict(info["ray_actor_options"]),
        version=info["version"], route_prefix=info["route_prefix"])


def get_deployment(name: str) -> Deployment:
    """Fetch a live deployment by name as a re-deployable Deployment
    object (reference: serve.get_deployment)."""
    controller = _get_or_create_controller()
    infos = ray_tpu.get(controller.get_deployment_info.remote(name),
                        timeout=30)
    if not infos:
        raise KeyError(f"no deployment named {name!r}")
    return _deployment_from_info(infos[0])


def list_deployments() -> Dict[str, Deployment]:
    """All live deployments, by name (reference: serve.list_deployments)."""
    controller = _get_or_create_controller()
    infos = ray_tpu.get(controller.get_deployment_info.remote(),
                        timeout=30)
    return {i["name"]: _deployment_from_info(i) for i in infos}


def build(*import_paths: str) -> Dict:
    """Emit the declarative config for deployments given by import path
    ("module:attr"), the programmatic twin of `rt serve build`
    (reference: serve.build / serve build CLI)."""
    from ray_tpu.serve.schema import build_config
    return build_config(list(import_paths))


async def _run_asgi(app, request) -> Dict:
    """Drive one request through an ASGI app (FastAPI/Starlette/raw
    callable) and capture the response as a structured dict the HTTP
    proxy unwraps (reference: serve.ingress wrapping a FastAPI app in
    the replica; here the adapter is dependency-free ASGI)."""
    from urllib.parse import urlencode

    scope = {
        "type": "http",
        "asgi": {"version": "3.0", "spec_version": "2.3"},
        "http_version": "1.1",
        "method": request.method,
        "scheme": "http",
        "path": request.path,
        "raw_path": request.path.encode(),
        "root_path": "",
        "query_string": urlencode(request.query or {}).encode(),
        "headers": [(k.lower().encode(), v.encode())
                    for k, v in (request.headers or {}).items()],
        "client": ("127.0.0.1", 0),
        "server": ("127.0.0.1", 0),
    }
    body = request.body or b""
    sent = {"done": False}

    async def receive():
        if sent["done"]:
            return {"type": "http.disconnect"}
        sent["done"] = True
        return {"type": "http.request", "body": body, "more_body": False}

    out = {"status": 200, "headers": [], "chunks": []}

    async def send(message):
        if message["type"] == "http.response.start":
            out["status"] = message["status"]
            out["headers"] = message.get("headers", [])
        elif message["type"] == "http.response.body":
            out["chunks"].append(message.get("body", b""))

    await app(scope, receive, send)
    # Keep headers as an ordered (name, value) pair list: collapsing to
    # a dict would drop repeats, and Set-Cookie legitimately repeats.
    headers = [(k.decode("latin-1"), v.decode("latin-1"))
               for k, v in out["headers"]]
    content_type = next((v for k, v in headers
                         if k.lower() == "content-type"), "text/plain")
    return {"__http__": True, "status": out["status"],
            "content_type": content_type,
            "headers": headers, "body": b"".join(out["chunks"])}


def ingress(app):
    """Route ALL HTTP traffic of a deployment through an ASGI app
    (reference: serve.ingress(fastapi_app)).  The decorated class's
    instance is reachable from route handlers via
    serve.get_replica_context().servable_object; direct handle calls
    (`handle.method.remote`) still hit the class's own methods."""

    def decorator(cls):
        if not inspect.isclass(cls):
            raise TypeError("@serve.ingress must decorate a class")

        class _ASGIIngress(cls):
            async def __call__(self, request):  # proxy entry point
                if not isinstance(request, Request):
                    # Plain handle call falls through to the user class.
                    parent = getattr(super(), "__call__", None)
                    if parent is None:
                        raise TypeError(
                            f"{cls.__name__} has no __call__ for "
                            "non-HTTP invocation")
                    result = parent(request)
                    if inspect.iscoroutine(result):
                        result = await result
                    return result
                return await _run_asgi(app, request)

        _ASGIIngress.__name__ = cls.__name__
        _ASGIIngress.__qualname__ = getattr(cls, "__qualname__",
                                            cls.__name__)
        return _ASGIIngress

    return decorator


def get_proxy_address() -> Optional[Dict]:
    return _http_proxy_info


def status() -> List[Dict]:
    controller = _get_or_create_controller()
    return ray_tpu.get(controller.get_deployment_statuses.remote(),
                       timeout=30)


def delete(name: str, _blocking: bool = True):
    controller = _get_or_create_controller()
    ray_tpu.get(controller.delete_deployment.remote(name), timeout=30)
    if _blocking:
        import time
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(s["name"] != name for s in status()):
                return
            time.sleep(0.1)


def shutdown():
    """Tear the Serve instance down (controller + proxy + replicas)."""
    global _http_proxy_info, _http_proxy_addrs
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        _http_proxy_info = None
        return
    try:
        ray_tpu.get(controller.graceful_shutdown.remote(), timeout=60)
    except Exception:
        pass
    proxy_names = ["SERVE_PROXY"]
    try:
        proxy_names += [f"SERVE_PROXY::{n['NodeID'][:8]}"
                        for n in ray_tpu.nodes()]
    except Exception:
        pass
    for name in proxy_names:
        try:
            ray_tpu.kill(ray_tpu.get_actor(name))
        except Exception:
            pass
    try:
        ray_tpu.kill(controller)
    except Exception:
        pass
    _http_proxy_info = None
    _http_proxy_addrs = []
