"""Node: spawns and supervises the cluster processes on one machine.

Reference: python/ray/_private/node.py:1084 start_ray_processes /
:896 start_gcs_server / :928 start_raylet, with command assembly in
_private/services.py:1381,1440.  Head nodes run the GCS; every node runs a
raylet (which embeds the shared-memory store).  In-process variants
(`start_in_process`) run GCS + raylet coroutines inside the driver's event
loop — that is what the multi-node-in-one-process test Cluster uses
(reference analogue: python/ray/cluster_utils.py Cluster.add_node spawning
real raylets locally).
"""

from __future__ import annotations

import asyncio
import atexit
import os
import re
import subprocess
import sys
import tempfile
import time
import uuid

from ray_tpu._private.config import GLOBAL_CONFIG as cfg
from ray_tpu._private.resources import detect_node_resources


def new_session_dir():
    base = os.path.join(tempfile.gettempdir(), "ray_tpu")
    session = os.path.join(base,
                           f"session_{time.strftime('%Y%m%d-%H%M%S')}"
                           f"_{uuid.uuid4().hex[:8]}")
    os.makedirs(os.path.join(session, "logs"), exist_ok=True)
    return session


def _read_tag(proc, tag, timeout=30.0, convert=int):
    pattern = re.compile(rf"{tag}=(\S+)")
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(f"{tag} process exited "
                                   f"with {proc.returncode}")
            time.sleep(0.01)
            continue
        m = pattern.search(line.decode(errors="replace"))
        if m:
            return convert(m.group(1))
    raise RuntimeError(f"timed out waiting for {tag}")


def _read_port(proc, tag, timeout=30.0):
    return _read_tag(proc, tag, timeout, convert=int)


class NodeProcesses:
    """Out-of-process GCS + raylet — a REAL node, reachable across hosts.

    Reference: python/ray/_private/node.py:1084 start_ray_processes with
    command assembly services.py:1381 (gcs_server) / :1440 (raylet).  The
    head node spawns the GCS process; every node spawns a raylet process
    (which owns the node's shm store and worker pool).  ``host`` is the
    bind + advertise address — pass the machine's routable IP for
    multi-host clusters (the default loopback only works single-machine).
    ``rt start --head`` / ``rt start --address`` (scripts/cli.py) and the
    out-of-process test ``ProcessCluster`` both build on this."""

    def __init__(self, session_dir=None, num_cpus=None, num_tpus=None,
                 resources=None, object_store_memory=None, head=True,
                 gcs_addr=None, host="127.0.0.1", gcs_port=0, labels=None,
                 node_name=None, register_atexit=True):
        self.session_dir = session_dir or new_session_dir()
        self.gcs_proc: subprocess.Popen | None = None
        self.raylet_proc: subprocess.Popen | None = None
        self.gcs_addr = tuple(gcs_addr) if gcs_addr else None
        self.raylet_addr = None
        self.head = head
        self.host = host
        self.gcs_port = gcs_port
        self.node_name = node_name
        self._register_atexit = register_atexit
        self._resources = detect_node_resources(
            num_cpus=num_cpus, num_tpus=num_tpus, resources=resources)
        self._labels = dict(labels or {})
        self._object_store_memory = (object_store_memory
                                     or cfg.object_store_memory_bytes)

    def _logfile(self, tag):
        path = os.path.join(self.session_dir, "logs", f"{tag}.err")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        return open(path, "ab")

    def start(self):
        env = dict(os.environ)
        env.update(cfg.to_env())
        if self.head:
            self.gcs_proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.gcs",
                 "--host", self.host,
                 "--port", str(self.gcs_port),
                 "--persist-path",
                 os.path.join(self.session_dir, "gcs_snapshot.pkl")],
                stdout=subprocess.PIPE, stderr=self._logfile("gcs"),
                env=env, start_new_session=True)
            port = _read_port(self.gcs_proc, "GCS_PORT")
            self.gcs_addr = (self.host, port)
        self.start_raylet()
        if self._register_atexit:
            atexit.register(self.kill)
        return self

    def start_raylet(self):
        """(Re)spawn this node's raylet (also used after a SIGKILL in
        chaos flows to simulate a machine coming back)."""
        import json
        env = dict(os.environ)
        env.update(cfg.to_env())
        self.raylet_proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.raylet",
             "--host", self.host,
             "--gcs-host", self.gcs_addr[0],
             "--gcs-port", str(self.gcs_addr[1]),
             "--resources", json.dumps(self._resources),
             "--labels", json.dumps(self._labels),
             "--session-dir", self.session_dir,
             "--store-capacity", str(self._object_store_memory)]
            + (["--node-name", self.node_name] if self.node_name else []),
            stdout=subprocess.PIPE, stderr=self._logfile("raylet"),
            env=env, start_new_session=True)
        rport = _read_port(self.raylet_proc, "RAYLET_PORT")
        self.raylet_addr = (self.host, rport)
        self.raylet_node_id = _read_tag(self.raylet_proc, "RAYLET_NODE_ID",
                                        convert=str)
        return self.raylet_addr

    def restart_gcs(self):
        """Respawn the GCS on its previous port, reloading the snapshot
        (reference: GCS failover with Redis persistence)."""
        if not self.head or self.gcs_addr is None:
            raise RuntimeError("not a head node")
        env = dict(os.environ)
        env.update(cfg.to_env())
        self.gcs_proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.gcs",
             "--host", self.host,
             "--port", str(self.gcs_addr[1]),
             "--persist-path",
             os.path.join(self.session_dir, "gcs_snapshot.pkl")],
            stdout=subprocess.PIPE, stderr=self._logfile("gcs"),
            env=env, start_new_session=True)
        _read_port(self.gcs_proc, "GCS_PORT")

    @property
    def procs(self):
        return [p for p in (self.gcs_proc, self.raylet_proc)
                if p is not None]

    def pids(self):
        return {("gcs" if p is self.gcs_proc else "raylet"): p.pid
                for p in self.procs}

    def kill_raylet(self, sig=None):
        """SIGKILL (default) the raylet process — real fault injection;
        its workers die with it (they exit when the raylet socket
        closes)."""
        import signal as _signal
        p = self.raylet_proc
        if p is not None and p.poll() is None:
            try:
                os.kill(p.pid, sig or _signal.SIGKILL)
                p.wait(10)
            except Exception:
                pass

    def kill_gcs(self, sig=None):
        import signal as _signal
        p = self.gcs_proc
        if p is not None and p.poll() is None:
            try:
                os.kill(p.pid, sig or _signal.SIGKILL)
                p.wait(10)
            except Exception:
                pass

    def kill(self):
        self.kill_raylet()
        self.kill_gcs()
        self.gcs_proc = None
        self.raylet_proc = None


class InProcessNode:
    """GCS and/or raylet running as coroutines inside the current process's
    background event loop — used by the test Cluster fixture and by
    ray_tpu.init() for fast single-machine bring-up."""

    def __init__(self, loop, head=True, gcs_addr=None, num_cpus=None,
                 num_tpus=None, resources=None, labels=None,
                 object_store_memory=None, session_dir=None, node_name=None):
        self.loop = loop
        self.head = head
        self.gcs_addr = gcs_addr
        self.session_dir = session_dir or new_session_dir()
        self.gcs_server = None
        self.raylet = None
        self.raylet_addr = None
        self._resources = detect_node_resources(
            num_cpus=num_cpus, num_tpus=num_tpus, resources=resources)
        self._labels = dict(labels or {})
        self._object_store_memory = (object_store_memory
                                     or cfg.object_store_memory_bytes)
        self.node_name = node_name

    def start(self):
        fut = asyncio.run_coroutine_threadsafe(self._start_async(), self.loop)
        fut.result(60)
        return self

    async def _start_async(self):
        if self.head:
            from ray_tpu._private.gcs import GcsServer
            self.gcs_server = GcsServer(persist_path=os.path.join(
                self.session_dir, "gcs_snapshot.pkl"))
            port = await self.gcs_server.start(0)
            self.gcs_addr = ("127.0.0.1", port)
        from ray_tpu._private.raylet import Raylet
        self.raylet = Raylet(self.gcs_addr, self._resources,
                             labels=self._labels,
                             session_dir=self.session_dir,
                             store_capacity=self._object_store_memory,
                             node_name=self.node_name)
        rport = await self.raylet.start(0)
        self.raylet_addr = ("127.0.0.1", rport)
        n_warm = min(2, max(1, int(self._resources.get("CPU", 1))))
        self.raylet.prestart_workers(n_warm)

    @property
    def node_id(self):
        return self.raylet.node_id if self.raylet else None

    def kill(self, stop_gcs=True):
        async def _kill():
            if self.raylet is not None:
                await self.raylet.shutdown()
            if stop_gcs and self.gcs_server is not None:
                await self.gcs_server.stop()
        try:
            asyncio.run_coroutine_threadsafe(_kill(), self.loop).result(10)
        except Exception:
            pass
