"""Cross-host proof: one cluster spanning two NETWORK NAMESPACES.

Reference: python/ray/autoscaler/_private/fake_multi_node/test_utils.py
(docker-compose fake multi-node harness).  Here `ip netns` + a veth pair
give each node its own network stack and routable IP, so the
bind-vs-advertise path (`rt start --address ... --node-ip ...`) is
exercised across a real network boundary: loopback of one namespace is
unreachable from the other, so any 127.0.0.1 address leaking into
advertised state breaks these tests immediately."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

HEAD_NS = "rt_head_ns"
WORKER_NS = "rt_worker_ns"
HEAD_IP = "10.200.77.1"
WORKER_IP = "10.200.77.2"


def _run(argv, timeout=60, check=True, **kw):
    proc = subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, **kw)
    if check and proc.returncode != 0:
        raise RuntimeError(f"{argv} failed:\n{proc.stdout}\n{proc.stderr}")
    return proc


def _netns_available() -> bool:
    if os.geteuid() != 0:
        return False
    try:
        _run(["ip", "netns", "add", "rt_probe_ns"])
        _run(["ip", "netns", "del", "rt_probe_ns"])
        return True
    except Exception:
        return False


pytestmark = pytest.mark.skipif(not _netns_available(),
                                reason="needs root + ip netns")


@pytest.fixture(scope="module")
def netns_pair():
    """Two namespaces joined by a veth pair; loopback up in both."""
    for ns in (HEAD_NS, WORKER_NS):
        _run(["ip", "netns", "del", ns], check=False)
    _run(["ip", "netns", "add", HEAD_NS])
    _run(["ip", "netns", "add", WORKER_NS])
    _run(["ip", "link", "add", "rtveth0", "type", "veth",
          "peer", "name", "rtveth1"])
    _run(["ip", "link", "set", "rtveth0", "netns", HEAD_NS])
    _run(["ip", "link", "set", "rtveth1", "netns", WORKER_NS])
    for ns, dev, ip in ((HEAD_NS, "rtveth0", HEAD_IP),
                        (WORKER_NS, "rtveth1", WORKER_IP)):
        _run(["ip", "netns", "exec", ns, "ip", "addr", "add",
              f"{ip}/24", "dev", dev])
        _run(["ip", "netns", "exec", ns, "ip", "link", "set", dev,
              "up"])
        _run(["ip", "netns", "exec", ns, "ip", "link", "set", "lo",
              "up"])
    # Sanity: worker can reach head over the veth (no ping binary in
    # the image — a TCP connect probe is equivalent: ECONNREFUSED means
    # the packet ROUTED and the peer answered with RST).
    probe = _run(_in_ns(WORKER_NS, [sys.executable, "-S", "-c",
                 "import socket,sys\n"
                 "s = socket.socket()\n"
                 "s.settimeout(2)\n"
                 f"rc = s.connect_ex(('{HEAD_IP}', 1))\n"
                 "print('REACH' if rc in (111, 0) else rc)"]),
                 check=False)
    if "REACH" not in probe.stdout:
        pytest.skip(f"veth routing unavailable: {probe.stdout} "
                    f"{probe.stderr}")
    yield
    for ns in (HEAD_NS, WORKER_NS):
        _run(["ip", "netns", "del", ns], check=False)


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _in_ns(ns, argv):
    return ["ip", "netns", "exec", ns] + argv


@pytest.fixture(scope="module")
def cross_host_cluster(netns_pair):
    """Head in one namespace, worker joining via rt start --address
    with a routable --node-ip in the other."""
    state_file = "/tmp/ray_tpu/started_nodes.json"
    if os.path.exists(state_file):
        os.rename(state_file, state_file + ".bak")
    procs_to_sweep = []
    try:
        up = _run(_in_ns(HEAD_NS, [
            sys.executable, "-m", "ray_tpu.scripts.cli", "start",
            "--head", "--node-ip", HEAD_IP, "--num-cpus", "2"]),
            timeout=180, env=_env(), cwd="/root/repo")
        gcs_line = [ln for ln in up.stdout.splitlines()
                    if "GCS address" in ln][0]
        gcs = gcs_line.split()[-1]
        assert gcs.startswith(HEAD_IP), f"head advertised {gcs}"

        _run(_in_ns(WORKER_NS, [
            sys.executable, "-m", "ray_tpu.scripts.cli", "start",
            "--address", gcs, "--node-ip", WORKER_IP, "--num-cpus", "2",
            "--resources", json.dumps({"side": 2})]),
            timeout=180, env=_env(), cwd="/root/repo")

        with open(state_file) as f:
            entries = json.load(f)
        procs_to_sweep = [pid for e in entries
                          for pid in e["pids"].values()]
        worker_raylet_pid = [
            e["pids"]["raylet"] for e in entries
            if e["raylet_address"].startswith(WORKER_IP)][0]
        yield {"gcs": gcs, "worker_raylet_pid": worker_raylet_pid}
    finally:
        # Re-read the state file: a failure between head and worker
        # start leaves pids recorded there that procs_to_sweep missed.
        try:
            with open(state_file) as f:
                for e in json.load(f):
                    procs_to_sweep += list(e.get("pids", {}).values())
        except (OSError, ValueError):
            pass
        for pid in set(procs_to_sweep):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        if os.path.exists(state_file):
            os.unlink(state_file)
        if os.path.exists(state_file + ".bak"):
            os.rename(state_file + ".bak", state_file)


def _driver(ns, script, timeout=300):
    return _run(_in_ns(ns, [sys.executable, "-c", script]),
                timeout=timeout, env=_env(), cwd="/root/repo")


@pytest.mark.slow
def test_cross_namespace_tasks_and_objects(cross_host_cluster):
    gcs = cross_host_cluster["gcs"]
    out = _driver(HEAD_NS, f"""
import numpy as np
import ray_tpu
ray_tpu.init(address="{gcs}")

@ray_tpu.remote
def where():
    return ray_tpu.get_runtime_context().get_node_id()

local = ray_tpu.get(where.remote(), timeout=180)
remote = ray_tpu.get(where.options(resources={{"side": 0.1}}).remote(),
                     timeout=180)
assert local != remote, "task did not cross the namespace boundary"

@ray_tpu.remote(resources={{"side": 0.1}})
def make():
    import numpy as np
    return np.arange(500_000, dtype=np.int64)

arr = ray_tpu.get(make.remote(), timeout=180)
assert arr.sum() == 124999750000, arr.sum()
print("CROSS_OK nodes=%d" % sum(1 for n in ray_tpu.nodes() if n["Alive"]))
ray_tpu.shutdown()
""")
    assert "CROSS_OK nodes=2" in out.stdout


def test_cross_namespace_train_e2e(cross_host_cluster):
    """Train gang spanning both namespaces: one rank per node."""
    gcs = cross_host_cluster["gcs"]
    out = _driver(HEAD_NS, f"""
import ray_tpu
from ray_tpu.air import session
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train import DataParallelTrainer, JaxConfig

ray_tpu.init(address="{gcs}")

def loop(config):
    import socket
    from ray_tpu.air import session
    for step in range(3):
        session.report({{"step": step,
                        "host": session.get_world_rank()}})

trainer = DataParallelTrainer(
    loop,
    backend_config=JaxConfig(use_distributed=False),
    scaling_config=ScalingConfig(num_workers=2,
                                 resources_per_worker={{"CPU": 1}}))
result = trainer.fit()
assert result.metrics["step"] == 2
print("TRAIN_OK")
ray_tpu.shutdown()
""", timeout=420)
    assert "TRAIN_OK" in out.stdout


@pytest.mark.slow
def test_cross_namespace_sigkill_worker_node(cross_host_cluster):
    """SIGKILL the other namespace's raylet mid-run: the head detects
    the remote node's death across the network boundary, the dead
    node's exclusive resource becomes infeasible (its actor dies with a
    meaningful error), and the surviving node keeps serving."""
    gcs = cross_host_cluster["gcs"]
    pid = cross_host_cluster["worker_raylet_pid"]
    out = _driver(HEAD_NS, f"""
import os
import signal
import time
import ray_tpu
from ray_tpu.exceptions import ActorDiedError, RayTpuError
ray_tpu.init(address="{gcs}")

@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def bump(self):
        self.n += 1
        return self.n

# Pinned to the worker namespace's node by its exclusive resource.
a = Counter.options(max_restarts=1, max_task_retries=2, num_cpus=0,
                    resources={{"side": 0.1}}).remote()
assert ray_tpu.get(a.bump.remote(), timeout=180) == 1

os.kill({pid}, signal.SIGKILL)  # the worker namespace's raylet
time.sleep(2)
assert not os.path.exists("/proc/{pid}")

# 1. Node death is detected across the namespace boundary.
deadline = time.time() + 120
while time.time() < deadline:
    if sum(1 for x in ray_tpu.nodes() if x["Alive"]) == 1:
        break
    time.sleep(1)
assert sum(1 for x in ray_tpu.nodes() if x["Alive"]) == 1

# 2. The actor's resource died with its node: the restart is
# infeasible and surfaces as ActorDiedError, not a hang.
try:
    ray_tpu.get(a.bump.remote(), timeout=240)
    raise AssertionError("expected ActorDiedError")
except (ActorDiedError, RayTpuError):
    pass

# 3. The surviving node keeps serving generic work.
@ray_tpu.remote
def alive():
    return "ok"

assert ray_tpu.get(alive.remote(), timeout=180) == "ok"
# The lost node's resource is gone from the cluster view.
assert "side" not in ray_tpu.cluster_resources()
print("CHAOS_OK")
ray_tpu.shutdown()
""", timeout=540)
    assert "CHAOS_OK" in out.stdout
