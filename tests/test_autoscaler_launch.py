"""Cluster launcher + TPU-pod provider (reference:
autoscaler/ray-schema.json validation, _private/updater.py bootstrap,
and a queued-resources slice provider per SURVEY §7 phase 9)."""

import json
import os
import subprocess
import sys
import time

import pytest
import yaml

from ray_tpu.autoscaler import (ClusterConfigError, MockQueuedResourceAPI,
                                StandardAutoscaler, TPUPodProvider,
                                validate_cluster_config)


def test_cluster_config_validation():
    ok = validate_cluster_config({
        "provider": {"type": "local_process"},
        "available_node_types": {
            "w": {"resources": {"CPU": 1}, "min_workers": 1}},
    })
    assert ok["available_node_types"]["w"]["group_size"] == 1
    assert ok["max_workers"] == 8
    with pytest.raises(ClusterConfigError):
        validate_cluster_config({"available_node_types": {
            "w": {"resources": {"CPU": 1}}}})  # no provider
    with pytest.raises(ClusterConfigError):
        validate_cluster_config({
            "provider": {"type": "nope"},
            "available_node_types": {"w": {"resources": {"CPU": 1}}}})
    with pytest.raises(ClusterConfigError):
        validate_cluster_config({
            "provider": {"type": "fake"},
            "available_node_types": {"w": {"bogus": 1}}})
    with pytest.raises(ClusterConfigError):
        validate_cluster_config({
            "provider": {"type": "fake"}, "bogus_top": 1,
            "available_node_types": {"w": {"resources": {"CPU": 1}}}})


def test_tpu_pod_provider_queued_lifecycle():
    """Slices arrive through queued resources: PENDING contributes no
    capacity, ACTIVE contributes all hosts at once, terminate releases
    the whole slice atomically."""
    api = MockQueuedResourceAPI(grant_after=2)
    provider = TPUPodProvider(
        {"v5e-16": {"resources": {"TPU": 4}, "group_size": 4,
                    "node_config": {"accelerator_type": "v5litepod-16"}}},
        project="p", zone="z", api=api)
    created = provider.create_nodes("v5e-16", 1)
    assert len(created) == 1
    # Still queued: no capacity yet.
    assert provider.non_terminated_nodes() == []
    # Second poll grants it: all 4 hosts appear together.
    nodes = provider.non_terminated_nodes()
    assert len(nodes) == 4
    assert len({n["group_id"] for n in nodes}) == 1
    assert all(n["node_type"] == "v5e-16" for n in nodes)
    # Terminating ANY host deletes the whole queued resource.
    provider.terminate_node(nodes[2]["provider_id"])
    assert provider.non_terminated_nodes() == []
    assert api.list_queued_resources() == []


def test_tpu_pod_provider_bootstraps_granted_hosts():
    api = MockQueuedResourceAPI(grant_after=1)
    ran = []

    class Recorder:
        def __init__(self, ip):
            self.ip = ip

        def run(self, cmd, timeout=600.0):
            ran.append((self.ip, cmd))
            return ""

    provider = TPUPodProvider(
        {"pod": {"resources": {"TPU": 4}, "group_size": 2}},
        project="p", zone="z", api=api, gcs_addr=("10.9.9.9", 6379),
        bootstrap_runner_factory=Recorder)
    provider.create_nodes("pod", 1)
    nodes = provider.non_terminated_nodes()
    assert len(nodes) == 2
    assert len(ran) == 2  # one bootstrap per host
    assert all("rt start --address 10.9.9.9:6379" in cmd
               for _, cmd in ran)
    assert {ip for ip, _ in ran} == {n["host_ip"] for n in nodes}
    # Re-listing does NOT re-bootstrap.
    provider.non_terminated_nodes()
    assert len(ran) == 2


def test_tpu_pod_provider_failed_grant_reaped():
    api = MockQueuedResourceAPI(grant_after=1, capacity_slices=1)
    provider = TPUPodProvider(
        {"pod": {"resources": {"TPU": 4}, "group_size": 1}},
        project="p", zone="z", api=api)
    provider.create_nodes("pod", 1)
    provider.create_nodes("pod", 1)  # over capacity -> FAILED
    nodes = provider.non_terminated_nodes()
    assert len(nodes) == 1  # the failed request was reaped
    assert len(provider._slices) == 1


def test_autoscaler_launches_tpu_slices_on_demand():
    """The standard autoscaler + TPUPodProvider: an infeasible TPU
    demand launches a whole slice (atomic group) once granted."""
    api = MockQueuedResourceAPI(grant_after=1)
    provider = TPUPodProvider(
        {"v5e": {"resources": {"TPU": 4, "CPU": 1}, "group_size": 2,
                 "max_workers": 2}},
        project="p", zone="z", api=api)
    demands = [{"TPU": 4}]

    def gcs_request(method, body):
        if method == "get_resource_demands":
            return {"shapes": demands, "pending_pgs": []}
        if method == "get_nodes":
            return []
        raise AssertionError(method)

    autoscaler = StandardAutoscaler(provider, gcs_request,
                                    idle_timeout_s=9999)
    r = autoscaler.update()
    assert len(r["launched"]) == 1
    assert len(provider.non_terminated_nodes()) == 2  # both slice hosts


@pytest.mark.slow
def test_rt_up_down_process_provider(tmp_path):
    """rt up cluster.yaml -> head + min_workers as REAL processes with
    a monitor scaling the cluster; rt down tears it all down."""
    config = {
        "cluster_name": f"t{os.getpid()}",
        "provider": {"type": "local_process"},
        "head_node": {"resources": {"CPU": 1}},
        "available_node_types": {
            "worker": {"resources": {"CPU": 1, "spot": 1},
                       "min_workers": 1, "max_workers": 2}},
        "idle_timeout_minutes": 60,
    }
    cfg_path = tmp_path / "cluster.yaml"
    cfg_path.write_text(yaml.safe_dump(config))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    up = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "up",
         str(cfg_path)], capture_output=True, text=True, timeout=300,
        env=env, cwd="/root/repo")
    assert up.returncode == 0, up.stdout + up.stderr
    gcs = [ln for ln in up.stdout.splitlines() if "GCS address" in ln]
    address = gcs[0].split()[-1]
    state_path = f"/tmp/ray_tpu/cluster_{config['cluster_name']}.json"
    assert os.path.exists(state_path)

    try:
        # A driver sees head + the min_worker (2 alive nodes) and can
        # run on the worker's custom resource.
        probe = subprocess.run(
            [sys.executable, "-c", f"""
import time
import ray_tpu
ray_tpu.init(address="{address}")

@ray_tpu.remote(resources={{"spot": 0.1}})
def where():
    return ray_tpu.get_runtime_context().get_node_id()

print("NODE=" + ray_tpu.get(where.remote(), timeout=240))
print("ALIVE=%d" % sum(1 for n in ray_tpu.nodes() if n["Alive"]))
ray_tpu.shutdown()
"""], capture_output=True, text=True, timeout=300, env=env,
            cwd="/root/repo")
        assert probe.returncode == 0, probe.stdout + probe.stderr
        assert "NODE=" in probe.stdout
        alive = int([ln for ln in probe.stdout.splitlines()
                     if ln.startswith("ALIVE=")][0].split("=")[1])
        assert alive >= 2, probe.stdout
        with open(state_path) as f:
            state = json.load(f)
        assert state["worker_pids"], "monitor never persisted workers"
    finally:
        down = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts.cli", "down",
             str(cfg_path)], capture_output=True, text=True,
            timeout=120, env=env, cwd="/root/repo")
    assert down.returncode == 0, down.stdout + down.stderr
    assert not os.path.exists(state_path)
    # Every recorded process is really gone.
    deadline = time.time() + 20
    pids = (list(state.get("worker_pids", []))
            + list(state.get("head_pids", {}).values())
            + [state.get("monitor_pid")])
    while time.time() < deadline:
        left = [p for p in pids if p and os.path.exists(f"/proc/{p}")]
        if not left:
            break
        time.sleep(0.5)
    assert not left, f"processes survived rt down: {left}"
