"""chip_smoke.py and the bring-up rules it rests on, rehearsed on the CPU:
the contract's last line, the phases at toy size, failure without a
chip, a driver that stays off jax, chip detection, the TPU worker's
environment and lifetime, and where the compile cache goes."""

import json
import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import jax_utils, resources
from ray_tpu._private.ids import WorkerID
from ray_tpu._private.raylet import Raylet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TOY = dict(
    chip_smoke.SIZES,
    model=dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
               max_seq=128, dtype="float32"),
    engine=dict(num_slots=4, max_seq=64, page_size=8, kv_pages=32),
    prompt_lens=(5, 9, 12, 17), max_new_tokens=8, logit_margin=1e-3,
    train=dict(batch=8, seq=32, steps=5), mesh_steps=2, loss_tolerance=1e-3)


# ------------------------------------------------------------ the last line

def test_last_line_has_exactly_the_contract_keys():
    line = chip_smoke.last_line("tpu", "TPU v5 lite", 1)
    assert "\n" not in line
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"} and obj["ok"] is True
    assert obj["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}


# ------------------------------------------- the phases, toy size, on the CPU

@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=6, resources={"TPU": 2})
    yield
    ray_tpu.shutdown()


def test_serve_phase_on_cpu(cluster):
    r = chip_smoke.serve_phase(TOY, "cpu")
    assert r["device"]["platform"] == "cpu" and r["requests_completed"] == 6
    check = r["token_check"]
    assert check["worst_logit_gap"] <= check["margin"]
    assert check["argmax_matches"] == check["generated"] == 8
    assert check["reference_pid"] != r["device"]["pid"]


def test_train_phase_on_cpu(cluster):
    r = chip_smoke.train_phase(TOY, "cpu")
    assert r["losses"][-1] < r["losses"][0] and len(r["losses"]) == 5
    assert r["device"]["platform"] == "cpu"


def test_four_chip_phases_on_virtual_devices(cluster):
    """conftest gives every process 8 virtual CPU devices: fsdp 2 x tp 2,
    and dp takes the rest."""
    r = chip_smoke.mesh_train_phase(TOY, "cpu")
    assert r["mesh"]["fsdp"] == 2 and r["mesh"]["tp"] == 2
    assert r["max_loss_diff"] <= r["tolerance"]
    r = chip_smoke.replicas_phase(TOY, "cpu")
    assert len({i["pid"] for i in r["per_replica"]}) == 4
    assert all(i["completed"] for i in r["per_replica"])


def test_phase_demands_its_platform(cluster):
    """A worker that is not on the platform asked for fails the phase:
    here a CPU task is held to "tpu"."""
    with pytest.raises(Exception, match="computes on 'cpu'"):
        ray_tpu.get(ray_tpu.remote(chip_smoke.reference_scores).remote(
            TOY["model"], 0, [1, 2, 3], [4], "tpu"), timeout=120)


def test_tpu_worker_dies_with_its_lease(cluster):
    """A worker that was leased chips may hold them for as long as it
    lives, so it is never pooled: the next TPU lease gets a new process,
    and the chips are free again only once the old one is gone."""
    @ray_tpu.remote(num_tpus=1)
    def who():
        return os.getpid(), os.environ["JAX_PLATFORMS"], ray_tpu.get_tpu_ids()

    pid1, platform, ids = ray_tpu.get(who.remote(), timeout=60)
    assert platform == "tpu" and len(ids) == 1
    chip_smoke._wait_gone([pid1], timeout_s=30)  # not pooled: retired
    pid2, _, _ = ray_tpu.get(who.remote(), timeout=60)
    assert pid2 != pid1


# --------------------------------------------- the script, without a chip

@pytest.mark.parametrize("env, why", [
    ({}, "TPU chip(s) detected"),                  # nothing to lease
    ({"RT_NUM_TPUS": "1"}, "did not start"),       # a chip it cannot open
])
def test_script_fails_without_a_chip(env, why):
    """Under JAX_PLATFORMS=cpu, and even when the node claims a chip:
    non-zero exit in bounded time, and stdout alone never ends in the
    ok line.  With the claimed chip, the replica's TPU worker raises
    (JAX_PLATFORMS=tpu) where it used to serve from the CPU."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert why in p.stderr
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[0])["phase"] == "detect"
    assert '"ok"' not in lines[-1]


def test_driver_stays_off_jax():
    """init() with a chip to advertise neither counts chips through jax
    nor starts a backend in the driver."""
    code = (
        "import sys, ray_tpu, chip_smoke\n"
        "from ray_tpu._private.resources import detect_node_resources\n"
        "detect_node_resources()\n"
        "assert 'jax' not in sys.modules\n"
        "ray_tpu.init(num_cpus=1, num_tpus=1)\n"
        "assert ray_tpu.cluster_resources()['TPU'] == 1\n"
        "import jax\n"
        "assert not chip_smoke.driver_backend_initialized()\n"
        "ray_tpu.shutdown()\n"
        "jax.devices()\n"
        "assert chip_smoke.driver_backend_initialized()\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)


# -------------------------------------------------------------- detection

@pytest.mark.parametrize("accel, vfio, chips", [
    (["/dev/accel0", "/dev/accel1"], None, 2),
    ([], ["0", "1", "2", "3", "vfio"], 4),
    ([], ["3", "vfio"], 1),        # a one-chip slice of a four-chip host
    ([], ["vfio"], 0),
    ([], None, 0),                 # no /dev/vfio at all
])
def test_chips_are_counted_from_device_files(monkeypatch, accel, vfio, chips):
    def listdir(path):
        if vfio is None:
            raise FileNotFoundError(path)
        return vfio

    monkeypatch.setattr(resources.glob, "glob", lambda pattern: accel)
    monkeypatch.setattr(resources.os, "listdir", listdir)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # says nothing about chips
    assert resources.detect_tpu_chips() == chips
    expect = {"TPU": float(chips)} if chips else {}
    got = resources.detect_node_resources(num_cpus=1)
    assert {k: v for k, v in got.items() if k == "TPU"} == expect


# ------------------------------------------------- the worker's environment

@pytest.mark.parametrize("kind, platform", [("tpu", "tpu"), ("cpu", "cpu")])
def test_worker_platform_is_set_by_kind(monkeypatch, kind, platform):
    """Whatever the node's own environment says."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    raylet = Raylet.__new__(Raylet)
    raylet._subproc_env = None
    raylet.host, raylet.port, raylet.gcs_addr = "127.0.0.1", 1, ("h", 2)
    raylet.node_id, raylet.store_path = WorkerID.from_random(), "/s"
    raylet.store_capacity, raylet.session_dir = 1, "/d"
    env = raylet._worker_env_for(WorkerID.from_random(), kind)
    assert env["JAX_PLATFORMS"] == platform


@pytest.fixture
def unbound(monkeypatch):
    monkeypatch.setitem(jax_utils._BOUND, "ids", None)
    for name in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                 "TPU_PROCESS_BOUNDS"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(jax_utils, "detect_tpu_chips", lambda: 4)


def test_one_chip_lease_narrows_the_process(unbound):
    jax_utils.bind_tpu_chips([2])
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    jax_utils.bind_tpu_chips([2])               # the same lease again
    with pytest.raises(RuntimeError, match="cannot be re-bound"):
        jax_utils.bind_tpu_chips([3])


def test_whole_host_lease_needs_no_narrowing(unbound):
    jax_utils.bind_tpu_chips([3, 1, 0, 2])
    assert "TPU_VISIBLE_CHIPS" not in os.environ


def test_part_of_a_host_is_refused(unbound):
    with pytest.raises(RuntimeError, match="one chip or every chip"):
        jax_utils.bind_tpu_chips([0, 1])


# ---------------------------------------------------------- the compile cache

@pytest.fixture
def config_updates(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_from_the_environment_sets_nothing(monkeypatch,
                                                     config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert jax_utils.enable_compile_cache() == "/somewhere/else"
    assert config_updates == []


def test_cache_dir_default_is_one_fixed_path(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert jax_utils.enable_compile_cache() == fixed
    assert jax_utils.enable_compile_cache() == fixed
    assert config_updates == [("jax_compilation_cache_dir", fixed)] * 2
