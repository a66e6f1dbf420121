"""Test fixtures: in-process multi-node clusters, CPU-pinned jax.

Reference test strategy (SURVEY.md §4): real multi-raylet clusters inside
one process (reference: python/ray/tests/conftest.py:235 ray_start_regular,
:316 ray_start_cluster over cluster_utils.Cluster.add_node).
"""

import os

# Pin jax to an 8-device virtual CPU host platform BEFORE anything
# initializes a backend: tests never compute on (or take) a real chip.
os.environ["RT_NUM_CPUS"] = os.environ.get("RT_NUM_CPUS", "4")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

try:
    from ray_tpu._private.jax_utils import ensure_cpu
    ensure_cpu(8)
except Exception:
    pass

import pytest  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.cluster_utils import Cluster  # noqa: E402


@pytest.fixture(autouse=True)
def _locksan_no_new_violations():
    """When the runtime lock-order sanitizer is on (RT_LOCK_SANITIZER=1,
    e.g. `make chaos`), any test whose execution records a NEW
    lock-order violation fails with the witness message — the dynamic
    complement of the static RTC102 cycle detector."""
    from ray_tpu._private import locksan
    if not locksan.enabled():
        yield
        return
    before = len(locksan.violations())
    yield
    new = locksan.violations()[before:]
    assert not new, (
        "lock-order violation(s) recorded during this test:\n"
        + "\n".join(v["message"] for v in new))


@pytest.fixture(autouse=True)
def _serve_replica_context_does_not_leak():
    """A test that builds a Serve replica in its own process (the unit
    tests of serve/_private/replica.py) publishes a replica context
    there; the next test of the same worker finds the one this test
    started with, whichever files xdist hands that worker."""
    import sys
    name = "ray_tpu.serve.context"
    before = getattr(sys.modules.get(name), "_INTERNAL_REPLICA_CONTEXT",
                     None)
    yield
    if name in sys.modules:
        sys.modules[name]._INTERNAL_REPLICA_CONTEXT = before


@pytest.fixture
def ray_start_regular():
    """A fresh single-node cluster + connected driver."""
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-raylet in-process cluster factory (reference:
    conftest.py:316 _ray_start_cluster)."""
    cluster = Cluster()
    yield cluster
    cluster.shutdown()


@pytest.fixture(params=["span", "kernel"])
def tick_attention(request, monkeypatch):
    """How a tick's paged attention layers of `models/exaone_moe.py`
    and `models/deepseek_v2.py` attend: the span loop, what runs where
    there is no TPU, or the ragged kernel of `ops/paged_attention.py` as
    a TPU runs it, interpreted, in blocks small enough that a toy row
    walks several.  The engine's jitted tick is traced anew on both
    sides of the kernel's turn."""
    if request.param == "span":
        yield request.param
        return
    import functools

    from ray_tpu.models import deepseek_v2, exaone_moe
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.llm import engine
    kernel, gmm, traced = pa.paged_attention, deepseek_v2.gmm, []

    @functools.wraps(kernel)
    def interpreted(q, *args, **kw):
        traced.append(q.shape)
        return kernel(q, *args, interpret=True, **kw)
    monkeypatch.setattr(exaone_moe, "_on_tpu", lambda: True)
    # (deepseek_v2 asks the same of its grouped matmul, which has no TPU
    # to run on here either)
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    monkeypatch.setattr(deepseek_v2, "gmm", lambda *a, **kw: gmm(
        *a, **dict(kw, interpret=True)))
    monkeypatch.setattr(pa, "paged_attention", interpreted)
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 8)
    monkeypatch.setattr(pa, "_BLOCK_BYTES", 0)
    engine._paged_tick.clear_cache()
    yield request.param
    engine._paged_tick.clear_cache()
    assert traced, "the tick never reached the kernel"
