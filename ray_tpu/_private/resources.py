"""Node resource detection, with TPU chips/topology as first-class resources.

Reference: src/ray/common/task/scheduling_resources.h models CPU/GPU/custom
resources as fixed-point quantities; GPUs are opaque fungible units.  The
TPU-era model here counts a host's chips from their device files and
takes ICI topology (slice name + mesh coordinates) as node labels, so the
placement layer (placement.py) can allocate contiguous sub-meshes — the
scheduling-visible difference between a TPU pod and a bag of GPUs.
"""

from __future__ import annotations

import glob
import os


def detect_node_resources(num_cpus=None, num_tpus=None, resources=None,
                          object_store_memory=None):
    res = dict(resources or {})
    if num_cpus is None:
        num_cpus = float(os.environ.get("RT_NUM_CPUS", os.cpu_count() or 1))
    res["CPU"] = float(num_cpus)
    if num_tpus is None:
        env = os.environ.get("RT_NUM_TPUS")
        num_tpus = float(env) if env is not None else detect_tpu_chips()
    if num_tpus:
        res["TPU"] = float(num_tpus)
    res.setdefault("memory", float(_detect_memory()))
    return res


def detect_tpu_chips() -> int:
    """Count this host's TPU chips from their device files
    (``/dev/accel*``, or one numbered VFIO group per chip).  A chip
    belongs to one process at a time, so the node process must not
    open it to count it: that would take it from the very worker the
    count is advertised for.  Listing ``/dev`` starts no backend,
    imports no jax and does not depend on ``JAX_PLATFORMS``.  (The PCI
    bus is no substitute: a host can list functions it was not handed.)"""
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    try:
        return sum(name.isdigit() for name in os.listdir("/dev/vfio"))
    except OSError:
        return 0


def _detect_memory():
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 8 * 1024**3
