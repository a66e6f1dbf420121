"""Which JAX platform a process uses, which chips it may open, where its
compiled programs are kept, and how many of them it has compiled.

A TPU chip belongs to one process at a time, and JAX opens every chip it
can see when its backend starts.  So the platform, the visible chips and
the compile cache are settled per process *before* its first jax
compute: the driver and CPU workers are pinned to the host platform,
and a worker leased chips is narrowed to them and must open them
(raylet._worker_env_for gives it ``JAX_PLATFORMS=tpu``, under which a
chip that cannot be opened raises where JAX would otherwise fall back
to the CPU without a word).
"""

from __future__ import annotations

import collections
import os
import threading
import time

from ray_tpu._private import tracing
from ray_tpu._private.resources import detect_tpu_chips

_FORCED = {"value": None}


def ensure_cpu(n_devices: int | None = None) -> None:
    """Pin this process's jax to the host CPU platform.  Call before any
    jax compute.  ``n_devices`` forces a virtual multi-device host platform
    (for testing shardings without real chips)."""
    if _FORCED["value"] == ("cpu", n_devices):
        return
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {want}".strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    # jax reads JAX_PLATFORMS once, when it is imported.  Callers that
    # run after that (__graft_entry__.dryrun_multichip, whose host has
    # imported jax already) need the config value set as well.
    import jax
    jax.config.update("jax_platforms", "cpu")
    _FORCED["value"] = ("cpu", n_devices)


def cpu_pinned() -> bool:
    """True when this process's jax is (or will be) on the host CPU
    platform — robust to list values ('cpu,tpu') and casing."""
    plats = [p.strip().lower()
             for p in os.environ.get("JAX_PLATFORMS", "").split(",")]
    return "cpu" in plats or _FORCED["value"] is not None and \
        _FORCED["value"][0] == "cpu"


def enable_cpu_collectives() -> None:
    """Select the gloo cross-process collective transport for CPU gangs
    (jax.distributed federation needs it; on TPU the ICI fabric makes
    it a no-op).  Must run before this process creates its backend
    client; a late call raises inside jax, which we surface as a
    warning because the symptom otherwise appears much later as a
    hanging collective."""
    if not cpu_pinned():
        return
    try:
        import jax
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception as e:
        import logging
        logging.getLogger(__name__).warning(
            "could not select gloo CPU collectives (%r); if this gang "
            "spans processes, cross-process collectives will fail — "
            "was jax already initialized in this worker?", e)


def cpu_mesh_devices(n: int):
    """Return n virtual CPU devices (forcing the host platform count)."""
    ensure_cpu(n)
    import jax
    devs = jax.devices("cpu")
    if len(devs) < n:
        raise RuntimeError(
            f"asked for {n} virtual cpu devices but jax already initialized "
            f"with {len(devs)}; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before the first jax use in this process")
    return devs[:n]


# The chips this process was narrowed to (one lease per TPU worker).
_BOUND = {"ids": None}


def bind_tpu_chips(ids) -> None:
    """Narrow this process to the chips of its lease.  Runs in a TPU
    worker when a lease arrives, before user code can start the jax
    backend: libtpu reads these variables when it opens the chips.

    A lease of every chip of the host needs no narrowing.  A lease of
    one chip sees that chip alone, as a 1x1x1 host, which is what lets
    several one-chip workers share a host.  Anything between is refused:
    the chips of such a lease need not be ICI neighbours, and libtpu
    aborts on a sub-mesh it cannot form."""
    ids = tuple(sorted(int(i) for i in ids))
    if _BOUND["ids"] == ids:
        return
    if _BOUND["ids"] is not None:
        raise RuntimeError(
            f"this worker is bound to TPU chips {_BOUND['ids']} and "
            f"cannot be re-bound to {ids}: a chip stays with the "
            f"process that opened it")
    host = detect_tpu_chips()
    if len(ids) < host:
        if len(ids) != 1:
            raise RuntimeError(
                f"a TPU lease holds one chip or every chip of its host, "
                f"not {len(ids)} of {host}")
        os.environ.update({"TPU_VISIBLE_CHIPS": str(ids[0]),
                           "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                           "TPU_PROCESS_BOUNDS": "1,1,1"})
    _BOUND["ids"] = ids


def _import_pallas() -> None:
    """What every kernel under ray_tpu/ops is built with."""
    from jax.experimental import pallas  # noqa: F401
    from jax.experimental.pallas import tpu  # noqa: F401


def open_backend() -> None:
    """In a TPU worker (the one kind of process pinned to `tpu`: its
    first jax call opens the lease's chips, whoever makes it), make that
    call here, once, and leave a `jax.backend_init` span under the
    worker's boot: LLMServer and the JaxTrainer's mesh builder come
    here first, so the chip's opening has a name and a length instead
    of hiding in a loader's first array; Pallas is imported on a thread
    beside it (`_import_pallas`).  Any other process is left alone: its
    code may still configure jax before first use."""
    if os.environ.get("JAX_PLATFORMS") != "tpu" or _BOUND.get("opened"):
        return
    import jax
    t0 = time.time()
    # The opening is seconds of waiting on the device with the
    # interpreter free: what the worker's first trace of a kernel would
    # stop to import (Pallas, a second of Python) is imported beside it.
    beside = threading.Thread(target=_import_pallas, daemon=True)
    beside.start()
    devices = jax.devices()
    beside.join()
    _BOUND["opened"] = True
    tracing.start_record(
        "jax", "jax.backend_init", t0, time.time(),
        trace=tracing.start_link(),
        args={"platform": devices[0].platform, "devices": len(devices)})


def tree_nbytes(tree) -> int:
    """Bytes the arrays of a pytree hold (params, a KV cache)."""
    import jax
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(tree)))


def compile_cache_dir() -> str:
    """Where this installation keeps jax's persistent compilation
    cache: ``JAX_COMPILATION_CACHE_DIR`` when the environment places it,
    else one fixed directory beside the package.  Never a temporary or
    per-process name — the path is part of the cache key, so a
    directory that moves never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process, before
    its first compile.  Called by TPU workers (worker_main) and nothing
    else: a cold chip compile of a real model takes tens of seconds.
    When ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and
    no path is set in code."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    install_compile_listener()
    return path


# jax reports each stage of making a program runnable through
# jax.monitoring: tracing, lowering, and the backend compile.  The last
# wraps the persistent cache's lookup, so a program loaded from the
# cache ends a backend-compile event too, after one of _CACHE_LOAD.
_COMPILE_STAGES = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_COMPILES = {"installed": False, "n": 0, "s": 0.0}
_COMPILES_LOCK = threading.Lock()
# The last stages' (start, end, stage, fun_name), epoch seconds, kept
# outside the trace ring (a busy replica's ring turns over in seconds).
_RECENT_STAGES: collections.deque = collections.deque(maxlen=256)
_COMPILING = threading.local()   # per thread: .spans, .loaded


def _on_compile_stage(event, start_time, end_time, **kw) -> None:
    """Time-span listener.  Stages nest (tracing `f` traces the jitted
    `jnp` functions it calls; an eager op on a constant compiles inside
    a trace) and the inner ones end first, so each span adds its length
    less that of the spans it contains: `s` is time on the clock, not a
    sum over nesting levels."""
    if event not in _COMPILE_STAGES:
        return
    spans = getattr(_COMPILING, "spans", None)
    if spans is None:
        spans = _COMPILING.spans = []
    net = end_time - start_time
    while spans and spans[-1][0] >= start_time:
        net -= spans.pop()[1]
    spans.append((start_time, end_time - start_time))
    del spans[:-64]
    _RECENT_STAGES.append((start_time, end_time, event.rsplit("/", 1)[1],
                           str(kw.get("fun_name", "?"))))
    backend = event == _COMPILE_STAGES[2]
    with _COMPILES_LOCK:
        _COMPILES["s"] += max(0.0, net)
        _COMPILES["n"] += backend
    if backend:
        tracing.record("jax", "jax.compile", start_time,
                       end_time - start_time,
                       args={"fun_name": str(kw.get("fun_name", "?")),
                             "from_cache": getattr(_COMPILING, "loaded",
                                                   False)})
        _COMPILING.loaded = False


def _on_cache_load(event, duration_secs, **kw) -> None:
    if event == _CACHE_LOAD:
        _COMPILING.loaded = True


def install_compile_listener() -> None:
    """Count this process's compiles where they happen.  Idempotent;
    called by every TPU worker (enable_compile_cache) and by the serving
    engine's constructor.  From then on `compile_counters()` moves with
    every program that is traced, lowered, compiled or loaded from the
    persistent cache, on any thread, and each backend compile or cache
    load leaves one `jax.compile` event (fun_name, from_cache) in the
    process's trace ring — a steady-state recompile shows in
    `rt timeline --cluster` whichever worker it happens in."""
    with _COMPILES_LOCK:
        if _COMPILES["installed"]:
            return
        _COMPILES["installed"] = True
    from jax import monitoring
    monitoring.register_event_time_span_listener(_on_compile_stage)
    monitoring.register_event_duration_secs_listener(_on_cache_load)


def compile_counters() -> tuple:
    """(jit_compiles, jit_compile_s) of this process since the listener
    was installed: backend compiles plus cache loads, and the seconds
    spent tracing, lowering, compiling and loading."""
    return _COMPILES["n"], _COMPILES["s"]


def compile_stages(since: float = 0.0) -> list:
    """(start, end, stage, fun_name) of the stages (tracing, lowering,
    backend compile or cache load), on any thread, that ended at or
    after `since` (epoch seconds), oldest first; the last 256 are
    kept."""
    return [st for st in list(_RECENT_STAGES) if st[1] >= since]


def device_facts() -> dict:
    """What this process computes on, as jax reports it.  A driver must
    stay off jax (it would take the chip from its own workers), so it
    learns the device from the worker that holds it: serve replicas
    (LLMServer.replica_info), train loops, chip_smoke.py.
    ``device_files`` are the chip device files this process has open —
    what tells apart two one-chip workers that each see "device 0"."""
    import jax
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    files = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith(("/dev/accel", "/dev/vfio/")) \
                and target != "/dev/vfio/vfio":
            files.add(target)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "bytes_limit": stats[0].get("bytes_limit"),
        "pid": os.getpid(),
        "device_files": sorted(files),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
