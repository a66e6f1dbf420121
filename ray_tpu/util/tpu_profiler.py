"""XLA/TPU device profiling (the accelerator-side complement of
`ray_tpu.timeline()`'s host-side chrome trace).

The reference's `ray timeline` shows task/actor scheduling; what it
cannot show is where the chip time goes inside a jitted step.  This
wraps `jax.profiler` so a trace lands in the session directory (or any
dir) and can be opened in TensorBoard/Perfetto, and works inside remote
tasks/actors — each process writes to its own subdirectory, so a gang
profile is one directory tree.

    from ray_tpu.util import tpu_profiler

    with tpu_profiler.trace():          # session-dir default
        state, m = step(state, tokens)

    tpu_profiler.start(); ...; path = tpu_profiler.stop()

A capture of a serving replica also holds the engine loop's phases:
while one runs, `serve.llm`'s worker thread wraps each phase of a loop
turn in `annotate("engine.<phase>")`, so the host plane carries
`engine.commands`, `engine.sweep`, `engine.admit`,
`engine.prefill_dispatch`, `engine.tick_dispatch`, `engine.device_wait`
and `engine.emit` on the profiler's own clock, beside the device plane
(the idle wait is the absence of all seven), and keeps a capture log of
its own: every phase it closes, `idle` too, and a mark before every
program it hands to the device.  Read the log after `stop()` with
`LLMServer.trace_spans()` (`engine.phase.<name>`, `engine.dispatch`,
`engine.capture_log`); `benchmarks/readers/idle_by_phase.py` joins it
to the device plane's idle gaps, and
`benchmarks/tools/host_gaps.py <file.xplane.pb>` splits them by the
host plane's regions alone.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Optional

_active_dir: Optional[str] = None


def default_trace_dir() -> str:
    """<session_dir>/tpu_profile/<pid> when a runtime session exists,
    else /tmp/ray_tpu/tpu_profile/<pid>."""
    base = os.environ.get("RT_SESSION_DIR", "/tmp/ray_tpu")
    try:
        from ray_tpu._private import api as _api
        node = getattr(_api, "_head_node", None)
        if node is not None and getattr(node, "session_dir", None):
            base = node.session_dir
    except Exception:
        pass
    return os.path.join(base, "tpu_profile",
                        f"{int(time.time())}-{os.getpid()}")


def start(trace_dir: Optional[str] = None) -> str:
    """Begin capturing a device trace; returns the trace directory.

    The Python tracer is off and the host tracer at the level that
    keeps `annotate()`d regions and XLA's own host events: device lines
    plus named host phases, a small file, and a host that is not slowed
    by a hook on every Python call — the capture the benchmark reduces
    (`benchmarks/lib/probes.start_trace` sets the same options)."""
    global _active_dir
    if _active_dir is not None:
        raise RuntimeError(f"a trace is already active: {_active_dir}")
    import jax
    d = trace_dir or default_trace_dir()
    os.makedirs(d, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    _active_dir = d
    return d


def stop() -> str:
    """Finish the capture; returns the directory holding the trace
    (open with `tensorboard --logdir <dir>` or upload the contained
    .trace.json.gz to Perfetto)."""
    global _active_dir
    if _active_dir is None:
        raise RuntimeError("no active trace (call start() first)")
    import jax
    # Clear the guard FIRST: a failing stop_trace must not wedge every
    # later start() with "a trace is already active".
    d, _active_dir = _active_dir, None
    jax.profiler.stop_trace()
    return d


@contextlib.contextmanager
def trace(trace_dir: Optional[str] = None):
    """Context manager: profile the enclosed device work."""
    d = start(trace_dir)
    try:
        yield d
    finally:
        stop()


def annotate(name: str):
    """Label a region so it shows up named in the trace's host plane
    (wraps jax.profiler.TraceAnnotation).  With no capture running the
    region costs one check of the profiler's flag (~0.5 us)."""
    import jax
    return jax.profiler.TraceAnnotation(name)
