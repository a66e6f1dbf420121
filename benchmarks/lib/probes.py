"""What only the process that holds the chip can do, shared by the
serving replica's probes and the training loop."""

from __future__ import annotations

import os


def cache_entries() -> int:
    """Entries in this process's persistent compile cache (-1 when it
    has none)."""
    import jax
    try:
        return sum(not n.endswith("-atime") for n in
                   os.listdir(jax.config.jax_compilation_cache_dir))
    except (OSError, TypeError):
        return -1


def start_trace(trace_dir: str) -> None:
    """A device trace with the Python tracer off: device lines and XLA's
    host events only, so the file stays small and the host undisturbed."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
