"""Share (%) of its roofline that the block step reached in the traced
window: the least time the chip could take for the steps' needed
operations and bytes (the architecture's `block_step`: weights once a
step, every live row's columns, the context its rows hold) over the
traced time of `program`.

Rows, columns and depths are THE PROGRAM'S OWN COUNTS, not client
tokens (a block step yields tokens a block at a time, one forward in
five yields none, and a row's forwards are not its tokens): the gain of
the engine's cumulative counters `block_steps`, `block_row_forwards`,
`block_columns` and `attn_keys_resident` (the keys the steps' rows held,
context and block, times the layers) between the first and the last
`stats()` sample taken inside the traced window.  Their ratios are the
mean step's rows, columns and keys; the trace gives the steps' times.
None where the program has no such program or counter (a parent commit,
a body whose block is 1)."""

from benchmarks.lib import obs as o
from benchmarks.lib.costs import min_time
from benchmarks.lib.peaks import peaks_for

KEYS = ("block_steps", "block_row_forwards", "block_columns",
        "attn_keys_resident")


def traced_samples(obs):
    """The `stats()` samples taken inside the traced window: a sample
    carries the engine's uptime, and `stats0` was taken as the window
    opened, so a sample's time on the host's clock follows."""
    s0 = obs.get("stats0") or {}
    if "uptime_s" not in s0 or "trace_t0" not in obs:
        return []
    at = lambda s: obs["t_w"] + s["uptime_s"] - s0["uptime_s"]  # noqa: E731
    return [s for s in obs.get("samples") or []
            if obs["trace_t0"] <= at(s) <= obs["trace_t1"]]


def read(obs, program):
    trace = obs.get("trace") or {}
    runs = (trace.get("programs") or {}).get(program)
    arch = obs.get("arch")
    if not runs or not hasattr(arch, "block_step"):
        return None
    inside = traced_samples(obs)
    first, last = (inside[0], inside[-1]) if len(inside) > 1 \
        else (obs.get("stats0") or {}, obs.get("stats1") or {})
    if any(k not in first or k not in last for k in KEYS):
        return None
    steps, rows, columns, keys = (last[k] - first[k] for k in KEYS)
    if not steps:
        return None
    layers = obs["config"]["num_hidden_layers"]
    context = keys / layers - columns     # what the rows held before
    least = min_time(
        arch.block_step(obs["config"], rows / steps, columns / steps,
                        context / steps),
        peaks_for(obs["replica_info"]["kind"]))
    o.note(obs, f"{program}_bound", least["bound"])
    o.note(obs, f"{program}_mean_step",
           {"rows": rows / steps, "columns": columns / steps,
            "context_tokens": context / steps, "steps_counted": steps,
            "steps_traced": len(runs)})
    return 100 * least["seconds"] * len(runs) / sum(runs)
