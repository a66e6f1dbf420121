"""Share (%) of its roofline that the decode tick reached in the traced
window: the least time the chip could take for the ticks' needed
operations and bytes (the architecture's `decode_tick`: weights once
per tick, the context actually attended to) over the traced time of
`program`.  Memory bounds it at these sizes; the bound found is noted."""

from benchmarks.lib import obs as o
from benchmarks.lib.costs import min_time
from benchmarks.lib.peaks import peaks_for


def read(obs, program):
    trace = obs.get("trace") or {}
    runs = (trace.get("programs") or {}).get(program)
    if not runs:
        return None
    rows = ctx = 0
    for r, i, _ in o.tokens_between(obs, obs["trace_t0"], obs["trace_t1"]):
        if i > 0:                      # token i came from a decode tick
            rows += 1
            ctx += r.prompt_len + i
    n = len(runs)
    least = min_time(
        obs["arch"].decode_tick(obs["config"], rows / n, ctx / n),
        peaks_for(obs["replica_info"]["kind"]))
    o.note(obs, f"{program}_bound", least["bound"])
    return 100 * least["seconds"] * n / sum(runs)
