"""Share (%) of its roofline that the single-row prefill chunk reached:
the mean least time of a chunk (the architecture's `prefill_chunk`,
averaged over every chunk of every prompt sent in the window; the output
head only in a prompt's last chunk) over the mean traced time of
`program`."""

import statistics

from benchmarks.lib import obs as o
from benchmarks.lib.costs import min_time
from benchmarks.lib.peaks import peaks_for


def read(obs, program):
    runs = ((obs.get("trace") or {}).get("programs") or {}).get(program)
    if not runs:
        return None
    width = obs["config"]["serving"]["engine"].get("prefill_chunk", 32)
    peaks = peaks_for(obs["replica_info"]["kind"])
    least, bounds = [], set()
    for r in obs["requests"]:
        if r.sent is None or not o.in_window(obs, r.sent):
            continue
        for start in range(0, r.prompt_len, width):
            m = min_time(obs["arch"].prefill_chunk(
                obs["config"], min(width, r.prompt_len - start), start,
                with_head=start + width >= r.prompt_len), peaks)
            least.append(m["seconds"])
            bounds.add(m["bound"])
    if not least:
        return None
    o.note(obs, f"{program}_bound", sorted(bounds))
    return 100 * statistics.fmean(least) / statistics.fmean(runs)
