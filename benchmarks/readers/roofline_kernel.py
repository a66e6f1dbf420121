"""Share (%) of its roofline that ONE KERNEL of a program reached in the
traced window: the least time the chip could take for the kernel's
needed operations and bytes (the architecture's function `cost`, which
counts every instance of the kernel in one call of the program, at the
window's mean live rows a call) over the kernel's own traced time.

The trace reduction hands readers the ten heaviest device operations,
each named `<program>/<HLO instruction>`, and a Pallas call's
instruction carries its kernel's name (`kda_step.3`): the kernel's time
is the sum over the instructions `<program>/<kernel>` and
`<program>/<kernel>.<n>`.  `instances` names the entry of the
architecture's `dims` that says how many there are in a call (one a
layer that runs it): unless ALL of them are among the ten the time
would leave part of the work out, and nothing is returned.  None, too,
where the program has no such kernel (a parent commit, another body) or
the architecture no such function."""

import re

from benchmarks.lib import obs as o
from benchmarks.lib.costs import min_time
from benchmarks.lib.peaks import peaks_for


def read(obs, program, kernel, cost, instances):
    trace = obs.get("trace") or {}
    runs = (trace.get("programs") or {}).get(program)
    arch = obs.get("arch")
    if not runs or not hasattr(arch, cost) or not hasattr(arch, "dims"):
        return None
    name = re.compile(re.escape(f"{program}/{kernel}") + r"(\.\d+)?$")
    found = [t for op, t in (trace.get("breakdown") or {}).get(
        "device_ops", []) if name.match(op)]
    want = arch.dims(obs["config"]).get(instances)
    o.note(obs, f"{kernel}_instances_among_the_heaviest",
           [len(found), want])
    if not found or len(found) != want:
        return None
    rows = sum(1 for _, i, _ in o.tokens_between(
        obs, obs["trace_t0"], obs["trace_t1"]) if i > 0)
    n = len(runs)
    least = min_time(getattr(arch, cost)(obs["config"], rows / n),
                     peaks_for(obs["replica_info"]["kind"]))
    o.note(obs, f"{kernel}_bound", least["bound"])
    return 100 * least["seconds"] * n / sum(found)
