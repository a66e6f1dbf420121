"""From a profiler trace (`*.xplane.pb`, read with nothing but
`jax.profiler.ProfileData`) to the numbers the per-layer readers take:
device busy/idle, time per jitted program, idle gaps named by the
programs around them, the heaviest device operations, collective time
that no compute hides.

The arithmetic works on a small intermediate form, so that a test can
feed it intervals by hand:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

On a TPU (looked at by hand, PR 24) the device planes are
`/device:TPU:<n>`.  Line `XLA Modules` holds one event per run of a
jitted program, named `jit__paged_tick(<fingerprint>)`.  Line `XLA Ops`
holds one event per synchronous operation, nested by time (a `while`
contains its body's operations) and named by its whole HLO text
(`%while.3 = (...) while(...)`), which `op_name` cuts to `while.3`.
Line `Async XLA Ops` holds the asynchronous ones (`copy-start`, and the
`all-gather-start` kind across chips), each lasting until its `done`.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OP_LINES = ("XLA Ops", "Async XLA Ops")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
ASYNC_EDGE = re.compile(r"-(start|done)(\.\d+)?$")
# operations that only contain others: their time is their children's
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Dict[str, Any]:
    """xplane.pb -> the intermediate form.  Device planes only: idle
    gaps are named by the programs around them, not by host events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            cut = op_name if line.name in OP_LINES else str
            lines.append({"name": line.name, "events": [
                [cut(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the (merged) intervals `a` that no interval of the
    (merged) `b` covers."""
    out, j = [], 0
    b = list(b)
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


OPCODE = re.compile(r"[\s)]([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> str:
    """`%while.3 = (s32[], ...) while(...)` -> `while.3`.  A collective
    that jax named after its own primitive keeps its HLO opcode in
    front: `%psum.125 = f32[...] all-reduce(...)` ->
    `all-reduce/psum.125`."""
    name, _, rest = event_name.partition(" = ")
    name = name.lstrip("%").strip()
    code = OPCODE.search(rest)
    if code and COLLECTIVE.match(code.group(1)) \
            and not COLLECTIVE.match(name):
        return f"{code.group(1)}/{name}"
    return name


def program_name(event_name: str) -> str:
    """`jit__paged_tick(1234)` -> `jit__paged_tick`."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _short(program: str) -> str:
    return program[4:] if program.startswith("jit_") else program


def _line(plane: Dict, *names: str) -> List[List]:
    for want in names:
        for line in plane["lines"]:
            if line["name"] == want:
                return line["events"]
    return []


def reduce_plane(plane: Dict) -> Dict[str, Any]:
    """One device plane -> its summary, from its first to its last
    device event.  Times in seconds."""
    ops = _line(plane, "XLA Ops")
    modules = _line(plane, "XLA Modules")
    timed = ops or modules
    if not timed:
        return {}
    lo = min(e[1] for e in timed)
    hi = max(e[1] + e[2] for e in timed)
    busy = union([(e[1], e[1] + e[2]) for e in timed if e[2] > 0])
    busy_ns = total(busy)

    programs: Dict[str, List[float]] = {}
    mods = sorted(modules, key=lambda e: e[1])
    for name, _, dur in mods:
        programs.setdefault(program_name(name), []).append(dur / 1e9)

    # idle gaps between programs, named by the programs on either side
    gaps: Dict[str, float] = {}
    gap_events: List[Tuple[str, float, float]] = []
    end, last = None, "?"
    for name, start, dur in mods:
        if end is not None and start > end:
            label = (f"after:{_short(program_name(last))}"
                     f"/before:{_short(program_name(name))}")
            gaps[label] = gaps.get(label, 0.0) + (start - end) / 1e9
            gap_events.append((label, end / 1e9, (start - end) / 1e9))
        if end is None or start + dur > end:
            end, last = start + dur, name

    # heaviest operations, named <program>/<op>
    op_time: Dict[str, float] = {}
    starts = [m[1] for m in mods]
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        prog = program_name(mods[i][0]) if i >= 0 and \
            start < mods[i][1] + mods[i][2] else "?"
        key = f"{prog}/{name}"
        op_time[key] = op_time.get(key, 0.0) + dur / 1e9

    # collectives, and the part of them no compute covers
    coll = union([(e[1], e[1] + e[2])
                  for e in ops + _line(plane, "Async XLA Ops")
                  if COLLECTIVE.match(e[0]) and e[2] > 0])
    compute = union([(e[1], e[1] + e[2]) for e in ops
                     if not COLLECTIVE.match(e[0])
                     and not CONTAINER.match(e[0])
                     and not ASYNC_EDGE.search(e[0]) and e[2] > 0])
    exposed = subtract(coll, compute)

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "programs": programs, "gaps": gaps, "gap_events": gap_events,
            "op_time": op_time, "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9}


def reduce(trace: Dict[str, Any], window_s: Optional[float] = None
           ) -> Dict[str, Any]:
    """Every device plane reduced, and the averages the last line's
    `device` takes: busy_s averaged over the chips used, window_s of the
    traced window (the caller's own clock when given)."""
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    per = [r for r in (reduce_plane(p) for p in planes) if r]
    if not per:
        return {"devices": 0}
    first = per[0]
    top_ops = sorted(first["op_time"].items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(first["gaps"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(per),
        "busy_s": statistics.fmean(r["busy_s"] for r in per),
        "window_s": window_s if window_s else max(r["window_s"]
                                                  for r in per),
        "programs": first["programs"],
        "gap_events": first["gap_events"],
        "collective_s": statistics.fmean(r["collective_s"] for r in per),
        "collective_exposed_s": statistics.fmean(
            r["collective_exposed_s"] for r in per),
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in top_gaps]},
    }


def describe(path: str, limit: int = 6) -> str:
    """What a trace holds, for a reader who has not seen one: planes,
    lines, event counts, the first few events of each line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:limit]:
                out.append(f"      {e.name!r} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f}")
    return "\n".join(out)
