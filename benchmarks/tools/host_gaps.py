"""What the host was doing while the chip sat idle.

    python3 benchmarks/tools/host_gaps.py <file.xplane.pb> [gaps to print]

The file is a capture of a serving replica: one kept from a traced run
(`benchmarks/run.py ... --trace 1 --keep-trace <file>`) or one an
operator made with `ray_tpu.util.tpu_profiler.start()/stop()`.  The
engine's worker thread wraps each phase of a loop turn in a profiler
annotation (`engine.commands`, `engine.sweep`, `engine.admit`,
`engine.prefill_dispatch`, `engine.tick_dispatch`, `engine.device_wait`,
`engine.emit`), so the host plane holds them on the profiler's own
clock, beside the device plane.  This tool

1. states the evidence that the two planes share a clock: the offset
   between the end of each `jit__paged_tick` run on the device and the
   end of the `engine.device_wait` nearest to it (the host's blocking
   fetch of that tick's tokens) — median, quartiles, and the share of
   ticks within 5 ms.  Two clocks that did not agree would scatter the
   offsets over half a tick either way;
2. prints the idle gaps of the device as `breakdown.idle_gaps` names
   them (`after:<program>/before:<program>`), each split by the host
   phase that overlapped it.  `(no phase)` is idle time under no
   annotation: the loop's idle wait, or a program that predates them.
   `(capture's edge)` is idle time before the first or after the last
   host phase the capture kept: a phase still open when the capture
   stops is not written, so up to one phase is missing at each end.

It reads with `trace_reduce`'s loader conventions and interval
arithmetic and edits nothing; the ledger's `breakdown` keeps its
program-named gaps.
"""

from __future__ import annotations

import bisect
import os
import statistics
import sys
from typing import Any, Dict, List, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import trace_reduce as tr  # noqa: E402

PHASE_PREFIX = "engine."
NO_PHASE = "(no phase)"
EDGE = "(capture's edge)"
TICK = "jit__paged_tick"
WAIT = PHASE_PREFIX + "device_wait"
Event = Tuple[str, float, float]          # name, start_s, dur_s


def load_host_phases(path: str) -> List[Event]:
    """`engine.*` events of every host plane, in seconds, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                       for e in line.events
                       if e.name.startswith(PHASE_PREFIX))
    return sorted(out, key=lambda e: e[1])


def _covered(phases: Sequence[Event]) -> Tuple[float, float]:
    """From the first kept host phase's start to the last one's end."""
    if not phases:
        return (0.0, 0.0)
    return (min(s for _, s, _ in phases), max(s + d for _, s, d in phases))


def clock_offset(modules: Sequence[Sequence], phases: Sequence[Event]
                 ) -> Dict[str, Any]:
    """Offsets (ms) between each tick's end on the device plane
    (`modules`: [name, start_ns, dur_ns]) and the end of the nearest
    `engine.device_wait` on the host plane, over the ticks that end
    inside the host phases' coverage."""
    waits = sorted(s + d for n, s, d in phases if n == WAIT)
    lo, hi = _covered(phases)
    ticks = [end for end in ((m[1] + m[2]) / 1e9 for m in modules
                             if tr.program_name(m[0]) == TICK)
             if lo <= end <= hi]
    if not waits or not ticks:
        return {"ticks": len(ticks), "waits": len(waits)}
    offs = []
    for end in ticks:
        i = bisect.bisect_left(waits, end)
        near = min(waits[max(0, i - 1):i + 1], key=lambda w: abs(w - end))
        offs.append((near - end) * 1e3)
    q1, q2, q3 = (statistics.quantiles(offs, n=4) if len(offs) > 1
                  else [offs[0]] * 3)
    return {"ticks": len(ticks), "waits": len(waits), "median_ms": q2,
            "q1_ms": q1, "q3_ms": q3, "min_ms": min(offs),
            "max_ms": max(offs),
            "within_5ms_share": sum(abs(o) <= 5 for o in offs) / len(offs)}


def split_gaps(gap_events: Sequence[Sequence], phases: Sequence[Event]
               ) -> Dict[str, Dict[str, float]]:
    """{gap label: {host phase: seconds of the label's gaps that the
    phase overlapped, ..., NO_PHASE: the rest, "total": all}}.
    `gap_events`: (label, start_s, dur_s) as trace_reduce gives them."""
    by_phase: Dict[str, List[tr.Interval]] = {}
    for name, start, dur in phases:
        by_phase.setdefault(name[len(PHASE_PREFIX):], []).append(
            (start, start + dur))
    by_phase = {k: tr.union(v) for k, v in by_phase.items()}
    lo, hi = _covered(phases)
    by_phase[EDGE] = [(float("-inf"), lo), (hi, float("inf"))]
    gaps: Dict[str, List[tr.Interval]] = {}
    for label, start, dur in gap_events:
        gaps.setdefault(label, []).append((start, start + dur))
    out = {}
    for label, spans in gaps.items():
        spans = tr.union(spans)
        whole = tr.total(spans)
        row = {p: whole - tr.total(tr.subtract(spans, iv))
               for p, iv in by_phase.items()}
        row = {p: s for p, s in row.items() if s > 0}
        row[NO_PHASE] = max(0.0, whole - sum(row.values()))
        row["total"] = whole
        out[label] = row
    return out


def report(path: str, top: int = 10) -> str:
    trace = tr.load(path)
    planes = [p for p in trace["planes"] if tr.DEVICE_PLANE.match(p["name"])]
    if not planes:
        return "no device plane in this capture"
    plane = planes[0]
    reduced = tr.reduce_plane(plane)
    phases = load_host_phases(path)
    lines = [f"{plane['name']}: window {reduced['window_s']:.3f} s, busy "
             f"{reduced['busy_s']:.3f} s, idle "
             f"{reduced['window_s'] - reduced['busy_s']:.3f} s; "
             f"{len(phases)} engine.* host events"]
    if not phases:
        return "\n".join(lines + [
            "the host plane holds no engine.* event: the program predates "
            "the loop's annotations, or the capture dropped the host "
            "tracer"])
    dev_lo = min(e[1] for e in tr._line(plane, "XLA Modules")) / 1e9
    host_s: Dict[str, float] = {}
    for name, _, dur in phases:
        host_s[name] = host_s.get(name, 0.0) + dur
    lines.append("host phases in the capture (s): " + ", ".join(
        f"{n[len(PHASE_PREFIX):]} {s:.3f}"
        for n, s in sorted(host_s.items(), key=lambda kv: -kv[1])))
    off = clock_offset(tr._line(plane, "XLA Modules"), phases)
    if "median_ms" in off:
        lines.append(
            f"clock: end of {TICK} -> end of nearest {WAIT}, "
            f"{off['ticks']} ticks: median {off['median_ms']:+.3f} ms, "
            f"quartiles {off['q1_ms']:+.3f} / {off['q3_ms']:+.3f}, range "
            f"{off['min_ms']:+.3f} .. {off['max_ms']:+.3f}; "
            f"{100 * off['within_5ms_share']:.1f} % within 5 ms "
            f"(first device event {dev_lo - phases[0][1]:+.3f} s after "
            f"the first host phase)")
    else:
        lines.append(f"clock: nothing to pair ({off})")
    split = split_gaps(reduced["gap_events"], phases)
    lines.append(f"idle gaps by the programs around them, split by host "
                 f"phase (s; the {top} largest):")
    for label, row in sorted(split.items(),
                             key=lambda kv: -kv[1]["total"])[:top]:
        parts = ", ".join(f"{p} {s:.3f}" for p, s in sorted(
            ((p, s) for p, s in row.items() if p != "total"),
            key=lambda kv: -kv[1]) if s >= 0.0005)
        lines.append(f"  {row['total']:.3f}  {label}: {parts}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(report(sys.argv[1],
                 int(sys.argv[2]) if len(sys.argv) > 2 else 10))
