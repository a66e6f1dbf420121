"""Share (%) of the traced window in which the chip stood idle while the
host was in one of `phases`: the device's idle gaps (`gap_events` of the
first device plane) joined to the engine's capture log (the
`engine.phase.<name>`, `engine.dispatch` and `engine.capture_log` events
`LLMServer.trace_spans()` returns behind the ring's spans while or after
a profiler capture), so the reading is a part of `device_idle_share.*`
and has its denominator.  None where the program keeps no such log (a
parent commit), where the trace is empty, and where the two clocks
cannot be joined: no attribution is better than a wrong one.

**The clocks.**  The log is in epoch us (the ring's form; one
(monotonic, epoch) pair a capture).  The capture's clock is not
assumed: CLOCK_NOTE below says what it was found to be on the chip.
The one offset between the two is fitted from the data.  A gap's END is
the device start of the program its label names after `before:`, and a
program that starts after an idle gap was dispatched at most a launch
earlier.  So every (gap end, dispatch mark of the same program) pair
within a prior of PRIOR_S votes for its difference, in bins of BIN_S;
the prior is centred where the log's first entry meets the capture's
first gap and, where the harness's own clock says when the capture
began (`obs["trace_t0"]`), on the two hypotheses that gives (the
capture's clock is the epoch; it counts from the capture's start).
True pairs pile up a launch above the offset; pairs of a gap with
another turn's mark, and gaps whose program was queued long before,
scatter (ticks are periodic, but chunks, sweeps and the ticks' own
jitter are not).  A gap under TURNAROUND_S has no vote: a program that
was already queued starts a turnaround after the one before it (1-10 us
on a v5e, against 100 us and more where the device waited for the
host: nothing between, my chip runs, PR 61), so its gap says nothing
about when it was handed over, and such gaps are four in five where the
loop reads a tick one turn late.  Under MIN_VOTES votes within PEAK_S
of the peak, or under MIN_RATIO times the tallest bin at least RIVAL_S
away, there is no fit.  The offset is the low edge of the peak: the
least launch.  And since a program of more leaves takes longer to hand
over (a step's least launch stands up to a millisecond over an eager
scalar's), each program with MIN_VOTES votes of its own within SPREAD_S
of the peak gives its own low edge, and the offset is the least of
them: no mark may read later than its program's start.

A window in which the chip was kept fed holds few late dispatches (8
settling turns in one chat window of 467 turns: 6 votes), and their
pairs with other turns' marks are as tall.  The loop's `device_wait`
phases then bound the offset from the other side, and there are
hundreds: a wait for a step's result cannot end before the step has, so
(a gap's START after a step program, the end of the `device_wait` that
read it) differ by the offset LESS a readback (1.2-1.6 ms on a v5e
behind this runtime).  Alone they cannot tell one turn from the next (a
fed loop is periodic), but they can tell a true pair from another
turn's: where the votes fall short, only the pairs keep their vote
that WAIT_SHARE of the steps' ends stand within READBACK_S below (each
against some wait's end), and BACKED_VOTES of those, BACKED_RATIO times
the tallest bin elsewhere and CHANCE times what as many pairs strewn
over the prior would leave there, make a fit (`clock_fit.backed_by`
says so).  And where even those fail (a window with two late
dispatches), the waits' edge alone is taken, a readback under the
offset and a turn's alias at worst, and `clock_fit.weak` says so: such
a window is all `idle` or all `queued`, and moves by under a point of
itself for it.

**The attribution**, gap by gap, with the log moved onto the capture's
clock.  What a compile covers of a gap (the log's `engine.compile`
stages: tracing, lowering, the backend's compile; kept outside the
ring, which a busy replica turns over before the harness pulls it) is
`compile`, whatever else holds there: a program compiles inside the
call that hands it over, AFTER its mark.
Of the rest: the closing program has a mark (the latest of its name at
or before the gap's end): marked before the gap began, the gap is
`queued` (the program was already the device's to start); else the
part after the mark is `launch` and the part before it is split by the
phases that cover it.  The closing program has no mark (`copy`,
`squeeze`, `dynamic_slice`: eager, and queued behind a step): the gap is
split by overlap alone, and its seconds are also summed under
`unmarked`.  Idle the log does not cover is `unseen`.  The arithmetic
is `trace_reduce`'s (`union` / `subtract` / `total`), as
`tools/host_gaps.split_gaps` uses it.

Notes (`obs.note`): `idle_by_phase_s` (seconds under each of CATEGORIES:
they sum to the plane's idle seconds), `idle_unmarked_s`,
`idle_gaps_by_phase` (the labels of `breakdown.idle_gaps`, each with
its split), `clock_fit`, `capture_log` (the log's own count of itself:
`dropped` must read 0).
"""

from __future__ import annotations

import bisect
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.lib import obs as o
from benchmarks.lib import trace_reduce as tr

CLOCK_NOTE = (
    "On a TPU v5e (my chip runs, PR 61, every serving cell): the device "
    "plane counts seconds from the capture's own start, not from the "
    "epoch: the first gap starts 0.06-0.10 s after 0 where "
    "obs['trace_t0'] reads 1.79e9, and the fitted offsets are -trace_t0 "
    "+ 0.05..0.08 s.  The capture's HOST plane is not on the device "
    "plane's clock to a millisecond: in three kept captures (mimo-agent, "
    "internlm2-batch, zaya-reason) the `engine.<phase>` regions start "
    "1.32 / 0.54 / 0.25 ms (quartiles 3-5 us apart) after the same "
    "phases of the log moved by the offset fitted to the device plane, "
    "which one clock would forbid (a program would start before its own "
    "mark).  So the join is fitted to the device's events and to nothing "
    "else.")

PHASES = ("idle", "commands", "sweep", "admit", "prefill_dispatch",
          "tick_dispatch", "device_wait", "emit")
CATEGORIES = PHASES + ("compile", "launch", "queued", "unseen")
PHASE_EVENT = "engine.phase."
PRIOR_S = 1.0
BIN_S = 50e-6
PEAK_S = 100e-6
RIVAL_S = 1e-3
SPREAD_S = 2e-3
TURNAROUND_S = 20e-6
MIN_VOTES = 12
MIN_RATIO = 3.0
STEPS = ("_paged_tick", "_paged_block_step", "_paged_verify")
READBACK_S = 3e-3
WAIT_SHARE = 0.5
BACKED_VOTES = 3
CHANCE = 8.0
BACKED_RATIO = 2.0
# a mark may read this much later than its program's start on the
# device and still be its mark: the fit's own resolution
LATE_S = 100e-6


def read_log(spans: Sequence[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """The capture log out of a replica's spans, times in epoch
    seconds: {"phases": [(name, start, end)] by start, "marks":
    {program: [times]}, "compiles": [(start, end)], "self": the
    `engine.capture_log` event's arguments}; None where there is none."""
    own = [s for s in spans if s.get("name") == "engine.capture_log"]
    if not own:
        return None
    phases, marks, compiles = [], {}, []
    for s in spans:
        name = s.get("name", "")
        if name.startswith(PHASE_EVENT):
            phases.append((name[len(PHASE_EVENT):], s["ts"] / 1e6,
                           (s["ts"] + s["dur"]) / 1e6))
        elif name == "engine.dispatch":
            marks.setdefault(s["args"]["program"], []).append(s["ts"] / 1e6)
        elif name == "engine.compile":
            compiles.append((s["ts"] / 1e6, (s["ts"] + s["dur"]) / 1e6))
    phases.sort(key=lambda p: p[1])
    return {"phases": phases, "compiles": tr.union(compiles),
            "marks": {p: sorted(ts) for p, ts in marks.items()},
            "self": own[-1].get("args") or {}}


def closing_program(label: str) -> str:
    """`after:_paged_tick/before:paged_read_pages` -> `paged_read_pages`."""
    return label.rpartition("/before:")[2]


def _peak(diffs: np.ndarray) -> Optional[Dict[str, Any]]:
    """The tallest bin of `diffs` (seconds, within +-PRIOR_S), what
    stands within PEAK_S of it, its tallest rival RIVAL_S or more away
    and the peak's low edge."""
    if not len(diffs):
        return None
    n_bins = int(round(2 * PRIOR_S / BIN_S))
    idx = np.clip(((diffs + PRIOR_S) / BIN_S).astype(np.int64), 0,
                  n_bins - 1)
    hist = np.bincount(idx, minlength=n_bins)
    top = int(hist.argmax())
    near = int(round(PEAK_S / BIN_S))
    far = int(round(RIVAL_S / BIN_S))
    votes = int(hist[max(0, top - near):top + near + 1].sum())
    rivals = np.concatenate([hist[:max(0, top - far + 1)],
                             hist[top + far:]])
    rival = int(rivals.max()) if len(rivals) else 0
    # the low edge: down from the top while the bins stay part of it
    floor = max(2, 0.2 * hist[top])
    low = top
    while low > max(0, top - 2 * near) and hist[low - 1] >= floor:
        low -= 1
    inside = diffs[(idx >= low) & (idx <= top + near)]
    return {"votes": votes, "rival": rival, "edge": float(inside.min()),
            "inside": inside}


def _backed(diffs: np.ndarray, step_ends: np.ndarray,
            waits: np.ndarray) -> np.ndarray:
    """The differences that WAIT_SHARE of the steps' ends on the device
    stand within READBACK_S below, each paired with the end of a
    `device_wait` (the log's clock moved by the same centre): were the
    offset there, that share of the steps would have been read a
    readback after they ended."""
    if not len(diffs) or not len(step_ends) or not len(waits):
        return diffs[:0]
    lo = np.searchsorted(waits, step_ends - PRIOR_S, side="left")
    hi = np.searchsorted(waits, step_ends + PRIOR_S, side="right")
    ends = np.sort(np.concatenate([s - waits[a:b] for s, a, b
                                   in zip(step_ends, lo, hi)]))
    support = np.searchsorted(ends, diffs, side="left") \
        - np.searchsorted(ends, diffs - READBACK_S, side="left")
    return diffs[support >= WAIT_SHARE * len(step_ends)]


def _waits_alone(step_ends: np.ndarray, waits: np.ndarray
                 ) -> Optional[Dict[str, Any]]:
    """The last resort, for a window with next to no late dispatch (a
    loop that idles, or one that never runs dry): the peak of (a
    `device_wait`'s end less a step's end on the device), whose low edge
    is the least readback past the offset; None under MIN_VOTES."""
    if not len(step_ends) or not len(waits):
        return None
    lo = np.searchsorted(waits, step_ends - PRIOR_S, side="left")
    hi = np.searchsorted(waits, step_ends + PRIOR_S, side="right")
    peak = _peak(np.concatenate([waits[a:b] - s for s, a, b
                                 in zip(step_ends, lo, hi)]))
    return peak if peak and peak["votes"] >= MIN_VOTES else None


def fit_clock(gap_events: Sequence[Sequence], log: Dict[str, Any],
              trace_t0: Optional[float] = None) -> Dict[str, Any]:
    """The offset (seconds; capture's clock minus the log's) that the
    pairs of gap end and dispatch mark agree on, with its evidence; no
    key `offset_s` where they agree on none."""
    if not gap_events or not log["phases"]:
        return {"votes": 0, "why": "no gap or no phase to centre on"}
    centres = [gap_events[0][1] - log["phases"][0][1]]
    if trace_t0 is not None:
        centres += [c for c in (0.0, -float(trace_t0))
                    if abs(c - centres[0]) > PRIOR_S / 2]
    ends: Dict[str, List[float]] = {}
    for label, start, dur in gap_events:
        program = closing_program(label)
        if program in log["marks"] and dur >= TURNAROUND_S:
            ends.setdefault(program, []).append(start + dur)
    step_ends = np.asarray([start for label, start, _ in gap_events
                            if label[len("after:"):].partition("/")[0]
                            in STEPS])
    waits = np.asarray([b for name, _, b in log["phases"]
                        if name == "device_wait"])
    best, pairs = None, 0
    for centre in centres:
        by_program = {}
        for program, es in ends.items():
            marks = np.asarray(log["marks"][program]) + centre
            es = np.asarray(es)
            lo = np.searchsorted(marks, es - PRIOR_S, side="left")
            hi = np.searchsorted(marks, es + PRIOR_S, side="right")
            diffs = np.concatenate([e - marks[a:b]
                                    for e, a, b in zip(es, lo, hi)])
            by_program[program] = diffs[np.abs(diffs) < PRIOR_S]
        diffs = np.concatenate(list(by_program.values())) \
            if by_program else np.zeros(0)
        pairs = max(pairs, len(diffs))
        peak = _peak(diffs)
        if peak and (peak["votes"] < MIN_VOTES or peak["votes"]
                     < MIN_RATIO * max(1, peak["rival"])):
            backed = _backed(diffs, step_ends, waits + centre)
            again = _peak(backed)
            by_chance = len(backed) * (2 * PEAK_S + BIN_S) / (2 * PRIOR_S)
            if again and again["votes"] >= max(
                    BACKED_VOTES, CHANCE * by_chance,
                    BACKED_RATIO * max(1, again["rival"])):
                peak = {**again, "backed": len(backed)}
                by_program = {p: _backed(d, step_ends, waits + centre)
                              for p, d in by_program.items()}
        if peak and (best is None or "backed" in peak
                     or peak["votes"] > best["votes"]):
            best = {**peak, "centre": centre, "by_program": by_program}
    if best is None:
        return {"votes": 0, "pairs": pairs,
                "why": "no gap closed by a marked program"}
    ratio = best["votes"] / max(1, best["rival"])
    fit = {"votes": best["votes"], "rival_bin": best["rival"],
           "ratio": round(ratio, 2), "pairs": pairs,
           "voters": sum(len(es) for es in ends.values())}
    if "backed" in best:
        fit["backed_by"] = (f"device_wait: {best['backed']} pairs have "
                            f"{WAIT_SHARE:g} of the steps' ends within "
                            f"{READBACK_S * 1e3:g} ms below them")
    elif best["votes"] < MIN_VOTES or ratio < MIN_RATIO:
        why = (f"under {MIN_VOTES} votes or a ratio under {MIN_RATIO}, "
               f"and no peak the device_wait ends back")
        alone = _waits_alone(step_ends, waits + centres[0])
        if alone is None:
            return {**fit, "why": why}
        return {**fit, "weak": why + ": the high edge of the steps' ends "
                "against the device_wait ends alone, a readback under "
                "the offset and a turn's alias at worst",
                "wait_votes": alone["votes"],
                "offset_s": centres[0] - alone["edge"],
                "capture_clock": "its own"}
    edges = {}
    for program, diffs in best["by_program"].items():
        own = _peak(diffs[np.abs(diffs - best["edge"]) < SPREAD_S]
                    - best["edge"])
        if own and own["votes"] >= MIN_VOTES:
            edges[program] = own["edge"]
    least = min(0.0, min(edges.values(), default=0.0))
    offset = best["centre"] + best["edge"] + least
    above = sorted(float(d - best["edge"]) * 1e6 for d in best["inside"])
    q = statistics.quantiles(above, n=4) if len(above) > 1 else above * 3
    return {**fit, "offset_s": offset,
            "capture_clock": "epoch" if abs(offset) < 2 * PRIOR_S
            else "its own",
            "peak_quartiles_us": [round(x, 1) for x in q],
            "least_launch_over_the_offset_us": {
                p: round((e - least) * 1e6, 1) for p, e in edges.items()}}


def _moved(log: Dict[str, Any], offset: float) -> Dict[str, Any]:
    """The log on the capture's clock."""
    by_phase: Dict[str, List[tr.Interval]] = {}
    for name, a, b in log["phases"]:
        by_phase.setdefault(name, []).append((a + offset, b + offset))
    return {"by_phase": {p: tr.union(iv) for p, iv in by_phase.items()},
            "compiles": [(a + offset, b + offset)
                         for a, b in log["compiles"]],
            "marks": {p: [t + offset for t in ts]
                      for p, ts in log["marks"].items()}}


def _overlap(spans: List[tr.Interval], cover: List[tr.Interval]) -> float:
    return tr.total(spans) - tr.total(tr.subtract(spans, cover))


def split(gap_events: Sequence[Sequence], log: Dict[str, Any],
          offset: float) -> Tuple[Dict[str, Dict[str, float]], float]:
    """({gap label: {category: seconds}}, seconds in gaps an unmarked
    program closed).  A label's categories sum to its gaps' seconds."""
    moved = _moved(log, offset)
    parts: Dict[str, Dict[str, List[tr.Interval]]] = {}
    unmarked = 0.0
    for label, start, dur in gap_events:
        end = start + dur
        row = parts.setdefault(label, {"host": [], "launch": [],
                                       "queued": []})
        marks = moved["marks"].get(closing_program(label))
        i = bisect.bisect_right(marks, end + LATE_S) - 1 if marks else -1
        if marks is None:
            unmarked += dur
        if i < 0:
            row["host"].append((start, end))
        elif marks[i] <= start:
            row["queued"].append((start, end))
        else:
            mark = min(marks[i], end)
            row["host"].append((start, mark))
            row["launch"].append((mark, end))
    out = {}
    for label, row in parts.items():
        cats = dict.fromkeys(CATEGORIES, 0.0)
        for kind, spans in row.items():
            rest = tr.subtract(tr.union(spans), moved["compiles"])
            cats["compile"] += tr.total(spans) - tr.total(rest)
            if kind != "host":
                cats[kind] += tr.total(rest)
                continue
            seen = 0.0
            for phase, cover in moved["by_phase"].items():
                s = _overlap(rest, cover)
                cats[phase] = cats.get(phase, 0.0) + s
                seen += s
            cats["unseen"] += max(0.0, tr.total(rest) - seen)
        out[label] = cats
    return out, unmarked


def _rounded(row: Dict[str, float], drop_zeros: bool = False
             ) -> Dict[str, float]:
    return {k: round(v, 6) for k, v in row.items()
            if round(v, 6) or not drop_zeros}


def read(obs, phases):
    trace = obs.get("trace") or {}
    gaps = trace.get("gap_events")
    if not trace.get("devices") or not gaps:
        return None
    log = read_log(obs.get("spans") or [])
    if log is None:
        return None
    o.note(obs, "capture_log", log["self"])
    fit = fit_clock(gaps, log, obs.get("trace_t0"))
    o.note(obs, "clock_fit", fit)
    if "offset_s" not in fit:
        return None
    by_label, unmarked = split(gaps, log, fit["offset_s"])
    whole = dict.fromkeys(CATEGORIES, 0.0)
    for row in by_label.values():
        for k, v in row.items():
            whole[k] = whole.get(k, 0.0) + v
    o.note(obs, "idle_by_phase_s", _rounded(whole))
    o.note(obs, "idle_unmarked_s", round(unmarked, 6))
    top = [label for label, _ in (trace.get("breakdown") or {}).get(
        "idle_gaps") or [] if label in by_label]
    o.note(obs, "idle_gaps_by_phase", {
        label: _rounded(by_label[label], drop_zeros=True)
        for label in top})
    return 100 * sum(whole[p] for p in phases) / trace["window_s"]
