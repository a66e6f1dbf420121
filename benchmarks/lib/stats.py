"""Metric arithmetic kept with the benchmark (bench.py's `_pct` is the
original of `pct`; listed in PERF.md for deletion there)."""

from __future__ import annotations

import statistics
from typing import List, Optional, Sequence, Tuple


def pct(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile on the sorted sample; None when empty."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


def subwindow_pct(events: Sequence[Tuple[float, float]], t0: float,
                  t1: float, q: float, parts: int
                  ) -> Tuple[Optional[float], List[dict]]:
    """`events` are (time, value).  The window [t0, t1) is cut into
    `parts` equal sub-windows; each yields the q-percentile of the values
    whose time falls in it; the result is the MEDIAN of those readings
    (sub-windows without events are left out).  Returns (median,
    [{"n", "value"} per sub-window])."""
    width = (t1 - t0) / parts
    buckets: List[List[float]] = [[] for _ in range(parts)]
    for t, v in events:
        if t0 <= t < t1:
            buckets[min(parts - 1, int((t - t0) / width))].append(v)
    readings = [{"n": len(b), "value": pct(b, q)} for b in buckets]
    values = [r["value"] for r in readings if r["value"] is not None]
    return (statistics.median(values) if values else None), readings


def iqr_share(xs: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median — the spread the bounds are set from."""
    if len(xs) < 2:
        return None
    q1, _, q3 = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q3 - q1) / med if med else None
