"""User-defined metrics: Counter / Gauge / Histogram.

Reference: python/ray/util/metrics.py — Counter (:155), Histogram (:220),
Gauge (:295); C++ stats flow through the node agent to Prometheus
(SURVEY.md §5 metrics).  Here every process keeps a registry and pushes
snapshots into the GCS KV (ns="metrics"); the dashboard head renders the
Prometheus exposition text from those snapshots.
"""

from __future__ import annotations

import bisect
import threading
import time

from ray_tpu._private import locksan
from typing import Dict, List, Optional, Tuple

_REGISTRY_LOCK = locksan.make_lock("metrics._REGISTRY_LOCK")
_REGISTRY: Dict[str, "Metric"] = {}

DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10]


class Metric:
    _kind = "untyped"

    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Tuple[str, ...]] = None):
        if not name:
            raise ValueError("metric name required")
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}
        # label-values-tuple -> scalar (or bucket-counts for histograms)
        self._values: Dict[tuple, float] = {}
        self._lock = locksan.make_lock("Metric._lock")
        with _REGISTRY_LOCK:
            _REGISTRY[name] = self

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _label_values(self, tags: Optional[Dict[str, str]]) -> tuple:
        merged = {**self._default_tags, **(tags or {})}
        extra = set(merged) - set(self.tag_keys)
        if extra:
            raise ValueError(f"unknown tag keys {extra} for {self.name} "
                             f"(declared: {self.tag_keys})")
        return tuple(merged.get(k, "") for k in self.tag_keys)

    def snapshot(self) -> dict:
        with self._lock:
            return {"name": self.name, "kind": self._kind,
                    "description": self.description,
                    "tag_keys": self.tag_keys,
                    "values": dict(self._values),
                    "ts": time.time()}

    def series(self, tags: Optional[Dict[str, str]] = None) -> "_Series":
        """Pre-resolved handle for ONE label combination: set()/inc()
        without the per-call tag merge/validation (hot paths — e.g. the
        serve router updates its gauges on every request).  The handle
        registers the series eagerly so it appears in snapshots even
        before the first write."""
        key = self._label_values(tags)
        with self._lock:
            self._values.setdefault(key, 0.0)
        return _Series(self._values, key, self._lock)


class _Series:
    """Single-series view of a metric.  set() is one dict store on a
    pre-existing key — atomic under the GIL, so it takes no lock (the
    snapshot path copies the dict, which is likewise GIL-atomic).
    inc() is a read-modify-write and DOES take the metric's lock."""

    __slots__ = ("_values", "_key", "_lock")

    def __init__(self, values: Dict[tuple, float], key: tuple, lock):
        self._values = values
        self._key = key
        self._lock = lock

    def set(self, value: float):
        self._values[self._key] = float(value)

    def inc(self, value: float = 1.0):
        with self._lock:
            self._values[self._key] = \
                self._values.get(self._key, 0.0) + value


class Counter(Metric):
    _kind = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None):
        if value < 0:
            raise ValueError("counters only increase")
        key = self._label_values(tags)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value


class Gauge(Metric):
    _kind = "gauge"

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        key = self._label_values(tags)
        with self._lock:
            self._values[key] = float(value)


class Histogram(Metric):
    _kind = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Optional[List[float]] = None,
                 tag_keys: Optional[Tuple[str, ...]] = None):
        super().__init__(name, description, tag_keys)
        self.boundaries = list(boundaries or DEFAULT_HISTOGRAM_BOUNDARIES)

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None):
        self._observe(self._label_values(tags), (value,))

    def observe_many(self, values,
                     tags: Optional[Dict[str, str]] = None):
        """Record every value of `values` under one take of the lock:
        the same buckets, sum and count as observe() called for each
        in turn (a hot path that gathers a batch of observations, e.g.
        the LLM engine's inter-token gaps of one turn)."""
        self._observe(self._label_values(tags), values)

    def _observe(self, key: tuple, values):
        if not values:
            return
        with self._lock:
            entry = self._values.get(key)
            if not isinstance(entry, dict):
                entry = self._values[key] = {
                    "buckets": [0] * (len(self.boundaries) + 1),
                    "sum": 0.0, "count": 0}
            buckets, total = entry["buckets"], entry["sum"]
            for value in values:
                # the first boundary the value does not exceed
                # (boundaries ascend); past the last, the +Inf bucket
                buckets[bisect.bisect_left(self.boundaries, value)] += 1
                total += value
            entry["sum"] = total
            entry["count"] += len(values)

    def series(self, tags: Optional[Dict[str, str]] = None
               ) -> "_HistogramSeries":
        """Pre-resolved handle for ONE label combination (see
        Metric.series).  Unlike a scalar's, it registers nothing until
        the first observation: an empty histogram has no snapshot."""
        return _HistogramSeries(self, self._label_values(tags))

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["boundaries"] = self.boundaries
        return snap


class _HistogramSeries:
    """Single-series view of a Histogram: observations without the
    per-call tag merge/validation."""

    __slots__ = ("_hist", "_key")

    def __init__(self, hist: Histogram, key: tuple):
        self._hist = hist
        self._key = key

    def observe(self, value: float):
        self._hist._observe(self._key, (value,))

    def observe_many(self, values):
        self._hist._observe(self._key, values)


def registry_snapshot() -> List[dict]:
    with _REGISTRY_LOCK:
        metrics = list(_REGISTRY.values())
    return [m.snapshot() for m in metrics]


def prometheus_text(snapshots: List[dict]) -> str:
    """Render snapshots (possibly from many processes) as Prometheus
    exposition text (reference: _private/prometheus_exporter.py)."""
    by_name: Dict[str, List[dict]] = {}
    for s in snapshots:
        by_name.setdefault(s["name"], []).append(s)
    out: List[str] = []
    for name, snaps in sorted(by_name.items()):
        first = snaps[0]
        kind = first["kind"] if first["kind"] != "untyped" else "gauge"
        if first.get("description"):
            out.append(f"# HELP {name} {first['description']}")
        out.append(f"# TYPE {name} {kind}")
        for s in snaps:
            keys = s["tag_keys"]
            for label_vals, val in s["values"].items():
                labels = ",".join(
                    f'{k}="{v}"' for k, v in zip(keys, label_vals) if v)
                suffix = "{" + labels + "}" if labels else ""
                if isinstance(val, dict):  # histogram
                    cum = 0
                    for b, cnt in zip(s["boundaries"], val["buckets"]):
                        cum += cnt
                        lb = (labels + "," if labels else "") + f'le="{b}"'
                        out.append(f"{name}_bucket{{{lb}}} {cum}")
                    lb = (labels + "," if labels else "") + 'le="+Inf"'
                    out.append(f"{name}_bucket{{{lb}}} {val['count']}")
                    out.append(f"{name}_sum{suffix} {val['sum']}")
                    out.append(f"{name}_count{suffix} {val['count']}")
                else:
                    out.append(f"{name}{suffix} {val}")
    return "\n".join(out) + "\n"
