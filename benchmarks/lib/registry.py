"""Where the benchmark's data lives, found by the names in BENCHMARK.json.

A cell (`workloads` entry) names a configuration and a traffic mix or
job; each is a file of its own under the benchmark's first `paths`
directory, and so is every metric:

    <root>/BENCHMARK.json
    <root>/<paths[0]>/configs/<config>.json     model sizes + deployment
    <root>/<paths[0]>/traffic/<traffic>.json    mix or training job
    <root>/<paths[0]>/metrics/<metric>.json     {"reader", "args"} only
    <root>/<paths[0]>/readers/<reader>.py       read(obs, **args) -> float

BENCHMARK.json alone owns a metric's unit, layer, `moves` and cells.  A
tagged name (`device_idle_share.chat`) with no file of its own is read
by its base name's file (`device_idle_share.json`), so a new cell that
reports a quantity already defined adds entries and no metric file.

A later PR adds a cell, a configuration, a mix or a metric by adding
files and entries; nothing here is edited for it.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ROOT = os.path.dirname(HERE)


class Registry:
    def __init__(self, root: str = DEFAULT_ROOT,
                 overrides: Optional[Dict[str, Any]] = None):
        # `overrides` ({"rate_rps": 1.2}) replace keys of every traffic
        # file read: for a sweep made by hand (tools/measure.py), never
        # for a judged run.
        self.overrides = overrides or {}
        self._readers: Dict[str, Any] = {}
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.dir = os.path.join(self.root, self.spec["paths"][0])

    # -- entries of BENCHMARK.json -------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {[w['name'] for w in self.spec['workloads']]})")

    def metrics_for(self, cell: str, group: str) -> List[Dict[str, Any]]:
        """Entries of `end_to_end` or `per_layer` that this cell
        reports: those with no `workloads` key, or listing the cell."""
        return [m for m in self.spec[group]
                if "workloads" not in m or cell in m["workloads"]]

    # -- files found by name -------------------------------------------
    def _json(self, kind: str, name: str) -> Dict[str, Any]:
        with open(os.path.join(self.dir, kind, name + ".json")) as f:
            return json.load(f)

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return {**self._json("traffic", name), **self.overrides}

    def metric(self, name: str) -> Dict[str, Any]:
        """{"reader", "args"} of metrics/<name>.json or, for a tagged
        name without a file, of metrics/<name less its last .tag>.json."""
        own = os.path.join(self.dir, "metrics", name + ".json")
        if not os.path.exists(own) and "." in name:
            name = name.rsplit(".", 1)[0]
        return self._json("metrics", name)

    def reader(self, name: str):
        """The `read` function of readers/<name>.py; the benchmark's own
        readers directory is searched after the registry's, so a cell
        registered elsewhere (a test's temporary directory) can reuse
        them."""
        if name in self._readers:
            return self._readers[name]
        for base in (self.dir, HERE):
            path = os.path.join(base, "readers", name + ".py")
            if os.path.exists(path):
                spec = importlib.util.spec_from_file_location(
                    f"benchmarks_reader_{name}", path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                self._readers[name] = mod.read
                return mod.read
        raise KeyError(f"no reader {name!r}")

    def read_metrics(self, cell: str, group: str, obs: Dict[str, Any]
                     ) -> Dict[str, Dict[str, Any]]:
        """{name: {"value", "unit"}} for every metric of `group` the cell
        reports.  A reader that finds nothing to read returns None and
        its metric is left out of the line."""
        out = {}
        for m in self.metrics_for(cell, group):
            spec = self.metric(m["name"])
            value = self.reader(spec["reader"])(obs, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
