"""Where the benchmark's data lives, found by the names in BENCHMARK.json.

A cell (`workloads` entry) names a configuration and a traffic mix or
job; each is a file of its own under the benchmark's first `paths`
directory, and so is every metric:

    <root>/BENCHMARK.json
    <root>/<paths[0]>/configs/<config>.json     model sizes + deployment
    <root>/<paths[0]>/traffic/<traffic>.json    mix or training job
    <root>/<paths[0]>/metrics/<metric>.json     {"reader", "args"} only
    <root>/<paths[0]>/readers/<reader>.py       read(obs, **args) -> float
    <root>/<paths[0]>/archs/<arch>.py           or a package archs/<arch>/

A configuration names its architecture (key `arch`): the module that
builds the program's model from the file's sizes, draws its weights,
holds its plain reference and counts the operations and bytes its calls
need (the default one's `__init__.py` lists what the harness asks
for).  The harness looks the name up and never compares it.

BENCHMARK.json alone owns a metric's unit, layer, `moves` and cells.  A
tagged name (`device_idle_share.chat`) with no file of its own is read
by its base name's file (`device_idle_share.json`), so a new cell that
reports a quantity already defined adds entries and no metric file.

A later PR adds a cell, a configuration, a mix, a metric or an
architecture by adding files and entries; nothing here is edited for it.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
import zlib
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ROOT = os.path.dirname(HERE)


def find_module(kind: str, name: str, dirs: Sequence[str]):
    """The module `<dir>/<kind>/<name>.py`, or the package
    `<dir>/<kind>/<name>/`, of the first of `dirs` that has one, loaded
    once a process under a name made from its path (two roots can each
    bring a `<name>`)."""
    for base in dirs:
        flat = os.path.join(base, kind, name + ".py")
        package = os.path.join(base, kind, name)
        for path, search in ((flat, None),
                             (os.path.join(package, "__init__.py"),
                              [package])):
            if not os.path.exists(path):
                continue
            modname = "benchmarks_%s_%s_%08x" % (
                kind, re.sub(r"\W", "_", name), zlib.crc32(path.encode()))
            if modname not in sys.modules:
                spec = importlib.util.spec_from_file_location(
                    modname, path, submodule_search_locations=search)
                mod = importlib.util.module_from_spec(spec)
                sys.modules[modname] = mod   # a package's `from . import`
                try:
                    spec.loader.exec_module(mod)
                except BaseException:
                    del sys.modules[modname]
                    raise
            return sys.modules[modname]
    raise KeyError(f"no {kind}/{name}.py and no {kind}/{name}/ under "
                   f"{list(dirs)}")


def arch_of(config: Dict[str, Any], bench_dir: str = HERE):
    """The architecture module a configuration names; searched like a
    reader, in `bench_dir` first and the benchmark's own after it.  A
    worker process calls this with the registry's `dir`, which is all
    it needs to find what the driver found."""
    return find_module("archs", config.get("arch", "llama"),
                       (bench_dir, HERE))


class Registry:
    def __init__(self, root: str = DEFAULT_ROOT,
                 overrides: Optional[Dict[str, Any]] = None):
        # `overrides` ({"rate_rps": 1.2}) replace keys of every traffic
        # file read: for a sweep made by hand (tools/measure.py), never
        # for a judged run.
        self.overrides = overrides or {}
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
        return find_module("readers", name, (self.dir, HERE)).read

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
