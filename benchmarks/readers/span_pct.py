"""A percentile of the durations (ms) of one of the program's spans,
over the spans that ended inside the window."""

from benchmarks.lib import obs as o
from benchmarks.lib import stats


def read(obs, name, q):
    durs = [s["dur"] / 1e3 for s in obs.get("spans") or []
            if s["name"] == name
            and o.in_window(obs, (s["ts"] + s["dur"]) / 1e6)]
    return stats.pct(durs, q)
