"""Client-seen first-token time minus the engine's emit time, median in
ms.  The engine's emit time is the end of its `engine.first_tick` span;
spans carry the engine's own request ids, so each client first token is
paired with the latest emit that precedes it."""

import bisect

from benchmarks.lib import obs as o
from benchmarks.lib import stats


def read(obs):
    emits = sorted((s["ts"] + s["dur"]) / 1e6 for s in obs.get("spans") or []
                   if s["name"] == "engine.first_tick")
    if not emits:
        return None
    over = []
    for _, t in o.first_tokens(obs):
        i = bisect.bisect_right(emits, t) - 1
        if i >= 0:
            over.append((t - emits[i]) * 1e3)
    return stats.pct(over, 0.5)
