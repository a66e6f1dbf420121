"""A percentile of the gaps between consecutive output tokens, as the
client saw them on the streaming handle.  With `parts`, the window is
cut into that many equal sub-windows, each yields the percentile of the
gaps ending in it, and the reading is the median of those."""

from benchmarks.lib import obs as o
from benchmarks.lib import stats


def read(obs, q, parts=None):
    gaps = o.gaps(obs)
    if parts is None:
        return stats.pct([g for _, g in gaps], q)
    return stats.subwindow_pct(gaps, obs["t_w"], obs["t_end"], q, parts)[0]
