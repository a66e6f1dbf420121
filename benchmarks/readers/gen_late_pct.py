"""How late the load generator sent (sent - due), a percentile in ms
over the requests due inside the window of an open loop."""

from benchmarks.lib import obs as o
from benchmarks.lib import stats


def read(obs, q):
    late = [(r.sent - o.due_time(obs, r)) * 1e3 for r in obs["requests"]
            if r.sent is not None and r.due is not None
            and o.in_window(obs, o.due_time(obs, r))]
    return stats.pct(late, q)
