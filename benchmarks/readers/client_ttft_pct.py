"""A percentile of (first token seen by the client - time the request
was due to be sent), over requests whose first token arrived in the
window."""

from benchmarks.lib import obs as o
from benchmarks.lib import stats


def read(obs, q):
    return stats.pct([(t - o.due_time(obs, r)) * 1e3
                      for r, t in o.first_tokens(obs)], q)
