"""Output tokens delivered to clients inside the window, per second of
window — counted token by token as they stream."""

from benchmarks.lib import obs as o


def read(obs):
    n = sum(1 for _ in o.tokens_between(obs, obs["t_w"], obs["t_end"]))
    return n / (obs["t_end"] - obs["t_w"]) if n else None
