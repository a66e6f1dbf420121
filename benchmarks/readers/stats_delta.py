"""What the engine's own cumulative counters gained over the whole
measured window: `scale` x (the sum of `stats1[k] - stats0[k]` over the
keys of `num`) / (the same over `den`; 1 where `den` is empty).
`stats0` / `stats1` are `LLMServer.stats()` at the window's two ends,
taken in every run.  None when the program lacks a key (a parent commit
from before the counter), or nothing in the denominator moved."""


def _gain(obs, keys):
    s0, s1 = obs.get("stats0"), obs.get("stats1")
    if not s0 or not s1 or any(k not in s0 or k not in s1 for k in keys):
        return None
    return sum(s1[k] - s0[k] for k in keys)


def read(obs, num, den, scale):
    top = _gain(obs, num)
    bottom = _gain(obs, den) if den else 1
    if top is None or not bottom:
        return None
    return scale * top / bottom
