"""A level the engine reports, not a counter: `scale` x `stats1[key]`,
the value of `LLMServer.stats()` at the window's end (taken in every
run).  For what is resident whatever the traffic, as the bytes of state
per decode row are.  None when the program lacks the key (a parent
commit from before it)."""


def read(obs, key, scale):
    stats = obs.get("stats1") or {}
    if key not in stats:
        return None
    return scale * stats[key]
