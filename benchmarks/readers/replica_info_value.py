"""One number of what the replica says of itself after the load
(`LLMServer.replica_info()`, in `obs["replica_info"]` in every run):
the float at `path`, key by key, as in `["start", "load"]` for the
seconds its start spent in `llm.load_model`.  None where a key is
absent (a parent commit from before the replica kept its start's
books) or the value is not a number yet."""


def read(obs, path):
    value = obs.get("replica_info")
    for key in path:
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    return float(value)
