"""Share (%) of the traced window in which a collective ran on the
device and no compute did (lib/trace_reduce: the union of collective
operations minus the union of compute operations, containers such as
`while` and async copies left out; averaged over the chips)."""


def read(obs):
    trace = obs.get("trace") or {}
    if not trace.get("devices"):
        return None
    return 100 * trace["collective_exposed_s"] / trace["window_s"]
