"""Share (%) of the traced window with no operation on the device:
1 - union of busy intervals / window, averaged over the chips."""


def read(obs):
    trace = obs.get("trace") or {}
    if not trace.get("devices"):
        return None
    return 100 * (1 - trace["busy_s"] / trace["window_s"])
