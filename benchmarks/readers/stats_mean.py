"""The mean, over the engine `stats()` sampled through the window, of a
share in percent: `occupancy` = active rows of the decode batch,
`kv_used` = pages of the pool not on the free list."""

import statistics


def read(obs, what):
    samples = obs.get("samples") or []
    if not samples:
        return None
    if what == "occupancy":
        vals = [s["active_slots"] / s["num_slots"] for s in samples]
    elif what == "kv_used":
        vals = [1 - s["kv_blocks_free"] / s["kv_blocks_total"]
                for s in samples]
    else:
        raise ValueError(what)
    return 100 * statistics.fmean(vals)
