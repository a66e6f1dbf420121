"""Device memory (GB, fullest chip) that holds something: live arrays
(`hbm_in_use`) less the pages of the KV pool that sat on the free list,
as a mean over the engine `stats()` sampled through the window.  The
pool is reserved whole at start-up, so `memory_peak_bytes` and
`hbm_in_use_gb.*` count pages no request ever touched; this does not."""

import statistics


def read(obs):
    used = [b for b in obs["replica_info"].get("bytes_in_use") or []
            if b is not None]
    samples = obs.get("samples") or []
    if not used or not samples:
        return None
    engine = obs["config"]["serving"]["engine"]
    pool = engine["kv_pages"] * engine["page_size"] \
        * obs["arch"].kv_bytes_per_token(obs["config"])
    free = statistics.fmean(s["kv_blocks_free"] / s["kv_blocks_total"]
                            for s in samples)
    return (max(used) - free * pool) / 1e9
