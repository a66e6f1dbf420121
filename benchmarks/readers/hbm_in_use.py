"""Live device memory (GB, fullest chip) after the window:
`memory_stats()["bytes_in_use"]` — arrays the process holds (weights,
page pool or optimizer state), NOT a program's temporaries, which the
allocator's counters leave out."""


def read(obs):
    used = [b for b in obs["replica_info"].get("bytes_in_use") or []
            if b is not None]
    return max(used) / 1e9 if used else None
