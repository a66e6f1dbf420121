"""Device-idle time (ms, summed over the traced window) that follows a
run of `program` — with `paged_read_pages`, the blocking device-to-host
copy of the KV tier sweep and what the host does with the pages before
the next program starts — plus the program's own time."""


def read(obs, program):
    trace = obs.get("trace") or {}
    if not trace.get("devices"):
        return None
    own = sum(trace["programs"].get("jit_" + program, []))
    idle = sum(g[2] for g in trace["gap_events"]
               if g[0].startswith(f"after:{program}/"))
    return (own + idle) * 1e3
