"""One run of a serving cell: deploy the configuration behind
`ray_tpu.serve`, offer the mix's load through the streaming handle from
this one process (an asyncio loop; the handle's router loop is the only
other thread), measure a window, check the engine against the
reference, tear down.

The driver (this process) never starts a jax backend: the chip belongs
to the replica.  Everything device-side comes back from the replica's
probes (lib/replica.py).
"""

from __future__ import annotations

import asyncio
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from benchmarks.lib import traffic as traffic_mod
from benchmarks.lib import trace_reduce

START_TIMEOUT_S = 1100.0    # a cold replica compiles for minutes
STATS_PERIOD_S = 0.5


class Request:
    __slots__ = ("index", "due", "sent", "prompt_len", "max_new",
                 "token_times", "n_tokens", "error", "done")

    def __init__(self, spec: Dict[str, Any]):
        self.index = spec["index"]
        self.due = spec["due"]          # seconds from the start of load
        self.prompt_len = spec["prompt_len"]
        self.max_new = spec["max_new"]
        self.sent: Optional[float] = None
        self.token_times: List[float] = []
        self.n_tokens = 0
        self.error: Optional[str] = None
        self.done = False


def deploy(config: Dict[str, Any], seed: int, platform: str, log,
           bench_dir: str):
    from benchmarks.lib.model import serving_loader
    from benchmarks.lib.replica import bench_deployment
    from ray_tpu import serve

    serve.start()
    options = {"num_tpus": 1} if platform == "tpu" else {"num_cpus": 1}
    dep = bench_deployment(serving_loader(config, seed, platform,
                                          bench_dir),
                           engine_config=dict(config["serving"]["engine"]),
                           ray_actor_options=options)
    t0 = time.time()
    handle = dep.deploy(_blocking=False)
    while True:
        status = {s["name"]: s["status"] for s in serve.status()}.get("llm")
        if status == "HEALTHY":
            break
        if status == "DEPLOY_FAILED" or time.time() - t0 > START_TIMEOUT_S:
            raise RuntimeError(f"the replica did not start: {status} "
                               f"(its errors are in the log above)")
        time.sleep(0.25)
    start_s = time.time() - t0
    log(f"replica healthy after {start_s:.1f}s")
    return handle, start_s


async def _load(handle, mix, requests: List[Request], seed: int,
                vocab: int, seconds: float, trace: bool, log) -> Dict:
    """Offer the load, open the window, close it, cancel what is still
    in flight.  Returns the window and what was sampled inside it."""
    stream_h = handle.options("stream")
    stats_h = handle.stats
    probe = {name: getattr(handle, name) for name in (
        "probe_cache_entries", "probe_trace_start", "probe_trace_stop")}
    open_loop = mix["loop"] == "open"
    t_load = time.time()
    first_tokens = 0
    window_open = asyncio.Event()
    state: Dict[str, Any] = {"t_load": t_load, "stop": False}

    async def one(req: Request):
        nonlocal first_tokens
        if open_loop:
            delay = t_load + req.due - time.time()
            if delay > 0:
                await asyncio.sleep(delay)
        if state["stop"]:
            return
        prompt = traffic_mod.prompt_tokens(seed, req.index, req.prompt_len,
                                           vocab)
        req.sent = time.time()
        stream = stream_h.stream(prompt, max_new_tokens=req.max_new)
        try:
            async for _ in stream:
                req.token_times.append(time.time())
                if len(req.token_times) == 1:
                    first_tokens += 1
                    if not open_loop and first_tokens >= \
                            mix["warmup_first_tokens"]:
                        window_open.set()
            req.n_tokens = len(req.token_times)
            req.done = True
            if req.n_tokens != req.max_new:
                req.error = f"{req.n_tokens} tokens of {req.max_new}"
        except asyncio.CancelledError:
            req.n_tokens = len(req.token_times)
            await stream.aclose()
            raise
        except Exception as e:           # refused, failed, timed out
            req.n_tokens = len(req.token_times)
            req.error = repr(e)

    async def client(queue):
        while not state["stop"]:
            try:
                req = next(queue)
            except StopIteration:
                return
            await one(req)

    if open_loop:
        tasks = [asyncio.ensure_future(one(r)) for r in requests]
    else:
        queue = iter(requests)
        tasks = [asyncio.ensure_future(client(queue))
                 for _ in range(mix["clients"])]

    # -- the window opens ---------------------------------------------
    if open_loop:
        block_s = seconds / mix["blocks_per_window"]
        await asyncio.sleep(max(0.0, t_load + mix["warmup_blocks"]
                                * block_s - time.time()))
    else:
        await window_open.wait()
    cache0 = await probe["probe_cache_entries"].remote()
    stats0 = await stats_h.remote()
    t_w = time.time()
    state.update(t_w=t_w, cache0=cache0, stats0=stats0)
    log(f"window opens {t_w - t_load:.1f}s after the load began")

    samples: List[Dict] = []
    trace_dir = None
    if trace:
        trace_len = min(float(mix.get("trace_seconds", 6.0)), seconds / 2)
        trace_at = t_w + 0.4 * seconds
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")

    async def sampler():
        while True:
            samples.append(await stats_h.remote())
            await asyncio.sleep(STATS_PERIOD_S)

    async def tracer():
        await asyncio.sleep(max(0.0, trace_at - time.time()))
        await probe["probe_trace_start"].remote(trace_dir)
        t0 = time.time()
        await asyncio.sleep(trace_len)
        t1 = time.time()
        await probe["probe_trace_stop"].remote()
        state.update(trace_t0=t0, trace_t1=t1,
                     trace_stop_s=time.time() - t1)

    side = [asyncio.ensure_future(sampler()),
            asyncio.ensure_future(tracer())] if trace else []
    await asyncio.sleep(max(0.0, t_w + seconds - time.time()))
    t_end = time.time()
    state["stop"] = True
    stats1 = await stats_h.remote()
    cache1 = await probe["probe_cache_entries"].remote()
    if side:
        side[0].cancel()
        await asyncio.gather(side[1], return_exceptions=True)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, *side[:1], return_exceptions=True)
    state.update(t_end=t_w + seconds, t_closed=t_end, stats1=stats1,
                 cache1=cache1, samples=samples, trace_dir=trace_dir)
    return state


def run(reg, cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        platform: str, t_proc0: float, log, init_kwargs=None,
        keep_trace: Optional[str] = None) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve

    config = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    vocab = config["vocab_size"]
    # What the replica spills (the KV store tier) goes to a directory of
    # this run's own, removed at the end: runs share nothing, and a
    # check of many runs does not fill the machine.
    scratch = tempfile.mkdtemp(prefix="bench-run-")
    leftovers: List[str] = [scratch]
    replica_pid = None
    ray_tpu.init(_system_config={"serve_kv_store_dir":
                                 os.path.join(scratch, "kv_store")},
                 **(init_kwargs or {}))
    obs: Dict[str, Any] = {"cell": cell, "config": config, "traffic": mix,
                           "seed": seed, "seconds": seconds, "trace": None,
                           "t_proc0": t_proc0}
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if platform == "tpu" and chips < cell["chips"]:
            raise RuntimeError(f"{chips} TPU chip(s) on this host, "
                               f"{cell['chips']} needed")
        handle, start_s = deploy(config, seed, platform, log, reg.dir)
        info = handle.replica_info.remote().result(timeout=START_TIMEOUT_S)
        if info["platform"] != platform:
            raise RuntimeError(f"the replica computes on "
                               f"{info['platform']!r}, not {platform!r}")
        obs["replica_start_s"] = start_s
        replica_pid = info["pid"]

        if mix["loop"] == "open":
            n_blocks = mix["warmup_blocks"] + mix["blocks_per_window"]
        else:
            n_blocks = int(mix.get("blocks", 64))
        requests = [Request(s) for s in traffic_mod.schedule(
            mix, seed, seconds, n_blocks)]
        state = asyncio.run(_load(handle, mix, requests, seed, vocab,
                                  seconds, trace, log))
        obs.update(state, requests=requests)
        obs["replica_info"] = handle.replica_info.remote().result(
            timeout=60)
        if trace:
            obs["spans"] = handle.trace_spans.remote().result(timeout=60)
            path = trace_reduce.find_xplane(state["trace_dir"])
            if path:
                if keep_trace:
                    shutil.copy(path, keep_trace)
                obs["trace"] = trace_reduce.reduce(
                    trace_reduce.load(path),
                    window_s=state["trace_t1"] - state["trace_t0"])
            shutil.rmtree(state["trace_dir"], ignore_errors=True)

        # -- correctness, outside the window ---------------------------
        deadline = time.time() + 60
        while time.time() < deadline:
            s = handle.stats.remote().result(timeout=60)
            if s["active_slots"] == 0 and s["queue_depth"] == 0:
                break
            time.sleep(0.2)
        check = config["serving"]["check"]
        obs["check"] = handle.probe_check_logits.remote(
            seed, check["prompt_len"], check["decode_tokens"], config,
            reg.dir).result(timeout=900)
        obs["check"]["tolerance"] = check["tolerance"]
        leftovers += handle.probe_host_files.remote().result(timeout=60)
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
            _wait_gone(replica_pid)
            for path in leftovers:
                if os.path.isdir(path):
                    shutil.rmtree(path, ignore_errors=True)
                elif os.path.exists(path):
                    os.unlink(path)
    return obs


def _wait_gone(pid: Optional[int], timeout_s: float = 60.0) -> None:
    """Return when the process has ended (a zombie has: it holds nothing
    and only waits for its parent to collect it)."""
    t0 = time.time()
    while pid is not None and time.time() - t0 < timeout_s:
        try:
            with open(f"/proc/{pid}/stat") as f:
                if f.read().rsplit(")", 1)[1].split()[0] in ("Z", "X"):
                    return
        except OSError:
            return
        time.sleep(0.05)
