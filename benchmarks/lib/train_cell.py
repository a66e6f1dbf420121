"""One run of a training cell: `JaxTrainer` with one worker that holds
every chip the cell asks for, the mesh from `ScalingConfig`, the
program's own train step as the configuration's architecture hands it
over (`make_train_step`, `param_specs`, `batch_axes`), weights and
batches from the seed on the device.  The loop below runs inside that
worker; the driver stays off jax.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from benchmarks.lib.registry import arch_of


def train_loop(cfg: Dict[str, Any]) -> None:
    import shutil
    import tempfile

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.lib import model, probes, trace_reduce
    from ray_tpu._private.jax_utils import device_facts
    from ray_tpu.air import session

    facts = device_facts()
    if facts["platform"] != cfg["platform"]:
        raise RuntimeError(f"this worker computes on {facts['platform']!r}"
                           f", not {cfg['platform']!r}")
    job, seconds = cfg["job"], cfg["seconds"]
    mesh = session.get_mesh()
    t_init = time.time()
    arch = arch_of(cfg["config"], cfg["bench_dir"])
    state, opt, mcfg = model.train_state(arch, cfg["config"], job,
                                         cfg["seed"], mesh)
    step = arch.make_train_step(mcfg, mesh, opt)
    key = model.seed_key(cfg["seed"] + 1)
    B, T = job["batch"], job["seq"]
    make_batch = jax.jit(
        lambda i: jax.random.randint(jax.random.fold_in(key, i),
                                     (B, T + 1), 0, mcfg.vocab_size),
        out_shardings=NamedSharding(mesh, P(arch.batch_axes(), None)))

    def one(i):
        nonlocal state
        t0 = time.time()
        state, m = step(state, make_batch(i))
        loss = float(jax.block_until_ready(m["loss"]))
        return t0, time.time(), loss

    warm = [one(i) for i in range(job["warmup_steps"])]
    cache0 = probes.cache_entries()
    t_w = time.time()
    steps, i = [], job["warmup_steps"]
    trace_summary, trace_dir, traced = None, None, 0
    trace_at = t_w + 0.4 * seconds if cfg["trace"] else None
    trace_t0 = 0.0

    def finish_trace():
        window_s = time.time() - trace_t0
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(trace_dir)
        if path and cfg.get("keep_trace"):
            shutil.copy(path, cfg["keep_trace"])
        summary = trace_reduce.reduce(trace_reduce.load(path),
                                      window_s=window_s) \
            if path else {"devices": 0}
        summary["steps"] = traced
        summary["gap_events"] = sorted(summary.get("gap_events", []),
                                       key=lambda g: -g[2])[:200]
        shutil.rmtree(trace_dir, ignore_errors=True)
        return summary

    while True:
        if trace_at is not None and trace_dir is None \
                and time.time() >= trace_at:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            probes.start_trace(trace_dir)
            trace_t0 = time.time()
        t0, t1, loss = one(i)
        i += 1
        if t1 > t_w + seconds:
            break
        steps.append([t0, t1, loss])
        if trace_dir is not None and trace_summary is None:
            traced += 1
            if traced >= job["trace_steps"]:
                trace_summary = finish_trace()
    if trace_dir is not None and trace_summary is None:
        trace_summary = finish_trace()
    cache1 = probes.cache_entries()
    session.report({
        "t_init": t_init, "t_w": t_w, "warm": warm, "steps": steps,
        "cache0": cache0, "cache1": cache1, "trace": trace_summary,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "params": sum(int(x.size) for x in
                      jax.tree_util.tree_leaves(state["params"])),
        "device": device_facts()})


def run(reg, cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        platform: str, t_proc0: float, log, init_kwargs=None,
        keep_trace: Optional[str] = None) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    config = reg.config(cell["config"])
    job = reg.traffic(cell["traffic"])
    arch = arch_of(config, reg.dir)
    lacks = [n for n in ("param_specs", "make_train_step", "batch_axes")
             if not hasattr(arch, n)]
    if lacks:
        raise RuntimeError(
            f"cell {cell['name']!r} trains, but the architecture of "
            f"configuration {cell['config']!r} ({arch.__file__}) only "
            f"serves: it has no {', '.join(lacks)}")
    ray_tpu.init(**(init_kwargs or {}))
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if platform == "tpu" and chips < cell["chips"]:
            raise RuntimeError(f"{chips} TPU chip(s) on this host, "
                               f"{cell['chips']} needed")
        scaling = dict(job["mesh"])
        if platform == "tpu":
            scaling["resources_per_worker"] = {"TPU": cell["chips"]}
        log(f"fitting over {job['mesh']}")
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"config": config, "job": job, "seed": seed,
                               "seconds": seconds, "trace": trace,
                               "platform": platform,
                               "keep_trace": keep_trace,
                               "bench_dir": reg.dir},
            scaling_config=ScalingConfig(num_workers=1, **scaling))
        report = trainer.fit().metrics
    finally:
        ray_tpu.shutdown()
    if report["device"]["count"] < cell["chips"] and platform == "tpu":
        raise RuntimeError(f"the worker saw {report['device']['count']} "
                           f"devices, {cell['chips']} needed")
    return {"cell": cell, "config": config, "traffic": job, "seed": seed,
            "seconds": seconds, "t_proc0": t_proc0, "t_w": report["t_w"],
            "t_end": report["t_w"] + seconds, "train": report,
            "trace": report["trace"], "cache0": report["cache0"],
            "cache1": report["cache1"], "replica_info": report["device"]}
