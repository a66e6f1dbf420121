"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                # one chip: serve, hand-over, train
    python chip_smoke.py --four-chips   # one four-chip host: sharded train
                                        # + four one-chip replicas, nothing else

Drives the two main paths once through the entry points a user calls —
``serve.llm.llm_deployment`` and ``train.jax.JaxTrainer`` — at the full
width of the repo's 737M GPT (12 layers, bf16, weights from a seed), and
checks what comes out: greedy tokens against a cache-free forward, a
finite falling loss, the platform each worker really computed on.

The driver (this process) never starts a jax backend: a chip belongs to
one process, and it has to go to the workers.  Every device fact printed
here was reported by the worker that held the chip.

Standard output carries one JSON object per phase and, last, exactly
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Progress and
worker logs go to standard error.  Any failure exits non-zero without
that line; there is no option under which a CPU passes.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import random
import sys
import time
import urllib.request

GPT_737M = dict(vocab_size=32000, d_model=2048, n_heads=16, n_layers=12,
                d_ff=8192, max_seq=1024, dtype="bfloat16")

# What one run asks of the model.  A test rehearses the same phases with
# a toy of these on the CPU (tests/test_chip_smoke.py).
SIZES = dict(
    model=GPT_737M, seed=0,
    # page pool: 8 rows x 1024 tokens
    engine=dict(num_slots=8, max_seq=1024, page_size=16, kv_pages=512),
    prompt_lens=(24, 48, 96, 200), max_new_tokens=32,
    # A generated token must be the reference's argmax or within this
    # many logits of it.  Seeded random weights give near-ties among
    # 32000 logits of spread ~0.9, and the engine's paged bf16 decode
    # rounds differently from the one-shot forward, so bare argmax
    # equality would flake; 2**-3 is far below the spread.
    logit_margin=0.125,
    train=dict(batch=8, seq=1024, steps=5),
    # Four chips: the same model sharded fsdp x tp against one device.
    # Losses near ln(32000) ~ 10.4 in bf16; the two runs reduce in
    # different orders (0.00074 apart at worst on the v5e, PR 22).
    mesh=dict(fsdp=2, tp=2), mesh_steps=3, loss_tolerance=0.01,
)

TIMEOUT_S = 600.0   # any one wait: a cold replica start is ~90 s of compiles


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# --------------------------------------------------------------------------
# Code that runs inside workers (the only places jax is used).

def gpt_config(model: dict, remat: bool):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    return gpt.GPTConfig(**{**model, "dtype": getattr(jnp, model["dtype"])},
                         remat=remat)


def load_model(model: dict, seed: int, serving: bool):
    """(params, cfg) of the configured GPT from a seed; a serving copy is
    cast to the compute dtype (what a replica keeps in HBM)."""
    import jax

    from ray_tpu.models import gpt

    cfg = gpt_config(model, remat=not serving)
    params = gpt.init_params(cfg, jax.random.PRNGKey(seed))
    if serving:
        params = jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype),
                                        params)
    return params, cfg


def reference_scores(model: dict, seed: int, prompt: list,
                     continuation: list, platform: str) -> dict:
    """Cache-free reference: one gpt.forward over prompt + continuation.
    Row i scores the position that produced continuation[i]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.jax_utils import device_facts
    from ray_tpu.models import gpt

    facts = _demand_platform(device_facts(), platform)
    params, cfg = load_model(model, seed, serving=True)
    tokens = jnp.asarray([list(prompt) + list(continuation)], jnp.int32)
    logits = jax.jit(lambda p, t: gpt.forward(p, t, cfg))(params, tokens)
    n, k = len(prompt), len(continuation)
    rows = np.asarray(logits[0, n - 1:n + k - 1], np.float32)
    return {"argmax": rows.argmax(-1).tolist(),
            "max": rows.max(-1).tolist(),
            "chosen": rows[np.arange(k), np.asarray(continuation)].tolist(),
            "device": facts}


def train_loop(config: dict) -> None:
    """JaxTrainer's per-worker loop: `steps` steps on one fixed batch over
    session.get_mesh(); with compare_single_device, the same steps again
    on one device (mesh=None) in this same worker."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu._private.jax_utils import device_facts
    from ray_tpu.air import session
    from ray_tpu.models import gpt

    _demand_platform(device_facts(), config["platform"])
    t = config["train"]
    cfg = gpt_config(config["model"], remat=True)
    key = jax.random.PRNGKey(config["seed"])
    tokens = jax.random.randint(key, (t["batch"], t["seq"] + 1), 0,
                                cfg.vocab_size)

    def run(mesh):
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
        state, _ = gpt.make_train_state(cfg, key, mesh=mesh, optimizer=opt)
        step = gpt.make_train_step(cfg, mesh=mesh, optimizer=opt,
                                   donate=True)
        losses, step_s = [], []
        for _ in range(t["steps"]):
            t0 = time.perf_counter()
            state, metrics = step(state, tokens)
            losses.append(float(jax.block_until_ready(metrics["loss"])))
            step_s.append(round(time.perf_counter() - t0, 3))
        n_params = sum(int(x.size) for x in
                       jax.tree_util.tree_leaves(state["params"]))
        # memory while the state is still alive: where the params are
        return {"losses": losses, "step_s": step_s, "params": n_params,
                "device": device_facts()}

    mesh = session.get_mesh()
    report = run(mesh)
    report["mesh"] = {k: int(v) for k, v in mesh.shape.items()}
    if config.get("compare_single_device"):
        report["single_device"] = run(None)
    session.report(report)


def _demand_platform(facts: dict, platform: str) -> dict:
    if facts["platform"] != platform:
        raise RuntimeError(f"this worker computes on {facts['platform']!r} "
                           f"({facts['kind']}), not {platform!r}")
    return facts


# --------------------------------------------------------------------------
# Phases (driver side).  Each takes the sizes and the platform to demand;
# each needs ray_tpu.init() done and raises on any failure.

def _tpu_options(platform: str) -> dict:
    return {"num_tpus": 1} if platform == "tpu" else {"num_cpus": 1}


def _prompts(sizes: dict) -> list:
    rng = random.Random(sizes["seed"])
    vocab = sizes["model"]["vocab_size"]
    return [[rng.randrange(vocab) for _ in range(n)]
            for n in sizes["prompt_lens"]]


def _check_tokens(tokens, sizes: dict) -> list:
    tokens = [int(t) for t in tokens]
    if len(tokens) != sizes["max_new_tokens"] or not all(
            0 <= t < sizes["model"]["vocab_size"] for t in tokens):
        raise RuntimeError(f"bad generation: {tokens}")
    return tokens


def _alive(pid: int) -> bool:
    """Still running?  A zombie is not: it has closed its files, the
    chip's among them, and only waits for its parent to collect it."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] not in ("Z", "X")
    except OSError:
        return False


def _wait_gone(pids, timeout_s: float = 60.0) -> float:
    """Seconds until every pid has exited (its chip goes with it)."""
    t0 = time.monotonic()
    while any(_alive(pid) for pid in pids):
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(f"processes {pids} still alive "
                               f"{timeout_s:.0f}s after shutdown")
        time.sleep(0.05)
    return round(time.monotonic() - t0, 2)


def _deploy(sizes: dict, platform: str, num_replicas: int = 1):
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    serve.start()
    deployment = llm_deployment(
        functools.partial(load_model, sizes["model"], sizes["seed"], True),
        num_replicas=num_replicas, engine_config=dict(sizes["engine"]),
        default_generation={"max_new_tokens": sizes["max_new_tokens"]},
        ray_actor_options=_tpu_options(platform))
    t0 = time.monotonic()
    handle = deployment.deploy(_blocking=False)
    while True:
        status = {s["name"]: s["status"] for s in serve.status()}.get("llm")
        if status == "HEALTHY":
            break
        if status == "DEPLOY_FAILED" \
                or time.monotonic() - t0 > TIMEOUT_S:
            raise RuntimeError(f"llm deployment did not start: {status} "
                               f"(replica errors are in the log above)")
        time.sleep(0.5)
    return handle, round(time.monotonic() - t0, 2)


def serve_phase(sizes: dict, platform: str) -> dict:
    """Serve through serve.llm: concurrent generate calls, a stream, an
    HTTP request; hand the chip over; check one continuation against
    the cache-free forward on the chip."""
    import ray_tpu
    from ray_tpu import serve

    log("serve: deploying")
    handle, start_s = _deploy(sizes, platform)
    info = _demand_platform(
        handle.replica_info.remote().result(timeout=TIMEOUT_S),
        platform)
    log(f"serve: replica pid {info['pid']} on {info['kind']} "
        f"x{info['count']}, up in {start_s}s")

    prompts = _prompts(sizes)
    t0 = time.monotonic()
    pending = [handle.generate.remote(p) for p in prompts]
    outs = [_check_tokens(r.result(timeout=TIMEOUT_S), sizes)
            for r in pending]
    generate_s = round(time.monotonic() - t0, 2)
    log(f"serve: {len(prompts)} concurrent generate calls in {generate_s}s "
        f"(first call waits for the engine's compiles)")

    t0 = time.monotonic()
    first_token_s, streamed = None, []
    for tok in handle.options("stream").stream(prompts[0][:16]):
        if first_token_s is None:
            first_token_s = round(time.monotonic() - t0, 3)
        streamed.append(tok)
    _check_tokens(streamed, sizes)

    serve.run(serve.get_deployment("llm"), _start_proxy=True)
    addr = serve.get_proxy_address()
    request = urllib.request.Request(
        f"http://{addr['host']}:{addr['port']}/llm",
        data=json.dumps({"tokens": prompts[1]}).encode(), method="POST",
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(request, timeout=TIMEOUT_S) as r:
        http_tokens = _check_tokens(json.loads(r.read())["tokens"], sizes)

    stats = handle.stats.remote().result(timeout=TIMEOUT_S)
    info = handle.replica_info.remote().result(timeout=TIMEOUT_S)

    log("hand-over: serve.shutdown()")
    serve.shutdown()
    handover_s = _wait_gone([info["pid"]])
    log(f"hand-over: replica gone after {handover_s}s")

    # The chip is free again, or this task's worker cannot open it.
    log("serve: reference forward on the chip")
    ref = ray_tpu.get(
        ray_tpu.remote(reference_scores).options(
            **_tpu_options(platform)).remote(
                sizes["model"], sizes["seed"], prompts[1], outs[1],
                platform),
        timeout=TIMEOUT_S)
    gaps = [m - c for m, c in zip(ref["max"], ref["chosen"])]
    exact = sum(int(a == t) for a, t in zip(ref["argmax"], outs[1]))
    result = {
        "phase": "serve", "model": sizes["model"],
        "engine": sizes["engine"], "device": info,
        "smoke_timings_s": {"replica_start": start_s,
                            "concurrent_generate": generate_s,
                            "stream_first_token": first_token_s,
                            "handover": handover_s},
        "requests_completed": stats["requests_completed"],
        "tokens_generated": stats["tokens_generated"],
        "http_matches_handle": http_tokens == outs[1],
        "token_check": {"prompt_len": len(prompts[1]),
                        "generated": len(outs[1]),
                        "argmax_matches": exact,
                        "worst_logit_gap": round(max(gaps), 5),
                        "margin": sizes["logit_margin"],
                        "reference_pid": ref["device"]["pid"]},
    }
    if max(gaps) > sizes["logit_margin"]:
        raise RuntimeError(f"token check failed: {result['token_check']}")
    if platform == "tpu":
        # A TPU worker dies with its lease (raylet._end_lease); a CPU
        # worker goes back to the pool.
        _wait_gone([ref["device"]["pid"]])
    return result


def _fit(sizes: dict, platform: str, scaling: dict, **loop_config) -> dict:
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"model": sizes["model"], "seed": sizes["seed"],
                           "platform": platform, **loop_config},
        scaling_config=ScalingConfig(num_workers=1, **scaling))
    return trainer.fit().metrics


def _check_finite(losses: list) -> None:
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise RuntimeError(f"loss is not finite: {losses}")


def train_phase(sizes: dict, platform: str) -> dict:
    """Train through JaxTrainer on one device: a few steps on one fixed
    batch, no batch fallback."""
    log("train: fitting")
    t0 = time.monotonic()
    m = _fit(sizes, platform, {"use_tpu": platform == "tpu"},
             train=sizes["train"])
    _check_finite(m["losses"])
    if not m["losses"][-1] < m["losses"][0]:
        raise RuntimeError(f"loss did not fall: {m['losses']}")
    _wait_gone([m["device"]["pid"]])
    return {"phase": "train", "model": sizes["model"],
            "train": sizes["train"], "params": m["params"],
            "mesh": m["mesh"], "losses": m["losses"], "device": m["device"],
            "smoke_timings_s": {"fit": round(time.monotonic() - t0, 2),
                                "first_step_with_compile": m["step_s"][0],
                                "later_steps": m["step_s"][1:]}}


def mesh_train_phase(sizes: dict, platform: str, chips: int = 4) -> dict:
    """One JaxTrainer worker holding every chip: the model sharded over a
    ScalingConfig mesh against the same steps on one device."""
    log(f"mesh train: fitting over {sizes['mesh']}")
    scaling = dict(sizes["mesh"])
    if platform == "tpu":
        scaling["resources_per_worker"] = {"TPU": chips}
    m = _fit(sizes, platform, scaling, compare_single_device=True,
             train={**sizes["train"], "steps": sizes["mesh_steps"]})
    single = m["single_device"]
    # Three steps are too few to ask for a fall (adamw's third step on a
    # fresh model overshoots, on one device as on four); they are enough
    # to ask that both runs take the same path.
    _check_finite(m["losses"] + single["losses"])
    diffs = [abs(a - b) for a, b in zip(m["losses"], single["losses"])]
    in_use = m["device"]["bytes_in_use"][:chips]
    result = {"phase": "mesh_train", "model": sizes["model"],
              "mesh": m["mesh"], "losses": m["losses"],
              "single_device_losses": single["losses"],
              "max_loss_diff": round(max(diffs), 5),
              "tolerance": sizes["loss_tolerance"],
              "bytes_in_use_per_device": in_use,
              "single_device_bytes_in_use": single["device"]["bytes_in_use"],
              "device": m["device"]}
    if max(diffs) > sizes["loss_tolerance"]:
        raise RuntimeError(f"sharded and single-device losses differ: "
                           f"{result}")
    if m["device"]["count"] < chips:
        raise RuntimeError(f"the worker saw {m['device']['count']} devices")
    # Sharded state is spread: no device holds what one device held alone.
    if None not in in_use and max(in_use) >= \
            0.6 * single["device"]["bytes_in_use"][0]:
        raise RuntimeError(f"parameters are not spread over devices: "
                           f"{result}")
    _wait_gone([m["device"]["pid"]])
    return result


def replicas_phase(sizes: dict, platform: str, n: int = 4) -> dict:
    """n one-chip replicas behind the router, each answering from its own
    device."""
    from ray_tpu import serve

    log(f"replicas: deploying {n}")
    handle, start_s = _deploy(sizes, platform, num_replicas=n)
    prompts = _prompts(sizes)
    seen: dict = {}
    for round_ in range(8):
        pending = [handle.generate.remote([round_] + p) for p in prompts * 2]
        for r in pending:
            _check_tokens(r.result(timeout=TIMEOUT_S), sizes)
        for r in [handle.replica_info.remote() for _ in range(4 * n)]:
            info = _demand_platform(r.result(timeout=TIMEOUT_S),
                                    platform)
            seen[info["pid"]] = info
        if len(seen) == n and all(i["completed"] for i in seen.values()):
            break
    else:
        raise RuntimeError(f"not every replica answered: {seen}")
    serve.shutdown()
    _wait_gone(list(seen))
    devices = sorted(tuple(i["device_files"]) or (i["pid"],)
                     for i in seen.values())
    if platform == "tpu" and (
            len(set(devices)) != n
            or any(i["count"] != 1 or len(i["device_files"]) != 1
                   for i in seen.values())):
        raise RuntimeError(f"replicas do not hold one chip each: {seen}")
    return {"phase": "replicas", "replicas": n,
            "smoke_timings_s": {"start": start_s},
            "per_replica": [{k: i[k] for k in (
                "pid", "platform", "kind", "count", "tpu_ids",
                "device_files", "completed", "peak_bytes_in_use")}
                for i in seen.values()]}


# --------------------------------------------------------------------------
# The script.

def last_line(platform: str, kind: str, count: int) -> str:
    """The contract's last line: these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}})


def driver_backend_initialized() -> bool:
    """Has this process started a jax backend (and so, on a TPU host,
    taken the chip)?  Importing jax does not; computing does."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def _cache_entries(path: str) -> int:
    try:
        return sum(not name.endswith("-atime") for name in os.listdir(path))
    except OSError:
        return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run the four-chip phases (and only those)")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    sizes = {**SIZES, "seed": args.seed}
    chips_needed = 4 if args.four_chips else 1

    # From here on this process's "stdout" — and every child's — is
    # stderr; the real one is reachable through `emit` alone, so nothing
    # (a logger, an atexit handler, a worker) can write after the last
    # line.
    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)

    def emit(line: str) -> None:
        os.write(real_stdout, (line + "\n").encode())

    import ray_tpu
    from ray_tpu._private.jax_utils import compile_cache_dir

    cache = compile_cache_dir()
    cache_before = _cache_entries(cache)
    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        emit(json.dumps({"phase": "detect", "tpu_chips_detected": chips,
                         "compile_cache_dir": cache,
                         "compile_cache_from_env":
                             "JAX_COMPILATION_CACHE_DIR" in os.environ,
                         "compile_cache_entries_before": cache_before}))
        if chips < chips_needed:
            # Asked of a node that advertises none, a TPU lease would
            # queue as autoscaler demand for ever.
            raise RuntimeError(f"{chips} TPU chip(s) detected on this "
                               f"host, {chips_needed} needed")
        phases = (mesh_train_phase, replicas_phase) if args.four_chips \
            else (serve_phase, train_phase)
        results = []
        for phase in phases:
            results.append(phase(sizes, "tpu"))
            emit(json.dumps(results[-1]))
        if driver_backend_initialized():
            raise RuntimeError("the driver started a jax backend")
    finally:
        ray_tpu.shutdown()
    emit(json.dumps({"phase": "cache", "compile_cache_dir": cache,
                     "compile_cache_entries_after": _cache_entries(cache)}))
    device = results[0]["device"]
    emit(last_line(device["platform"], device["kind"], device["count"]))
    os.close(real_stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
