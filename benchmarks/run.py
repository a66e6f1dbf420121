"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json in a new process: set up (deploy or
build, warm every shape), measure for --seconds, check the outputs
against the plain reference, tear down.  Standard output carries a few
JSON lines that diagnose noise and, LAST, the contract's object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
with --trace 1).  With --trace 0 the metrics are the cell's end-to-end
metrics, with --trace 1 its per-layer metrics.  Progress and worker
logs go to standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result: there is no option under which a CPU
passes.  (The tests rehearse the same code at toy size on the CPU
through `run_cell(..., platform="cpu")`.)
"""

from __future__ import annotations

import time

T_PROC0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import stats, traffic  # noqa: E402
from benchmarks.lib.registry import Registry, arch_of  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T_PROC0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _serve_summary(obs: dict) -> dict:
    from benchmarks.lib import obs as o
    reqs = obs["requests"]
    in_w = [r for r in reqs if r.sent is not None and o.in_window(obs, r.sent)]
    failed = [r for r in in_w if r.error]
    mix = obs["traffic"]
    s0, s1 = obs["stats0"], obs["stats1"]
    late = [(r.sent - o.due_time(obs, r)) * 1e3 for r in in_w
            if r.due is not None]
    gaps = o.gaps(obs)
    by_sub = {f"p{round(q * 100)}": stats.subwindow_pct(
        gaps, obs["t_w"], obs["t_end"], q, 5)[1] for q in (0.5, 0.9, 0.95)}
    gap_ms = [g for _, g in gaps]
    whole = {"n": len(gap_ms), "max": max(gap_ms) if gap_ms else None,
             **{f"p{round(q * 100)}": stats.pct(gap_ms, q)
                for q in (0.5, 0.9, 0.95, 0.96, 0.97, 0.98, 0.99)}}
    bad_tokens = [r.index for r in reqs if r.done and r.error]
    return {
        "attempted": len(in_w), "failed": len(failed),
        "failed_examples": [r.error for r in failed[:3]],
        "diagnostics": {
            "requests": {"sent_before_window": sum(
                1 for r in reqs if r.sent is not None
                and r.sent < obs["t_w"]),
                "sent_in_window": len(in_w),
                "completed_in_window": sum(
                    1 for r in reqs if r.done and r.token_times
                    and o.in_window(obs, r.token_times[-1])),
                "failed": len(failed)},
            "offered_in_window": {
                "prompt_tokens": sum(r.prompt_len for r in in_w),
                "output_tokens": sum(r.max_new for r in in_w)},
            "tokens_delivered_in_window": sum(
                1 for _ in o.tokens_between(obs, obs["t_w"], obs["t_end"])),
            "gaps_whole_window": whole,
            "gaps_by_subwindow": by_sub,
            "ttft_p50_by_subwindow": stats.subwindow_pct(
                [(t, (t - o.due_time(obs, r)) * 1e3)
                 for r, t in o.first_tokens(obs)],
                obs["t_w"], obs["t_end"], 0.5, 5)[1],
            "generator_late_ms": {"p50": stats.pct(late, 0.5),
                                  "p99": stats.pct(late, 0.99),
                                  "max": max(late) if late else None},
            "loop": mix["loop"],
            "rate_rps_used": (traffic.block_size(mix, obs["seconds"])
                              * mix["blocks_per_window"] / obs["seconds"]
                              if mix["loop"] == "open" else None),
            "knee_rps": mix.get("knee_rps"),
            "engine": {
                "queue_depth_at_open": s0["queue_depth"],
                "queue_depth_at_close": s1["queue_depth"],
                "active_slots_at_open": s0["active_slots"],
                "active_slots_at_close": s1["active_slots"],
                "tier_demotions_in_window":
                    s1["kv_demotions"] - s0["kv_demotions"],
                "kv_t1_pages": s1["kv_t1_pages"],
                "kv_t2_pages": s1["kv_t2_pages"],
                "prefix_hit_tokens_in_window":
                    s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"],
                "rejected": s1["requests_rejected"]},
            "window_opened_s_after_load": obs["t_w"] - obs["t_load"],
            "replica_start_s": obs["replica_start_s"],
            "compile_cache_entries": [obs["cache0"], obs["cache1"]],
            "check": obs["check"],
            # did the check run what was timed?  (a traced run says)
            "check_programs_not_in_trace": sorted(
                set(obs["check"]["programs"])
                - set(obs["trace"]["programs"])) if obs["trace"] else None},
        "correct": bool(obs["check"]["finite"]
                        and obs["check"]["max_abs_diff"]
                        <= obs["check"]["tolerance"]["max_abs_diff"]
                        and obs["check"]["mean_abs_diff"]
                        <= obs["check"]["tolerance"]["mean_abs_diff"]
                        and not bad_tokens),
    }


def _train_summary(obs: dict) -> dict:
    t = obs["train"]
    losses = [s[2] for s in t["warm"]] + [s[2] for s in t["steps"]]
    curve = obs["traffic"].get("loss_curve") or {}
    finite = all(math.isfinite(x) for x in losses)
    # every pinned step the run reached: [step, loss, pinned, tolerance]
    held = [[k, losses[k], v, tol] for k, v, tol in
            zip(curve.get("step", []), curve.get("value", []),
                curve.get("tolerance", [])) if k < len(losses)]
    on_curve = all(abs(x - v) <= tol for _, x, v, tol in held)
    return {
        "attempted": len(t["steps"]), "failed": 0,
        "diagnostics": {
            "steps_in_window": len(t["steps"]),
            "warmup_step_s": [s[1] - s[0] for s in t["warm"]],
            "step_s": [s[1] - s[0] for s in t["steps"]][:64],
            "losses": losses[:64], "loss_curve_held": held,
            "mesh": t["mesh"], "params": t["params"],
            "state_built_s": t["warm"][0][0] - t["t_init"],
            "compile_cache_entries": [t["cache0"], t["cache1"]]},
        "correct": bool(finite and on_curve and t["steps"]),
    }


def run_cell(reg: Registry, workload: str, seed: int, seconds: float,
             trace: bool, platform: str = "tpu", init_kwargs=None,
             keep_trace=None, emit=None):
    """One run; returns the last line's object.  `emit` receives the
    earlier (diagnostic) lines."""
    cell = reg.cell(workload)
    # a configuration whose architecture has no file fails here, by name
    arch = arch_of(reg.config(cell["config"]), reg.dir)
    kind = reg.traffic(cell["traffic"])["kind"]
    if kind == "serve":
        from benchmarks.lib import serve_cell as driver
        summarise = _serve_summary
    elif kind == "train":
        from benchmarks.lib import train_cell as driver
        summarise = _train_summary
    else:
        raise ValueError(f"traffic kind {kind!r}")
    obs = driver.run(reg, cell, seed, seconds, trace, platform, T_PROC0, log,
                     init_kwargs=init_kwargs, keep_trace=keep_trace)
    summary = summarise(obs)
    obs["arch"] = arch                       # the readers' yardstick
    group = "per_layer" if trace else "end_to_end"
    metrics = reg.read_metrics(workload, group, obs)
    missing = [m["name"] for m in reg.metrics_for(workload, group)
               if m["name"] not in metrics]
    if not trace and missing:
        raise RuntimeError(f"end-to-end metrics not measured: {missing}")
    if emit:
        emit(json.dumps({"workload": workload, "seed": seed,
                         "seconds": seconds, "trace": int(trace),
                         "notes": obs.get("notes"),
                         "per_layer_not_read": missing if trace else [],
                         **summary["diagnostics"]}, default=str))
    info = obs["replica_info"]
    peaks = [b for b in info.get("peak_bytes_in_use") or [] if b is not None]
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"],
              "memory_peak_bytes": max(peaks) if peaks else None}
    out = {"correct": summary["correct"], "attempted": summary["attempted"],
           "failed": summary["failed"], "metrics": metrics, "device": device}
    if trace:
        tr = obs.get("trace") or {}
        if not tr.get("devices") or not tr.get("busy_s"):
            raise RuntimeError("the traced window holds no device "
                               "operation")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = tr["breakdown"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    p.add_argument("--keep-trace", default=None, help=argparse.SUPPRESS)
    p.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                   help=argparse.SUPPRESS)   # traffic override, sweeps only
    args = p.parse_args(argv)

    # From here on fd 1 — this process's and every child's — is stderr;
    # the real standard output is reachable through `emit` alone, so
    # nothing can write after the last line.
    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)

    def emit(line: str) -> None:
        os.write(real_stdout, (line + "\n").encode())

    overrides = {k: json.loads(v) for k, v in
                 (item.split("=", 1) for item in args.set)}
    if overrides:
        log(f"traffic overridden for a sweep, not a judged run: {overrides}")
    out = run_cell(Registry(args.root, overrides), args.workload, args.seed,
                   args.seconds, bool(args.trace), keep_trace=args.keep_trace,
                   emit=emit)
    emit(json.dumps(out))
    os.close(real_stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
