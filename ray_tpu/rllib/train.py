"""`rllib train`-style CLI: run an algorithm from a declarative config.

Reference: rllib/train.py (+ rllib/tuned_examples/*.yaml, the
learning-regression configs CI replays).  A config file (JSON, or YAML
when pyyaml is present) names the algorithm, its config overrides, and
stop criteria:

    {"run": "PPO",
     "env": "CartPole-v1",
     "config": {"num_rollout_workers": 2, "lr": 3e-4},
     "stop": {"episode_reward_mean": 150, "training_iteration": 40}}

Usage:
    python -m ray_tpu.rllib.train -f rllib/tuned_examples/<name>.json
    python -m ray_tpu.rllib.train --run DQN --env CartPole-v1 \
        --stop-reward 100

Exit code 0 iff every stop criterion that names a metric bar was MET
(not merely timed out) — so a directory of tuned_examples doubles as a
learning-regression battery:

    for f in rllib/tuned_examples/*.json; do
        python -m ray_tpu.rllib.train -f "$f" || exit 1
    done
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict


def load_config(path: str) -> Dict:
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        try:
            import yaml
            return yaml.safe_load(text)
        except ImportError:
            raise ValueError(
                f"{path} is not JSON and pyyaml is unavailable")


def _resolve_algo(run: str):
    import ray_tpu.rllib as rl
    cfg_cls = getattr(rl, f"{run}Config", None)
    if cfg_cls is None:
        names = sorted(n[:-6] for n in rl.__all__ if n.endswith("Config"))
        raise SystemExit(f"unknown algorithm {run!r}; available: {names}")
    return cfg_cls


def run_experiment(spec: Dict, quiet: bool = False) -> bool:
    """Run one tuned-example spec; True iff metric bars were met."""
    import os

    import ray_tpu
    started = False
    if not ray_tpu.is_initialized():
        # Algorithms are cluster citizens (rollout workers are actors);
        # bring up a local runtime like `rllib train` does.  Logical
        # CPUs floor at 4: tuned examples assume a few rollout-worker
        # slots, and on a 1-core host the raylet would otherwise report
        # their resource demands infeasible (CPU here is a scheduling
        # token, not a pinned core).
        ray_tpu.init(num_cpus=int(os.environ.get(
            "RT_NUM_CPUS", max(4, os.cpu_count() or 1))),
            ignore_reinit_error=True)
        started = True
    try:
        return _run_experiment_inner(spec, quiet)
    finally:
        if started:
            ray_tpu.shutdown()


def _run_experiment_inner(spec: Dict, quiet: bool) -> bool:
    cfg_cls = _resolve_algo(spec["run"])
    builder = cfg_cls()
    if (spec.get("env") is not None or spec.get("env_config")) \
            and hasattr(builder, "environment"):
        builder.environment(spec.get("env"),
                            spec.get("env_config") or None)
    if spec.get("offline"):
        # Hermetic battery: generate the dataset the reference would
        # read from disk (offline/generators.py).
        from ray_tpu.rllib.offline.generators import generate
        builder.offline_data(generate(spec["offline"]))
    builder.training(**(spec.get("config") or {}))
    if spec.get("seed") is not None:
        builder.debugging(seed=spec["seed"])
    algo = builder.build()
    stop = dict(spec.get("stop") or {})
    max_iters = int(stop.pop("training_iteration", 100))
    bars = stop  # every remaining key is a metric >= bar
    # Lower-is-better bars (exploitability, model losses).
    bars_lte = dict(spec.get("stop_lte") or {})
    met = not bars and not bars_lte
    try:
        for i in range(max_iters):
            result = algo.train()
            if not quiet:
                shown = {k: round(v, 2) for k, v in result.items()
                         if isinstance(v, (int, float))
                         and k in ("episode_reward_mean",
                                   "episode_reward_this_iter",
                                   "mixture_exploitability",
                                   "timesteps_total")}
                print(f"iter {i + 1}: {shown}", flush=True)
            ge_ok = all(isinstance(result.get(k), (int, float))
                        and result[k] >= bar
                        for k, bar in bars.items())
            le_ok = all(isinstance(result.get(k), (int, float))
                        and result[k] <= bar
                        for k, bar in bars_lte.items())
            if (bars or bars_lte) and ge_ok and le_ok:
                met = True
                break
    finally:
        try:
            algo.stop()
        except Exception:
            pass
    return met


def run_battery(directory: str, include=None, quiet: bool = True) -> int:
    """Sweep every tuned example in ``directory`` (the regression
    battery the reference replays in CI from rllib/tuned_examples/ via
    rllib/BUILD learning-test targets).  Prints a PASS/FAIL table;
    exit code 0 iff every spec met its bars."""
    import glob
    import os
    import time as _time

    paths = sorted(glob.glob(os.path.join(directory, "*.json")))
    if include:
        wanted = set(include)
        paths = [p for p in paths
                 if os.path.splitext(os.path.basename(p))[0] in wanted]
        missing = wanted - {os.path.splitext(os.path.basename(p))[0]
                            for p in paths}
        if missing:
            raise SystemExit(f"no tuned example named: {sorted(missing)}")
    if not paths:
        raise SystemExit(f"no tuned examples under {directory}")
    rows = []
    failed = 0
    for p in paths:
        name = os.path.splitext(os.path.basename(p))[0]
        t0 = _time.monotonic()
        run = "?"
        try:
            # Inside the try: a malformed spec is THAT example's FAIL,
            # not a lost sweep.
            spec = load_config(p)
            run = spec["run"]
            ok = run_experiment(spec, quiet=quiet)
            err = ""
        except (KeyboardInterrupt, SystemExit):
            raise  # the operator's abort must abort the sweep
        except BaseException as e:  # a crash is a battery failure
            ok, err = False, f"{type(e).__name__}: {e}"
        rows.append((name, run, ok, _time.monotonic() - t0, err))
        failed += 0 if ok else 1
        print(f"[{len(rows)}/{len(paths)}] {name}: "
              f"{'PASS' if ok else 'FAIL'} ({rows[-1][3]:.0f}s)"
              + (f" {err}" if err else ""), flush=True)
    width = max(len(r[0]) for r in rows)
    print(f"\n{'example'.ljust(width)}  algo        result  seconds")
    for name, run, ok, dt, err in rows:
        print(f"{name.ljust(width)}  {run.ljust(10)}  "
              f"{'PASS' if ok else 'FAIL'}    {dt:7.1f}"
              + (f"  {err}" if err else ""))
    print(f"\n{len(rows) - failed}/{len(rows)} passed")
    return 1 if failed else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="rllib-train",
                                     description=__doc__.split("\n")[0])
    parser.add_argument("-f", "--file", help="JSON/YAML experiment spec")
    parser.add_argument("--batch", metavar="DIR", default=None,
                        help="run EVERY tuned example in DIR as a "
                             "regression battery (table + exit code)")
    parser.add_argument("--include", nargs="*", default=None,
                        help="with --batch: only these example names")
    parser.add_argument("--run", help="algorithm name (e.g. PPO)")
    parser.add_argument("--env", help="gym env id")
    parser.add_argument("--stop-reward", type=float, default=None)
    parser.add_argument("--stop-iters", type=int, default=20)
    parser.add_argument("--config", default="{}",
                        help="JSON dict of algorithm config overrides")
    parser.add_argument("-q", "--quiet", action="store_true")
    args = parser.parse_args(argv)

    if args.batch:
        return run_battery(args.batch, include=args.include,
                           quiet=args.quiet)
    if args.file:
        spec = load_config(args.file)
    elif args.run:
        spec = {"run": args.run, "env": args.env,
                "config": json.loads(args.config),
                "stop": {"training_iteration": args.stop_iters}}
        if args.stop_reward is not None:
            spec["stop"]["episode_reward_mean"] = args.stop_reward
    else:
        parser.error("need -f FILE or --run ALGO")
    ok = run_experiment(spec, quiet=args.quiet)
    print("PASSED" if ok else "FAILED: stop criteria not met")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
