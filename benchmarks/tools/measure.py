"""Repeated runs of one cell, as the bounds are measured: sets of runs,
each run of a set with another seed and the same seeds in every set;
for each metric the median and the spread of each set (distance between
the first and third quartile, `statistics.quantiles(n=4)`, as a share
of the median).  Every run is a new process of the benchmark's own
command; their last lines are kept under chiprun_out/.

    python3 benchmarks/tools/measure.py --workload mistral7b-chat \
        --seeds 11,12,13,14,15,16 --sets 2 [--trace 0] [--seconds N]
        [--set rate_rps=1.2]        # a sweep by hand, not a judged run
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import stats  # noqa: E402


def run_once(spec: dict, root: str, workload: str, seed: int,
             seconds: float, trace: int, extra=(), tag: str = "") -> dict:
    """One run of the benchmark's command in a new process started in
    `root`; its lines parsed, a line printed here."""
    cmd = spec["command"] + ["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace), *extra]
    t0 = time.time()
    r = subprocess.run(cmd, cwd=root, capture_output=True, text=True)
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    ok = r.returncode == 0 and lines
    row = {"seed": seed, "trace": trace, "rc": r.returncode,
           "wall_s": round(time.time() - t0, 1),
           "last": json.loads(lines[-1]) if ok else None,
           "earlier": [json.loads(ln) for ln in lines[:-1]] if ok else []}
    if not ok:
        row["stderr_tail"] = r.stderr[-3000:]
        print(r.stderr[-3000:], file=sys.stderr)
    last = row["last"] or {}
    print(f"{tag}seed {seed} trace {trace} rc {r.returncode} wall "
          f"{row['wall_s']}s correct {last.get('correct')} failed "
          f"{last.get('failed')} " + " ".join(
              f"{n}={m['value']:.6g}" for n, m in
              last.get("metrics", {}).items()), flush=True)
    return row


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--set", action="append", default=[])
    p.add_argument("--tag", default="")
    p.add_argument("--keep-trace", default=None)
    args = p.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = args.seconds or spec["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, f"measure-{args.workload}"
                            f"{args.tag}-t{args.trace}.jsonl")
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            extra = [x for item in args.set for x in ("--set", item)]
            if args.keep_trace:
                extra += ["--keep-trace", args.keep_trace]
            row = run_once(spec, ROOT, args.workload, seed, seconds,
                           args.trace, extra, tag=f"set {k} ")
            row["set"] = k
            with open(log_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            vals = {n: m["value"] for n, m in
                    (row["last"] or {}).get("metrics", {}).items()}
            rows.append(vals)
        sets.append(rows)
    names = sorted({n for rows in sets for r in rows for n in r})
    for n in names:
        parts = []
        for rows in sets:
            xs = [r[n] for r in rows if n in r]
            if not xs:
                continue
            sp = stats.iqr_share(xs)
            parts.append(f"median {statistics.median(xs):.5g} spread "
                         f"{'n/a' if sp is None else format(sp, '.4f')} "
                         f"(n={len(xs)})")
        print(f"{n}: " + " | ".join(parts), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
