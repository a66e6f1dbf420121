"""A change to the benchmark's own code, shown to move no reading: the
parent's harness against this one on the same program, at shared seeds,
in one call on one machine.  Every run is a new process of the
benchmark's command, started in the side's own checkout; the order is
parent-change, change-parent, ... with one seed a pair.  At each seed
the two sides' correctness diagnostics (a serving run's `check`, a
training run's losses) must agree to the last printed digit: one that
differs means the weights, the traffic or the reference moved.

    python3 benchmarks/tools/pairs.py --parent _parent \
        --workload mistral7b-chat --seeds 2147486001,2147486002 \
        [--change <dir>] [--traced-seed 2147486003] [--seconds N]

`--parent` is a `git archive` of the parent commit unpacked into a
directory `.gitignore` lists.  `--traced-seed` adds one `--trace 1` run
of the change.  Rows are kept under chiprun_out/.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tools.measure import run_once  # noqa: E402


def one(side: str, root: str, spec: dict, workload: str, seed: int,
        seconds: float, trace: int, log_path: str) -> dict:
    row = run_once(spec, root, workload, seed, seconds, trace,
                   tag=f"{side:6s} ")
    diag = row["earlier"][0] if row["earlier"] else {}
    # what `correct` was decided from, as printed
    row.update(side=side, digits={k: diag[k] for k in (
        "check", "losses", "loss_curve_held") if k in diag})
    with open(log_path, "a") as f:
        f.write(json.dumps(row) + "\n")
    return row


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--parent", required=True)
    p.add_argument("--change", default=ROOT)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--traced-seed", type=int, default=None)
    p.add_argument("--seconds", type=float, default=None)
    args = p.parse_args()
    roots = {"parent": os.path.abspath(args.parent),
             "change": os.path.abspath(args.change)}
    with open(os.path.join(roots["change"], "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = args.seconds or spec["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, f"pairs-{args.workload}.jsonl")
    bad = 0
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        order = ("parent", "change") if k % 2 == 0 else ("change", "parent")
        rows = {side: one(side, roots[side], spec, args.workload, seed,
                          seconds, 0, log_path) for side in order}
        a, b = rows["parent"], rows["change"]
        same = bool(a["digits"]) and a["digits"] == b["digits"]
        sound = all(r["last"] and r["last"]["correct"]
                    and r["last"]["failed"] == 0 for r in (a, b))
        bad += (not same) + (not sound)
        print(f"seed {seed}: digits equal {same}; both correct with 0 "
              f"failed {sound}; " + json.dumps(b["digits"])[:600],
              flush=True)
    if args.traced_seed is not None:
        row = one("change", roots["change"], spec, args.workload,
                  args.traced_seed, seconds, 1, log_path)
        bad += not (row["last"] and row["last"]["correct"])
        print("breakdown " + json.dumps((row["last"] or {}).get(
            "breakdown"))[:2000], flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
