"""The tuned-examples learning-regression battery (reference:
rllib/BUILD learning-test targets replaying rllib/tuned_examples/ in
CI; one config per algorithm family, each with a stop bar the run must
MEET — not merely time out on).

Tiers: the fast (CI) subset sweeps five quick families on every run;
the full battery is one slow test sweeping EVERY spec via the same
``rllib train --batch`` entry point operators use."""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "ray_tpu", "rllib", "tuned_examples")

ALL_EXAMPLES = sorted(
    os.path.splitext(os.path.basename(p))[0]
    for p in glob.glob(os.path.join(EXAMPLES, "*.json")))

# Five fast families for every CI run: a bandit, the league,
# value-factorized multi-agent, an async learner, and offline IL.
FAST_SUBSET = ["bandit-linucb", "rps-league", "twostep-qmix",
               "cartpole-impala", "cartpole-marwil"]


def _battery(include, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.rllib.train", "-q",
         "--batch", EXAMPLES] +
        (["--include", *include] if include else []),
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_battery_covers_every_algorithm_family():
    """One spec per family: every *Config the package exports (minus
    the abstract base) is exercised by some tuned example."""
    import json

    import ray_tpu.rllib as rl
    covered = {json.load(open(p))["run"]
               for p in glob.glob(os.path.join(EXAMPLES, "*.json"))}
    families = {n[:-6] for n in rl.__all__
                if n.endswith("Config")} - {"Algorithm"}
    missing = families - covered
    assert not missing, f"families without a tuned example: {missing}"


def test_battery_fast_subset():
    """CI tier: five families sweep green through the battery runner."""
    r = _battery(FAST_SUBSET, timeout=1800)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert f"{len(FAST_SUBSET)}/{len(FAST_SUBSET)} passed" in r.stdout


@pytest.mark.slow
@pytest.mark.nightly
def test_battery_full_sweep():
    """Nightly tier: EVERY tuned example meets its bar in one sweep.
    Crash isolation is per-spec (a crashing algorithm shows as FAIL in
    the table, not as a lost sweep)."""
    r = _battery(None, timeout=7200)
    assert r.returncode == 0, r.stdout[-8000:] + r.stderr[-2000:]
    assert f"{len(ALL_EXAMPLES)}/{len(ALL_EXAMPLES)} passed" in r.stdout
