"""`rllib train` CLI + tuned_examples battery (reference: rllib/train.py
and tuned_examples/ replayed in CI)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "ray_tpu", "rllib", "tuned_examples")


def _run_cli(*argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.rllib.train", "-q", *argv],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_unknown_algorithm_lists_available():
    r = _run_cli("--run", "NotAnAlgo", timeout=120)
    assert r.returncode != 0
    assert "PPO" in (r.stdout + r.stderr)


def test_tuned_example_league_passes():
    """The fastest tuned example end-to-end: the league reaches its
    exploitability bar and the CLI exits 0."""
    r = _run_cli("-f", os.path.join(EXAMPLES, "rps-league.json"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASSED" in r.stdout


def test_unmet_bar_fails(tmp_path):
    spec = {"run": "AlphaStar",
            "config": {"games_per_step": 64},
            "stop": {"episode_reward_mean": 1.0,  # unreachable (> 0 max)
                     "training_iteration": 2}}
    p = tmp_path / "impossible.json"
    p.write_text(json.dumps(spec))
    r = _run_cli("-f", str(p), timeout=300)
    assert r.returncode == 1
    assert "FAILED" in r.stdout


@pytest.mark.slow
def test_tuned_example_cartpole_dqn_passes():
    r = _run_cli("-f", os.path.join(EXAMPLES, "cartpole-dqn.json"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
