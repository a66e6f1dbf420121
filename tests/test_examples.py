"""Every example in examples/ runs end-to-end (reference: the doc/example
smoke suites in CI — examples are user surface, so they must not rot)."""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


def _run(name, timeout=240):
    repo = os.path.dirname(EXAMPLES)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, name)],
        env=env, timeout=timeout, capture_output=True, text=True,
        cwd=os.path.dirname(EXAMPLES))
    assert proc.returncode == 0, \
        f"{name} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    return proc.stdout


def test_core_walkthrough():
    out = _run("core_walkthrough.py")
    assert "core walkthrough done" in out
    assert "in-pg task: 49" in out


@pytest.mark.slow
def test_train_gpt():
    out = _run("train_gpt.py")
    assert "final loss:" in out and "params" in out


@pytest.mark.slow
def test_tune_asha():
    out = _run("tune_asha.py", timeout=360)
    assert "best lr:" in out


@pytest.mark.slow
def test_serve_model():
    out = _run("serve_model.py")
    assert "HTTP: {'class':" in out and "handle: {'class':" in out


@pytest.mark.slow
def test_data_to_train():
    out = _run("data_to_train.py")
    assert "read 400 rows from 4 files" in out
    assert "final loss:" in out


@pytest.mark.slow
def test_rllib_ppo():
    out = _run("rllib_ppo.py", timeout=480)
    assert "episode_reward_mean" in out


@pytest.mark.slow
def test_serve_llm():
    out = _run("serve_llm.py", timeout=360)
    assert "generated:" in out


@pytest.mark.slow
def test_llm_serving_continuous_batching():
    out = _run("llm_serving.py", timeout=360)
    assert "llm serving example done" in out
    assert "[DONE]" in out  # SSE stream reached its terminator
