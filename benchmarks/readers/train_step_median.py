"""Median step time (ms) on the host clock around `block_until_ready`
on the step's loss, over the steps that finished inside the window."""

import statistics


def read(obs):
    steps = obs["train"]["steps"]
    return statistics.median(t1 - t0 for t0, t1, _ in steps) * 1e3 \
        if steps else None
