"""Tokens of the training steps that finished inside the window, over
the time they took (window start to the last step's end)."""


def read(obs):
    steps = obs["train"]["steps"]
    if not steps:
        return None
    job = obs["traffic"]
    return len(steps) * job["batch"] * job["seq"] \
        / (steps[-1][1] - obs["t_w"])
