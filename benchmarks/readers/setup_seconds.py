"""Process start to the first measured request or step."""


def read(obs):
    return obs["t_w"] - obs["t_proc0"]
