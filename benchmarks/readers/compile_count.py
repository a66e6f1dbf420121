"""Entries the persistent compile cache gained between the window's
start and end, counted in the process that holds the chip.  Must read
0: nothing compiles inside the window."""


def read(obs):
    a, b = obs.get("cache0"), obs.get("cache1")
    if a is None or b is None or a < 0 or b < 0:
        return None
    return b - a
