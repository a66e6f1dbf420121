"""Median device time (ms) of one jitted program's runs in the traced
window, from the trace's `XLA Modules` line."""

import statistics


def read(obs, program):
    runs = ((obs.get("trace") or {}).get("programs") or {}).get(program)
    return statistics.median(runs) * 1e3 if runs else None
