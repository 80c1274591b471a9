"""Spread of the chips' busy time in the window, %: 100 (max - min) / mean
of each device's busy seconds (the union of its op intervals, from the
profiler trace).  A chip that straggles or is left out shows here."""
from bench import trace


def read(obs):
    tr = obs.get("trace")
    if tr is None or len(tr.devices) < 2:
        return None
    t0, t1 = tr.window()
    busy = [trace.busy_ns(ops, t0, t1) for ops in tr.devices.values()]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
