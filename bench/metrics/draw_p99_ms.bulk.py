"""The 99th percentile of the window's draw latencies, ms, in a cell that
saturates the farm.  There it is no end-to-end metric: runs of one seed
settle in one of two modes, about one flush cycle or about two, so more
than 1% of the draws wait a second cycle in some runs and not in others.
It moves ``words_per_s``: the runs in the short mode deliver more."""
import numpy as np


def read(obs):
    lat = obs.get("latency_ms")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 99))
