"""Share of the window in which no op ran on the device, %, from the
profiler trace (averaged over the cell's chips)."""
from bench import trace


def read(obs):
    tr = obs.get("trace")
    if tr is None or not tr.devices:
        return None
    return 100.0 * trace.idle_share(tr)
