"""Share of the Pallas kernels' device time that the chip's roofline
needs for their work, %: the sum over the window's kernel calls of the
larger of FLOPs over peak FLOP/s and least bytes over peak HBM bandwidth
(``bench/work.py``), over the sum of their device times in the trace."""
from bench import trace, work


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    calls = trace.kernel_calls(tr)
    if not calls:
        return None
    classes = list(obs["classes"].values())
    need = sum(work.lower_bound_s(work.call_work(c, classes), obs["peaks"])
               for c, _ in calls)
    took = sum(sec for _, sec in calls)
    return 100.0 * need / took if took > 0 else None
