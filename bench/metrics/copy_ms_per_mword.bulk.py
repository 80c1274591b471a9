"""Device-to-host copy host time per million delivered words: the
tracer's ``launch_copy`` span (``farm.launch.copy``, the copy of a
finished launch's words) over the window.  A part of ``launch``."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "launch_copy" not in st:
        return None
    return 1e3 * st["launch_copy"] / (obs["words"] / 1e6)
