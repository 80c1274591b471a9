"""Host time spent waiting for the device per million delivered words:
the tracer's ``launch_wait`` span (``farm.launch.wait``, between a
launch's dispatch and the copy of its words) over the window.  A part of
``launch``."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "launch_wait" not in st:
        return None
    return 1e3 * st["launch_wait"] / (obs["words"] / 1e6)
