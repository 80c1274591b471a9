"""Front-end resolve host time per million delivered words: the tracer's
``resolve`` span (``frontend.cycle.resolve``: the launch-free delivery
pass and the FIFO split onto the futures) over the window."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "resolve" not in st:
        return None
    return 1e3 * st["resolve"] / (obs["words"] / 1e6)
