"""Front-end commit host time per million delivered words: the tracer's
``commit`` span (``frontend.cycle.commit``, the body of the front-end's
commit phase) over the window."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "commit" not in st:
        return None
    return 1e3 * st["commit"] / (obs["words"] / 1e6)
