"""Service host time per million delivered words: the farm's ``absorb``
stage timer over the window."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "absorb" not in st:
        return None
    return 1e3 * st["absorb"] / (obs["words"] / 1e6)
