"""Launch host time per million delivered words: the farm's ``launch``
stage timer over the window, which holds the device wait and the
device-to-host copy of the words."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "launch" not in st:
        return None
    return 1e3 * st["launch"] / (obs["words"] / 1e6)
