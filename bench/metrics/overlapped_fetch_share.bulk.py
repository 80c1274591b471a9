"""Share of the window's launch fetches that began while a later launch
of the same flush was already dispatched, %: the tracer's
``fetches_overlapped`` over ``fetches``.  A flush of n launches fetches
all but its last under a later launch, so two launches a flush read 50;
a program without the counters reports nothing."""


def read(obs):
    st = obs["stages"]
    if "fetches_overlapped" not in st or not st.get("fetches"):
        return None
    return 100.0 * st["fetches_overlapped"] / st["fetches"]
