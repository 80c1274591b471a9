"""Mean time a draw waited in the front-end's queue, ms: the tracer's
summed ``queue_wait_s`` (commit time less submit time, over every
committed draw) over its ``draws_committed``, in the window."""


def read(obs):
    st = obs["stages"]
    if "queue_wait_s" not in st or not st.get("draws_committed"):
        return None
    return 1e3 * st["queue_wait_s"] / st["draws_committed"]
