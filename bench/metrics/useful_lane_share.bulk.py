"""Share of the lane-rows the kernels computed whose words a tenant
buffered, %: the tracer's ``lanes_used`` over ``lanes_computed`` in the
window.  Pad lanes and the lanes of idle tenants that ride a launch are
computed and thrown away."""


def read(obs):
    st = obs["stages"]
    if "lanes_used" not in st or not st.get("lanes_computed"):
        return None
    return 100.0 * st["lanes_used"] / st["lanes_computed"]
