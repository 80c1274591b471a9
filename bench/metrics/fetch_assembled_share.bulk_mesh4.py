"""Share of the window's launches of pools on a mesh of more than one
device whose words the host assembled from more than one device buffer,
%: the tracer's ``fetch_assembled`` over ``mesh_launches``.  0 when every
sharded launch hands back its words whole on one device buffer; a program
without the counter reports nothing."""


def read(obs):
    st = obs["stages"]
    if "fetch_assembled" not in st or not st.get("mesh_launches"):
        return None
    return 100.0 * st["fetch_assembled"] / st["mesh_launches"]
