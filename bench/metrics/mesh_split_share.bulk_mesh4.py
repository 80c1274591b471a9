"""Share of the window's launches of pools on a mesh of more than one
device that ran split over every device of it, %: the tracer's
``mesh_launches_split`` over ``mesh_launches``.  A launch whose words come
back from fewer devices than the mesh has ran on fewer chips."""


def read(obs):
    st = obs["stages"]
    if "mesh_launches_split" not in st or not st.get("mesh_launches"):
        return None
    return 100.0 * st["mesh_launches_split"] / st["mesh_launches"]
