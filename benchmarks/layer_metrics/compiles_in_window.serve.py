"""Programs compiled inside the measured window (``jax.monitoring`` backend
compile events between the window's edges); 0 is expected."""


def read(view):
    return view["compiles_in_window"]
