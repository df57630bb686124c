"""Backend compiles (persistent-cache loads included) inside the decode
window, from jax.monitoring events; 0 when warm-up covered every shape."""


def read(r, trace):
    return float(r["compiles_in_window"])
