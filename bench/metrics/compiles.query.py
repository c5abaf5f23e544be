"""XLA compilations (persistent-cache reads included) in the window,
counted through ``jax.monitoring``."""
from ehbench.readers import counter


def read(run):
    return counter(run, "compiles")
