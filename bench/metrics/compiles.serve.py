"""XLA compilations (persistent-cache reads included) that the window's
requests brought, counted through ``jax.monitoring``: those their
bindings made when first served in the warm-up, after a warm-up on an
independent draw from the mix, plus any in the window itself."""
from ehbench.readers import counter


def read(run):
    window, first = counter(run, "compiles"), counter(run,
                                                      "compiles.first_serve")
    return None if window is None or first is None else window + first
