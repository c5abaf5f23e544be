"""Device time of the pair kernels (``kernels/bitset_intersect``,
``kernels/uint_intersect``) per traced whole-graph query, in
milliseconds: the Pallas programs alone, not the gathers and padding
launched around them."""
from ehbench.readers import PAIR_KERNELS, module_ms_per_unit


def read(run):
    return module_ms_per_unit(run, PAIR_KERNELS)
