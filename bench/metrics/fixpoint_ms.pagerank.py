"""Device time of the naive fixpoint (``core.recursion._naive_device``)
per traced query, in milliseconds."""
from ehbench.fixpoint import NAIVE_DEVICE
from ehbench.readers import module_ms_per_unit


def read(run):
    return module_ms_per_unit(run, NAIVE_DEVICE)
