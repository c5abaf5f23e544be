"""Device time of the fused bag programs (``core.backend._bag_program``
and ``_bag_program_batch``) per request of the traced loop, in
milliseconds."""
from ehbench.readers import BAG_PROGRAM_ANY, module_ms_per_unit


def read(run):
    return module_ms_per_unit(run, BAG_PROGRAM_ANY)
