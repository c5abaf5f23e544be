"""Device time of the fused bag program (``core.backend._bag_program``)
per traced whole-graph query, in milliseconds."""
from ehbench.readers import BAG_PROGRAM, module_ms_per_unit


def read(run):
    return module_ms_per_unit(run, BAG_PROGRAM)
