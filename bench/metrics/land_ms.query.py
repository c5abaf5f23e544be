"""Wall time in the engine's closing transfers (span ``eh.land``:
``kernels.common.host_get``, the wait for the device included) per
query of the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.land")
