"""Wall time preparing the device fixpoints on the host (span
``eh.recursion.prepare``: the edge view, the key positions, validity
masks and factor gathers, and putting the operands on the device) per
query of the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.recursion.prepare")
