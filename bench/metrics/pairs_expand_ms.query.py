"""Wall time in the host part of pair preparation (span
``eh.pairs.expand``: the expansion before each lockstep search and the
index arithmetic after it) per query of the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.pairs.expand")
