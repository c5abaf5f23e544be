"""Share of the traced query's time inside the span ``eh.pairs`` in
which no operation ran on the device: how much of pair preparation the
host holds the chip idle for."""
from ehbench.spans import span_idle_share


def read(run):
    return span_idle_share(run, "eh.pairs")
