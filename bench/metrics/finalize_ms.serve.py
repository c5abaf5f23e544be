"""Wall time projecting landed frontiers to results (span
``eh.finalize``: ``GenericJoin._finalize``, its eager segment sums
included) per request answered in the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.finalize")
