"""Wall time in the engine's pair preparation and pair kernels (span
``eh.pairs``: ``ExecBackend.pair_count`` / ``pair_materialize``, whose
device work ends inside it) per query of the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.pairs")
