"""Wall time in the engine's closing transfers (span ``eh.land``, the
wait for the batched bag program included) per request answered in the
window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.land")
