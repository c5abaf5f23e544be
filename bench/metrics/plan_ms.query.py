"""Wall time in planning (span ``eh.plan``: ``Engine._compile`` and
``Engine._physical``, plan caches included) per query of the window, in
milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.plan")
