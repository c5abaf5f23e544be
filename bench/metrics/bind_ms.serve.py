"""Wall time binding requests to joins before the launch (span
``eh.bind`` in ``Engine._execute_batch``) per request answered in the
window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.bind")
