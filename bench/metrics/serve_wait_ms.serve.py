"""Median time from a request's due time to the start of the drain
that took it, in milliseconds."""
from ehbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.waits_s, 50) if run.loop == "open" else None
