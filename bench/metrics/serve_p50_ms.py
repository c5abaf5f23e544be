"""Median latency, from due time to the end of the answering
drain, over every request due in the window."""
from ehbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.latencies_s, 50) if run.loop == "open" else None
