"""95th percentile latency, from due time to the end of the
answering drain, over every request due in the window, in
milliseconds.  Near the knee it swings with the order of the
requests, so it stands per layer beside the median."""
from ehbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.latencies_s, 95) if run.loop == "open" else None
