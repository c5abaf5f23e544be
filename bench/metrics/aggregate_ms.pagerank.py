"""Wall time grouping count-distinct aggregates on the host (span
``eh.aggregate``: ``Engine._eval_count_distinct``'s unique, bincount and
expression) per query of the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.aggregate")
