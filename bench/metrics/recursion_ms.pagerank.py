"""Wall time in recursive rules (span ``eh.recursion``:
``Engine._eval_recursive``, its host preparation, the device fixpoint
and its closing sync included) per query of the window, in
milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.recursion")
