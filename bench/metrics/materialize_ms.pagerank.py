"""Wall time storing rule results under their heads (span
``eh.materialize``: ``Engine._materialize_head``, the head's
``Trie.build``) per query of the window, in milliseconds."""
from ehbench.spans import span_ms


def read(run):
    return span_ms(run, "eh.materialize")
