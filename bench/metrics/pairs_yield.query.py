"""Lanes of the pair paths' lockstep search that found their value:
``pairs.found`` / ``pairs.searched`` over the window."""
from ehbench.readers import ratio


def read(run):
    return ratio(run, "pairs.found", "pairs.searched")
