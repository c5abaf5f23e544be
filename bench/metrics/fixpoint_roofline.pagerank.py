"""Share of the HBM roofline the naive fixpoint reaches: the bytes a
pull SpMV must move for a query's rounds (``ehbench.fixpoint``) over
the fixpoint's device time, as a percentage of the chip's peak HBM
bandwidth."""
from ehbench.fixpoint import hbm_roofline_pct


def read(run):
    return hbm_roofline_pct(run)
