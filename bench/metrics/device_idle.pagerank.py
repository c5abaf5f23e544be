"""Share of one traced PageRank query in which no operation ran on the
device."""
from ehbench.readers import device_idle as read  # noqa: F401
