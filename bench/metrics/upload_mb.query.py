"""Bytes the engine put on the device (``upload.bytes``: trie levels,
offsets, bitset directories, cursors, pair-search and pair-kernel
operands) per query of the window, in megabytes."""
from ehbench.spans import per_unit


def read(run):
    return per_unit(run, "upload.bytes", 1e-6)
