"""Bytes the engine put on the device (``upload.bytes``: trie levels,
cursors and the device fixpoints' operands) per query of the window, in
megabytes."""
from ehbench.spans import per_unit


def read(run):
    return per_unit(run, "upload.bytes", 1e-6)
