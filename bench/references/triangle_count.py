"""Plain reference for the whole-graph triangle count program
``C(;w:long) :- R(x,y),S(y,z),T(x,z); w=<<COUNT(*)>>.`` with R, S, T the
symmetric edge relation: the number of ordered triples (x, y, z) with
all three edges present, i.e. ``sum(A @ A * A)``, six per triangle.

It reads only the benchmark's graph and counts in int64, in row blocks
of the adjacency matrix so that the wedge product stays small."""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

BLOCK_ROWS = 1 << 14
# The broken guarantees a control puts in the program's place: each
# triangle counted once, as a symmetry-breaking count does; counts in 16
# bits; counts in the engine's own 32 bits, the nearest type below the
# ``long`` the program declares.
CONTROLS = {"unordered": {"ordered": False},
            "int16": {"acc_dtype": np.int16},
            "int32": {"acc_dtype": np.int32}}


def adjacency(graph) -> sp.csr_matrix:
    return sp.csr_matrix(
        (np.ones(graph.m, np.int64), graph.neighbors, graph.offsets),
        shape=(graph.n, graph.n))


def reference(graph, acc_dtype=np.int64, ordered: bool = True) -> int:
    """Ordered triangle count, accumulated in ``acc_dtype``.  The
    controls take a narrower ``acc_dtype``, or ``ordered=False``: each
    triangle counted once, as a symmetry-breaking count does."""
    a = adjacency(graph)
    if not ordered:
        a = sp.triu(a, k=1, format="csr")
    total = np.zeros((), acc_dtype)
    for lo in range(0, graph.n, BLOCK_ROWS):
        rows = a[lo:lo + BLOCK_ROWS]
        per_row = np.asarray((rows @ a).multiply(rows).sum(axis=1)).ravel()
        total = _accumulate(total, per_row, acc_dtype)
    return int(total)


def _accumulate(total, values, acc_dtype):
    with np.errstate(over="ignore"):
        for chunk in np.array_split(values, max(1, len(values) // 4096)):
            total = (total + np.sum(chunk.astype(acc_dtype), dtype=acc_dtype)
                     ).astype(acc_dtype)
    return total


def answer(result) -> int:
    """The engine's answer as an integer (its scalar annotation)."""
    return int(np.asarray(result.scalar()))
