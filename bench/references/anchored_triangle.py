"""Plain reference for the anchored triangle program
``C(;w:long) :- R(v,y),S(y,z),T(v,z); w=<<COUNT(*)>>.`` bound to one
vertex v, with R, S, T the symmetric edge relation: the number of
ordered pairs (y, z) of neighbours of v that are themselves adjacent,
twice the triangles through v.

It reads only the benchmark's graph: for each bound vertex it marks
N(v) and counts the marked entries of the neighbour lists of N(v), in
int64."""
from __future__ import annotations

import numpy as np

# The broken guarantees a control puts in the program's place: each pair
# {y, z} counted once; counts in 16 bits; counts in the engine's own 32
# bits, the nearest type below the ``long`` the program declares.
CONTROLS = {"unordered": {"ordered": False},
            "int16": {"acc_dtype": np.int16},
            "int32": {"acc_dtype": np.int32}}


def per_vertex(graph, vertices, acc_dtype=np.int64,
               ordered: bool = True) -> dict[int, int]:
    """Answer for each distinct vertex, accumulated in ``acc_dtype``.
    The controls take a narrower ``acc_dtype``, or ``ordered=False``:
    each pair {y, z} counted once."""
    offs, nbr = graph.offsets, graph.neighbors
    mark = np.zeros(graph.n, bool)
    out = {}
    for v in sorted(set(int(x) for x in vertices)):
        ys = nbr[offs[v]:offs[v + 1]]
        mark[ys] = True
        lo, cnt = offs[ys], offs[ys + 1] - offs[ys]
        starts = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
        zs = nbr[starts + np.arange(int(cnt.sum()))]
        hits = mark[zs]
        if not ordered:
            hits &= zs > np.repeat(ys, cnt)
        hits = hits.astype(acc_dtype)
        with np.errstate(over="ignore"):
            out[v] = int(np.sum(hits, dtype=acc_dtype))
        mark[ys] = False
    return out


def answer(result) -> int:
    return int(np.asarray(result.scalar()))
