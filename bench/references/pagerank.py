"""Plain reference for the paper's PageRank program
(``core.workload.pagerank_program(5)``)::

    N(;w:int) :- Edge(x,y); w=<<COUNT(x)>>.
    InvDeg(x;y:float) :- Edge(x,z); y=1.0/<<COUNT(z)>>.
    PageRank(x;y:float) :- Edge(x,z); y=1.0/N.
    PageRank(x;y:float)*[i=5] :- Edge(x,z),PageRank(z),InvDeg(z); y=0.15/N+0.85*<<SUM(z)>>.

over the benchmark's graph: the keys are the vertices with out-edges, N
their number, every rank starts at 1/N, and each of the 5 rounds sets
``y(x) = 0.15/N + 0.85 * sum over Edge(x,z) of PR(z)/deg(z)``.

It reads only the benchmark's graph and computes in float64, the rank
sums in row blocks of the adjacency so that the gathered terms stay
small.  The answer is keyed: ``(keys int64[], ranks float64[])``.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

ITERS = 5
BLOCK_ROWS = 1 << 16
# The program declares its ranks float (float32).  A rank is a sum of up
# to max-degree float32 terms, so the program may lose a few ulps per
# term; the chip reads at most 6.0e-6 relative error at scale 21 (the
# hub's sum), more than 10x under RTOL.  Ranks held in bfloat16 (3
# significant digits) or a round short of 5 read 1e-3 or more, so RTOL
# fails both.
RTOL = 1e-4
# Every rank is at least 0.15/N (above 1e-7 for N < 2**20), so a floor
# this far below it only keeps a zero rank from being read as relative
# error 0.
ATOL = 1e-10
# The broken guarantees a control puts in the program's place: ranks
# accumulated in bfloat16, the nearest precision below the declared
# float; one round short, what a fixpoint that stops early gives.
CONTROLS = {"bf16": {"acc_dtype": ml_dtypes.bfloat16},
            "iters4": {"iters": ITERS - 1}}


def reference(graph, iters: int = ITERS, acc_dtype=np.float64):
    """Keys and ranks after ``iters`` rounds, each rank summed and held
    in ``acc_dtype``."""
    deg = graph.degrees
    keys = np.flatnonzero(deg > 0)
    n_keys = len(keys)
    inv = np.zeros(graph.n)
    inv[keys] = 1.0 / deg[keys]
    pr = np.zeros(graph.n, acc_dtype)
    pr[keys] = 1.0 / n_keys
    base = np.asarray(0.15 / n_keys, acc_dtype)
    damp = np.asarray(0.85, acc_dtype)
    for _ in range(iters):
        term = (pr.astype(np.float64) * inv).astype(acc_dtype)
        nxt = np.zeros(graph.n, acc_dtype)
        nxt[keys] = base + damp * _rank_sums(graph, keys, term)
        pr = nxt
    return keys, pr[keys].astype(np.float64)


def _rank_sums(graph, keys, term):
    """``sum over Edge(x,z) of term[z]`` for each key x, in the dtype of
    ``term``, a block of rows at a time."""
    offs, nbr = graph.offsets, graph.neighbors
    out = np.empty(len(keys), term.dtype)
    for lo in range(0, len(keys), BLOCK_ROWS):
        rows = keys[lo:lo + BLOCK_ROWS]
        first = offs[rows[0]]
        vals = term[nbr[first:offs[rows[-1] + 1]]]
        out[lo:lo + len(rows)] = np.add.reduceat(vals, offs[rows] - first)
    return out


def answer(result):
    """The engine's answer as host arrays: its key column and its
    annotation."""
    return (np.asarray(result.columns[result.vars[0]], np.int64),
            np.asarray(result.annotation, np.float64))


def compare(got, want) -> int:
    """Keys missing from or extra in ``got``, plus ranks of the keys both
    hold that lie outside ``RTOL`` / ``ATOL`` of the reference's."""
    gk, gv = got
    wk, wv = want
    gk_u, first = np.unique(gk, return_index=True)
    common, gi, wi = np.intersect1d(gk_u, wk, assume_unique=True,
                                    return_indices=True)
    off_keys = len(gk) + len(wk) - 2 * len(common)
    bad = ~np.isclose(gv[first[gi]], wv[wi], rtol=RTOL, atol=ATOL)
    return int(off_keys + np.count_nonzero(bad))
