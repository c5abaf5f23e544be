"""Graph500 Kronecker graphs, generated in bulk from a seed.

The Graph500 generator (graph500.org, "Kronecker generator"; the
``graph500-*`` datasets of LDBC Graphalytics are made with it) draws
``edge_factor * 2**scale`` edges.  At each of ``scale`` levels an edge
falls in quadrant a (0,0), b (0,1), c (1,0) or d (1,1) of the adjacency
matrix, with d = 1 - a - b - c: the source bit is 1 with probability
``c + d``, and the target bit is 1 with probability ``b / (a + b)``
after a 0 and ``d / (c + d)`` after a 1.  The vertex labels are then
randomly permuted, so the largest hub is not vertex 0.

The graph a deployment loads is the undirected simple graph: self-loops
and duplicate edges dropped, both directions stored.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected simple graph as a symmetric CSR (neighbours sorted)."""

    n: int
    offsets: np.ndarray    # [n + 1] int64
    neighbors: np.ndarray  # [m] int64, m = 2 * undirected edges

    @property
    def m(self) -> int:
        return int(len(self.neighbors))

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.offsets)

    def sources(self) -> np.ndarray:
        """Source vertex of each stored direction, aligned with
        ``neighbors``."""
        return np.repeat(np.arange(self.n, dtype=np.int64), self.degrees)


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng: np.random.Generator):
    """Raw Kronecker edge list (before relabelling and cleaning)."""
    d = 1.0 - a - b - c
    if min(a, b, c, d) < 0:
        raise ValueError(f"quadrant probabilities {a}, {b}, {c}, {d}")
    m = edge_factor << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    a_norm = a / (a + b)
    c_norm = c / (c + d)
    for level in range(scale):
        src_bit = rng.random(m) > a + b
        dst_bit = rng.random(m) > np.where(src_bit, c_norm, a_norm)
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    return src, dst


def simple_undirected(n: int, src: np.ndarray, dst: np.ndarray) -> Graph:
    """Drop self-loops and duplicates, store both directions."""
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    s = np.concatenate([lo, hi])
    t = np.concatenate([hi, lo])
    order = np.lexsort((t, s))
    s, t = s[order], t[order]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=offsets[1:])
    return Graph(n=n, offsets=offsets, neighbors=t)


def graph500(scale: int, edge_factor: int, a: float, b: float, c: float,
             edge_seed: int, label_seed: int) -> Graph:
    """The undirected Graph500 graph.  ``edge_seed`` draws the Kronecker
    edges, ``label_seed`` the random relabelling of the vertices."""
    n = 1 << scale
    src, dst = kronecker_edges(scale, edge_factor, a, b, c,
                               np.random.default_rng(edge_seed))
    perm = np.random.default_rng(label_seed).permutation(n)
    return simple_undirected(n, perm[src], perm[dst])
