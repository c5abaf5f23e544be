"""The benchmark's Graph500 generator: quadrant shares, relabelling and
the simple undirected graph it hands the loader."""
import numpy as np
import pytest

import benchpath  # noqa: F401
from ehbench.graph500 import graph500, kronecker_edges, simple_undirected

A, B, C = 0.57, 0.19, 0.19


def test_quadrant_shares_are_graph500s():
    # scale 1: every edge is one draw of one level's quadrant
    m = 1 << 20
    src, dst = kronecker_edges(1, m >> 1, A, B, C, np.random.default_rng(3))
    shares = np.bincount(src * 2 + dst, minlength=4) / m
    want = np.array([A, B, C, 1 - A - B - C])
    sigma = np.sqrt(want * (1 - want) / m)
    assert np.all(np.abs(shares - want) < 5 * sigma), shares


def test_levels_are_independent_draws():
    m = 1 << 18
    src, dst = kronecker_edges(3, m >> 3, A, B, C, np.random.default_rng(4))
    for level in range(3):
        q = ((src >> level) & 1) * 2 + ((dst >> level) & 1)
        shares = np.bincount(q, minlength=4) / m
        assert np.allclose(shares, [A, B, C, 1 - A - B - C], atol=0.01)


def test_labels_are_permuted():
    raw_src, raw_dst = kronecker_edges(10, 16, A, B, C,
                                       np.random.default_rng(5))
    raw = simple_undirected(1 << 10, raw_src, raw_dst)
    # without relabelling, vertex 0 (all bits in quadrant a) is the hub
    assert int(np.argmax(raw.degrees)) == 0
    g1 = graph500(10, 16, A, B, C, edge_seed=5, label_seed=1)
    g2 = graph500(10, 16, A, B, C, edge_seed=5, label_seed=2)
    assert np.array_equal(np.sort(g1.degrees), np.sort(raw.degrees))
    assert np.array_equal(np.sort(g2.degrees), np.sort(raw.degrees))
    assert not np.array_equal(g1.degrees, raw.degrees)
    assert not np.array_equal(g1.degrees, g2.degrees)


@pytest.mark.parametrize("scale", [6, 9])
def test_simple_symmetric_sorted(scale):
    g = graph500(scale, 16, A, B, C, edge_seed=scale, label_seed=7)
    src = g.sources()
    assert g.offsets[0] == 0 and g.offsets[-1] == g.m
    assert not np.any(src == g.neighbors)                       # no loops
    fwd = set(zip(src.tolist(), g.neighbors.tolist()))
    assert len(fwd) == g.m                                      # no dups
    assert fwd == set(zip(g.neighbors.tolist(), src.tolist()))  # symmetric
    for v in range(g.n):
        seg = g.neighbors[g.offsets[v]:g.offsets[v + 1]]
        assert np.all(np.diff(seg) > 0)


def test_same_seeds_same_graph():
    g1 = graph500(8, 16, A, B, C, edge_seed=[9, 1], label_seed=[9, 2])
    g2 = graph500(8, 16, A, B, C, edge_seed=[9, 1], label_seed=[9, 2])
    assert np.array_equal(g1.offsets, g2.offsets)
    assert np.array_equal(g1.neighbors, g2.neighbors)
