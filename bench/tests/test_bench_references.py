"""The plain references against brute force on small random graphs."""
import itertools

import numpy as np
import pytest

import benchpath  # noqa: F401
from ehbench import registry
from ehbench.graph500 import graph500, simple_undirected

tri = registry.reference("triangle_count")
anchored = registry.reference("anchored_triangle")


def random_graph(n, p, seed):
    rng = np.random.default_rng(seed)
    iu = np.triu_indices(n, 1)
    keep = rng.random(len(iu[0])) < p
    return simple_undirected(n, iu[0][keep], iu[1][keep])


def dense(g):
    a = np.zeros((g.n, g.n), np.int64)
    a[g.sources(), g.neighbors] = 1
    return a


@pytest.mark.parametrize("n,p,seed", [(12, 0.5, 0), (30, 0.3, 1),
                                      (40, 0.6, 2)])
def test_triangle_count_brute_force(n, p, seed):
    g = random_graph(n, p, seed)
    a = dense(g)
    ordered = sum(a[x, y] * a[y, z] * a[x, z]
                  for x, y, z in itertools.product(range(n), repeat=3))
    assert tri.reference(g) == ordered == np.trace(a @ a @ a)
    assert tri.reference(g, ordered=False) == ordered // 6


@pytest.mark.parametrize("n,p,seed", [(12, 0.5, 3), (30, 0.3, 4)])
def test_anchored_brute_force(n, p, seed):
    g = random_graph(n, p, seed)
    a = dense(g)
    got = anchored.per_vertex(g, range(n))
    half = anchored.per_vertex(g, range(n), ordered=False)
    for v in range(n):
        want = sum(a[v, y] * a[y, z] * a[v, z]
                   for y, z in itertools.product(range(n), repeat=2))
        assert got[v] == want and half[v] == want // 2


def test_anchored_sums_to_whole_graph_count():
    g = graph500(9, 16, 0.57, 0.19, 0.19, edge_seed=1, label_seed=2)
    per = anchored.per_vertex(g, range(g.n))
    assert sum(per.values()) == tri.reference(g)


def test_narrow_accumulator_wraps():
    g = graph500(10, 16, 0.57, 0.19, 0.19, edge_seed=3, label_seed=4)
    full = tri.reference(g)
    assert full > 1 << 15
    assert tri.reference(g, acc_dtype=np.int16) != full
    assert tri.reference(g, acc_dtype=np.int32) == full
