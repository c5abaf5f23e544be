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


pagerank = registry.reference("pagerank")


def dense_pagerank(a, iters):
    """Power iteration on the dense adjacency, over the vertices with
    edges."""
    deg = a.sum(axis=1)
    keys = np.flatnonzero(deg > 0)
    sub = a[np.ix_(keys, keys)] / deg[keys][None, :]
    pr = np.full(len(keys), 1.0 / len(keys))
    for _ in range(iters):
        pr = 0.15 / len(keys) + 0.85 * sub @ pr
    return keys, pr


@pytest.mark.parametrize("n,p,seed,iters", [(12, 0.3, 5, 5), (30, 0.2, 6, 5),
                                            (60, 0.1, 7, 5), (40, 0.3, 8, 1)])
def test_pagerank_power_iteration(n, p, seed, iters):
    g = random_graph(n, p, seed)
    keys, want = dense_pagerank(dense(g).astype(np.float64), iters)
    got = pagerank.reference(g, iters=iters)
    np.testing.assert_array_equal(got[0], keys)
    np.testing.assert_allclose(got[1], want, rtol=1e-12)
    assert pagerank.compare(got, (keys, want)) == 0


@pytest.mark.parametrize("n,p,seed", [(30, 0.2, 9), (60, 0.1, 10)])
@pytest.mark.parametrize("control", ["bf16", "iters4"])
def test_pagerank_tolerance_fails_the_controls(n, p, seed, control):
    g = random_graph(n, p, seed)
    want = pagerank.reference(g)
    got = pagerank.reference(g, **pagerank.CONTROLS[control])
    assert pagerank.compare(got, want) > 0
    # float32 ranks, as the program declares them, stay inside it
    f32 = (want[0], want[1].astype(np.float32).astype(np.float64))
    assert pagerank.compare(f32, want) == 0


def test_pagerank_compare_counts_keys_and_values():
    keys = np.array([1, 4, 6, 9])
    vals = np.array([0.1, 0.2, 0.3, 0.4])
    want = (keys, vals)
    assert pagerank.compare(want, want) == 0
    assert pagerank.compare((keys[1:], vals[1:]), want) == 1
    assert pagerank.compare((np.r_[keys, 11], np.r_[vals, 0.5]), want) == 1
    assert pagerank.compare((np.r_[keys, 4], np.r_[vals, 0.2]), want) == 1
    tol = pagerank.RTOL
    off = vals * np.array([1, 1 + 2 * tol, 1, 1 + 0.1 * tol])
    assert pagerank.compare((keys, off), want) == 1
    assert pagerank.compare((keys, np.r_[vals[:3], np.nan]), want) == 1
