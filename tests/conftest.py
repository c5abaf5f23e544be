"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches see
the 1 real CPU device; only launch/dryrun.py (a subprocess in tests) forces
512 placeholder devices."""
import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute compile-heavy tests (dry-run integration); "
        "deselect with -m 'not slow'")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_undirected_graph(n: int, p: float, seed: int = 0):
    """Symmetric edge list (both directions), no self loops."""
    r = np.random.default_rng(seed)
    a = r.random((n, n)) < p
    a = np.triu(a, 1)
    a = a | a.T
    src, dst = np.nonzero(a)
    return src.astype(np.int64), dst.astype(np.int64), a


def brute_triangle_count(adj: np.ndarray) -> int:
    """Count undirected triangles by trace(A^3)/6."""
    a = adj.astype(np.int64)
    return int(np.trace(a @ a @ a) // 6)


def hub_csr(n: int = 2048, hub_degree: int = 64, seed: int = 0):
    """Directed CSR whose longest set, vertex 0's, has ``hub_degree``
    elements spread over every 256-bit block of ``[0, n)``; vertices 1-199
    hold up to 20 elements each, half drawn from the hub's set, and the
    rest are empty."""
    from repro.core.trie import CSRGraph
    r = np.random.default_rng(seed)
    hub = 1 + np.arange(hub_degree) * (n // hub_degree)
    src, dst = [np.zeros(hub_degree, np.int64)], [hub]
    for u in range(1, 200):
        k = int(r.integers(0, 21))
        pool = np.concatenate([hub, r.integers(0, n, hub_degree)])
        nb = np.unique(r.choice(pool, k, replace=False))
        src.append(np.full(len(nb), u))
        dst.append(nb)
    return CSRGraph.from_edges(np.concatenate(src), np.concatenate(dst), n=n)
