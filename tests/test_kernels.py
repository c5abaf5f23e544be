"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle,
over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bitset_intersect.ops import bitset_and_popcount
from repro.kernels.bitset_intersect.ref import bitset_and_popcount_ref
from repro.kernels.fm_interaction.ops import fm_interaction
from repro.kernels.fm_interaction.ref import (fm_interaction_pairwise_ref,
                                              fm_interaction_ref)
from repro.kernels.spmv_ell.ops import csr_to_ell, spmv_ell
from repro.kernels.spmv_ell.ref import spmv_ell_ref
from repro.kernels.triangle_mm.ops import densify_csr, triangle_count_dense
from repro.kernels.triangle_mm.ref import triangle_count_dense_ref
from repro.kernels.uint_intersect.ops import uint_intersect_count
from repro.kernels.uint_intersect.ref import uint_intersect_count_ref


@pytest.mark.parametrize("n_blocks,words,pairs", [
    (1, 1, 1), (10, 8, 64), (64, 8, 300), (50, 128, 1000), (7, 13, 77),
])
def test_bitset_and_popcount_sweep(rng, n_blocks, words, pairs):
    blocks = rng.integers(0, 2**32, size=(n_blocks, words), dtype=np.uint32)
    pa = rng.integers(0, n_blocks, pairs)
    pb = rng.integers(0, n_blocks, pairs)
    got = np.asarray(bitset_and_popcount(blocks, pa, pb, interpret=True))
    want = np.asarray(bitset_and_popcount_ref(jnp.asarray(blocks)[pa],
                                              jnp.asarray(blocks)[pb]))
    np.testing.assert_array_equal(got, want)


def test_bitset_and_popcount_chunked_launches(rng, monkeypatch):
    """Pairs beyond one launch's budget split into several launches
    (the last one ragged) whose outputs concatenate in order."""
    from repro.kernels.bitset_intersect import ops
    monkeypatch.setattr(ops, "_MAX_PAIRS", 1024)
    blocks = rng.integers(0, 2**32, size=(40, 8), dtype=np.uint32)
    pa = rng.integers(0, 40, 2500)
    pb = rng.integers(0, 40, 2500)
    got = np.asarray(bitset_and_popcount(blocks, pa, pb, interpret=True))
    want = np.asarray(bitset_and_popcount_ref(jnp.asarray(blocks)[pa],
                                              jnp.asarray(blocks)[pb]))
    np.testing.assert_array_equal(got, want)


def test_bitset_and_popcount_empty():
    out = bitset_and_popcount(np.zeros((4, 8), np.uint32),
                              np.zeros(0, np.int64), np.zeros(0, np.int64),
                              interpret=True)
    assert out.shape == (0,)


@pytest.mark.parametrize("p,la,lb,hi", [
    (1, 5, 7, 50), (20, 37, 61, 200), (8, 128, 128, 1000),
    (33, 200, 90, 500),
])
def test_uint_intersect_sweep(rng, p, la, lb, hi):
    a = np.full((p, la), -1, np.int32)
    b = np.full((p, lb), -1, np.int32)
    for i in range(p):
        na = rng.integers(0, la + 1)
        nb = rng.integers(0, lb + 1)
        a[i, :na] = np.sort(rng.choice(hi, na, replace=False))
        b[i, :nb] = np.sort(rng.choice(hi, nb, replace=False))
    got = np.asarray(uint_intersect_count(a, b, interpret=True))
    want = np.asarray(uint_intersect_count_ref(jnp.asarray(a),
                                               jnp.asarray(b)))
    expect = [len(np.intersect1d(a[i][a[i] >= 0], b[i][b[i] >= 0]))
              for i in range(p)]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, expect)


@pytest.mark.parametrize("n,dens", [(64, 0.1), (300, 0.05), (400, 0.2),
                                    (128, 0.0)])
def test_triangle_mm_sweep(rng, n, dens):
    a = (rng.random((n, n)) < dens).astype(np.float32)
    a = np.triu(a, 1)
    a = a + a.T
    got = float(triangle_count_dense(a, symmetric=True, interpret=True))
    want = float(triangle_count_dense_ref(jnp.asarray(a))) / 6.0
    brute = int(np.trace(np.linalg.matrix_power(a.astype(np.int64), 3)) // 6)
    assert abs(got - want) < 1e-3
    assert abs(got - brute) < 1e-3


def test_triangle_mm_pruned_dag(rng):
    """On a src>dst pruned DAG the raw masked sum counts each triangle once
    ... for path DAGs; cross-check against the symmetric count."""
    n = 150
    a = (rng.random((n, n)) < 0.1).astype(np.float32)
    a = np.triu(a, 1) + np.triu(a, 1).T
    sym = float(triangle_count_dense(a, symmetric=True, interpret=True))
    lower = np.tril(a)  # src > dst pruning keeps lower triangle
    # wedges u>v>w with (u,w) edge: each triangle exactly once
    pruned = float(((lower @ lower) * lower).sum())
    assert abs(sym - pruned) < 1e-3


@pytest.mark.parametrize("n,max_deg", [(10, 3), (700, 8), (513, 1),
                                       (1000, 16)])
def test_spmv_ell_sweep(rng, n, max_deg):
    deg = rng.integers(0, max_deg + 1, n)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=offs[1:])
    nbr = rng.integers(0, n, offs[-1]).astype(np.int32)
    w = rng.random(offs[-1]).astype(np.float32)
    cols, vals = csr_to_ell(offs, nbr, w)
    x = rng.random(n).astype(np.float32)
    got = np.asarray(spmv_ell(cols, vals, x, interpret=True))
    want = np.asarray(spmv_ell_ref(jnp.asarray(cols), jnp.asarray(vals),
                                   jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # dense oracle
    dense = np.zeros((n, n), np.float32)
    row = np.repeat(np.arange(n), deg)
    np.add.at(dense, (row, nbr), w)
    np.testing.assert_allclose(got, dense @ x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,f,d", [(1, 2, 4), (33, 39, 10), (128, 16, 32),
                                   (7, 8, 8)])
def test_fm_interaction_sweep(rng, b, f, d):
    emb = rng.normal(size=(b, f, d)).astype(np.float32)
    got = np.asarray(fm_interaction(emb, interpret=True))
    w1 = np.asarray(fm_interaction_ref(jnp.asarray(emb)))
    w2 = np.asarray(fm_interaction_pairwise_ref(jnp.asarray(emb)))
    np.testing.assert_allclose(got, w1, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(got, w2, rtol=3e-4, atol=3e-4)


def test_densify_roundtrip(rng):
    n = 50
    deg = rng.integers(0, 5, n)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=offs[1:])
    nbr = rng.integers(0, n, offs[-1]).astype(np.int32)
    dense = densify_csr(offs, nbr, n)
    assert dense.sum() <= offs[-1]  # duplicates collapse
    for u in range(n):
        for v in nbr[offs[u]:offs[u + 1]]:
            assert dense[u, v] == 1.0
