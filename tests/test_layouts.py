"""Layout optimizer + set-intersection properties (paper §4), with
hypothesis property tests on the core invariants."""
from functools import partial

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import hub_csr

from repro.core import intersect as I
from repro.core.layouts import (HybridSetStore, decide_relation_level,
                                decide_set_level, set_ranges)
from repro.core.trie import CSRGraph
from repro.kernels.bitset_intersect.ops import as_word_kernel


def random_csr(n, mean_deg, seed):
    rng = np.random.default_rng(seed)
    deg = rng.poisson(mean_deg, n)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, len(src))
    keep = src != dst
    return CSRGraph.from_edges(src[keep], dst[keep], n=n)


# ------------------------------------------------------------- decision rule
def test_algorithm3_rule():
    """bitset iff range/|S| < SIMD width (paper Algorithm 3)."""
    # dense set: 0..99 complete -> inverse density 1
    src = np.zeros(100, np.int64)
    dst = np.arange(100)
    csr = CSRGraph.from_edges(src, dst, n=100)
    d = decide_set_level(csr, threshold=256)
    assert 0 in d.dense_ids
    # sparse set: two values 10^6 apart
    csr2 = CSRGraph.from_edges(np.zeros(2, np.int64),
                               np.array([0, 10**6]), n=10**6 + 1)
    d2 = decide_set_level(csr2, threshold=256)
    assert 0 in d2.sparse_ids


def test_set_ranges(rng):
    csr = random_csr(50, 4, 0)
    r = set_ranges(csr)
    for u in range(csr.n):
        nb = csr.neighbors_of(u)
        want = (nb.max() - nb.min() + 1) if len(nb) else 0
        assert r[u] == want


def test_relation_level_is_all_one_layout():
    csr = random_csr(40, 3, 1)
    d = decide_relation_level(csr, force="uint")
    assert len(d.dense_ids) == 0


# ----------------------------------------------------- intersection oracles
@settings(max_examples=25, deadline=None)
@given(n=st.integers(10, 120), mean=st.floats(1, 12),
       seed=st.integers(0, 10_000), threshold=st.sampled_from([64, 256, 4096]))
def test_hybrid_store_matches_numpy(n, mean, seed, threshold):
    """Routing through any layout combination preserves exact counts —
    the system invariant behind the paper's Table 4 study."""
    csr = random_csr(n, mean, seed)
    rng = np.random.default_rng(seed + 1)
    u = rng.integers(0, n, 50)
    v = rng.integers(0, n, 50)
    store = HybridSetStore.build(csr, threshold=threshold)
    got = store.intersect_count(u, v)
    want = I.intersect_count_uint_np(csr.offsets, csr.neighbors, u, v)
    np.testing.assert_array_equal(got, want)


def test_engine_layout_modes_agree():
    """The engine's terminal fold routed through set/uint/off layout modes
    must produce identical counts (the -R ablation's invariant)."""
    from repro.core.engine import Engine
    from repro.core.layouts import set_engine_layout_mode

    rng = np.random.default_rng(9)
    n = 60
    a = rng.random((n, n)) < 0.2
    a = np.triu(a, 1)
    a = a | a.T
    src, dst = np.nonzero(a)
    counts = {}
    try:
        for mode in ("set", "uint", "off"):
            set_engine_layout_mode(mode)
            eng = Engine()
            eng.load_edges("Edge", src, dst)
            for al in ("R", "S", "T"):
                eng.alias(al, "Edge")
            counts[mode] = int(eng.query(
                "T(;w:long) :- R(x,y),S(y,z),T(x,z); w=<<COUNT(*)>>.")
                .scalar())
    finally:
        set_engine_layout_mode("set")
    assert counts["set"] == counts["uint"] == counts["off"]


def test_hybrid_store_with_pallas_kernel():
    csr = random_csr(200, 8, 3)
    rng = np.random.default_rng(4)
    u = rng.integers(0, 200, 100)
    v = rng.integers(0, 200, 100)
    store = HybridSetStore.build(csr,
                                 word_kernel=as_word_kernel(interpret=True))
    got = store.intersect_count(u, v)
    want = I.intersect_count_uint_np(csr.offsets, csr.neighbors, u, v)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=25, deadline=None)
@given(sa=st.integers(0, 60), sb=st.integers(0, 60), hi=st.integers(64, 2000),
       seed=st.integers(0, 1000))
def test_segment_search_min_property_oracle(sa, sb, hi, seed):
    """The lockstep search intersection equals numpy for arbitrary pairs."""
    rng = np.random.default_rng(seed)
    a = np.sort(rng.choice(hi, min(sa, hi), replace=False)).astype(np.int32)
    b = np.sort(rng.choice(hi, min(sb, hi), replace=False)).astype(np.int32)
    values = np.concatenate([a, b])
    offsets = np.array([0, len(a), len(a) + len(b)], dtype=np.int64)
    got = I.intersect_count_uint(offsets, values, np.array([0]),
                                 np.array([1]))[0]
    assert got == len(np.intersect1d(a, b))


def test_blocked_bitset_roundtrip(rng):
    csr = random_csr(80, 6, 5)
    ids = np.flatnonzero(csr.degrees > 0)[:20]
    bs = I.build_blocked_bitset(csr.offsets, csr.neighbors, ids, csr.n, 256)
    # popcount of all blocks of set i == degree(i) (sets are deduped)
    card = I.popcount_u32_np(bs.words).sum(axis=1)
    for slot, nid in enumerate(ids):
        lo, hi = bs.offsets[slot], bs.offsets[slot + 1]
        assert card[lo:hi].sum() == len(np.unique(csr.neighbors_of(nid)))


def test_uint_bitset_cross_layout(rng):
    csr = random_csr(100, 10, 6)
    d = decide_set_level(csr, threshold=4096)  # force many dense
    if len(d.dense_ids) == 0 or len(d.sparse_ids) == 0:
        pytest.skip("degenerate split")
    bs = I.build_blocked_bitset(csr.offsets, csr.neighbors, d.dense_ids,
                                csr.n, 256)
    u = d.sparse_ids[:10]
    v = d.dense_ids[:10][:len(u)]
    u = u[:len(v)]
    got = I.uint_bitset_intersect_count(csr.offsets, csr.neighbors, u, bs,
                                        bs.slot_of[v])
    want = I.intersect_count_uint_np(csr.offsets, csr.neighbors, u, v)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------- search bounded by its segments
def _segments(k: int, longest: int):
    """Segments of length 0, 1, 2^k - 1 and 2^k up to ``longest``, even
    values, probed below, at, between and above their elements."""
    lens = [n for n in (0, 1, 2**k - 1, 2**k, 2**k + 1) if n <= longest]
    values, lo, hi, q = [], [], [], []
    start = 0
    for j, n in enumerate(lens):
        seg = 1000 * (j + 1) + 2 * np.arange(n)
        probes = np.concatenate([[seg[0] - 1 if n else 1000 * (j + 1)],
                                 seg, seg + 1, [1000 * (j + 1) + 2 * n + 7]])
        values.append(seg)
        lo.append(np.full(len(probes), start))
        hi.append(np.full(len(probes), start + n))
        q.append(probes)
        start += n
    values = np.concatenate(values).astype(np.int32)
    lo, hi = np.concatenate(lo), np.concatenate(hi)
    q = np.concatenate(q).astype(np.int32)
    pos, found = I.segment_searchsorted(values, lo, hi, q,
                                        iters=max(lens).bit_length())
    want_pos, want_found = I.segment_searchsorted_np(values, lo, hi, q)
    return [(pos, want_pos), (found, want_found)]


def _pairs_oracle(offsets, neighbors, u, v):
    """Pair-major ``(pair_id, value, pos_u, pos_v)`` by np.intersect1d."""
    out = [[], [], [], []]
    for i, (a, b) in enumerate(zip(u, v)):
        vals, ia, ib = np.intersect1d(
            neighbors[offsets[a]:offsets[a + 1]],
            neighbors[offsets[b]:offsets[b + 1]], return_indices=True)
        for col, x in zip(out, (np.full(len(vals), i), vals,
                                offsets[a] + ia, offsets[b] + ib)):
            col.append(x)
    return [np.concatenate(c) for c in out]


def _callers(name: str):
    """One ``_search`` caller on ``hub_csr``, whose longest set has 64
    elements over 8 blocks: both bounds are powers of two."""
    csr = hub_csr()
    off, nb = csr.offsets, csr.neighbors
    bs = I.build_blocked_bitset(off, nb, np.flatnonzero(csr.degrees > 0),
                                csr.n, 256)
    assert (np.diff(off).max(), np.diff(bs.offsets).max()) == (64, 8)
    rng = np.random.default_rng(5)
    u = np.concatenate([[0, 0, 7, 300], rng.integers(0, 260, 60)])
    v = np.concatenate([[3, 0, 0, 0], rng.integers(0, 260, 60)])
    # the bitset holds the non-empty sets only
    in_bs = csr.degrees[v] > 0
    if name.startswith("bitset"):
        in_bs &= csr.degrees[u] > 0
    if name != "intersect_count_uint" and name != "intersect_pairs_uint":
        u, v = u[in_bs], v[in_bs]
    if name == "intersect_count_uint":
        got = I.intersect_count_uint(off, nb, u, v)
    elif name == "bitset_intersect_count":
        got = I.bitset_intersect_count(bs, bs.slot_of[u], bs.slot_of[v])
    elif name == "uint_bitset_intersect_count":
        got = I.uint_bitset_intersect_count(off, nb, u, bs, bs.slot_of[v])
    else:
        if name == "intersect_pairs_uint":
            got = I.intersect_pairs_uint(off, nb, u, v)
        else:
            pid, vals, ra, rb = I.bitset_intersect_materialize(
                bs, bs.slot_of[u], bs.slot_of[v])
            got = (pid, vals, off[u[pid]] + ra, off[v[pid]] + rb)
        return list(zip(got, _pairs_oracle(off, nb, u, v)))
    return [(got, I.intersect_count_uint_np(off, nb, u, v))]


BOUNDED_SEARCH_CASES = {
    "segments-2^1": lambda: _segments(1, 2),
    "segments-2^4": lambda: _segments(4, 16),
    "segments-2^4+1": lambda: _segments(4, 17),
    "segments-2^6+1": lambda: _segments(6, 65),
    **{name: partial(_callers, name) for name in (
        "intersect_count_uint", "intersect_pairs_uint",
        "bitset_intersect_count", "bitset_intersect_materialize",
        "uint_bitset_intersect_count")},
}


@pytest.mark.parametrize("case", list(BOUNDED_SEARCH_CASES))
def test_search_bounded_by_the_longest_segment_is_exact(case):
    """``bit_length(longest segment)`` steps give the same ``pos`` and
    ``found`` as a full search, and every pair path that searches with
    that bound equals its numpy oracle."""
    for got, want in BOUNDED_SEARCH_CASES[case]():
        np.testing.assert_array_equal(np.asarray(got), want)
