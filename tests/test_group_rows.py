"""``core.gj.group_rows``: the distinct key rows and the inverse of a
projection or a count-distinct aggregate.

Each case compares the routine with
``np.unique(np.stack(cols, 1), axis=0, return_inverse=True)``: the same
distinct rows in the same order, each column in its own dtype, and the
same 1-D inverse.  ``group.presorted_rows`` counts exactly the rows that
came already in order and were grouped without a sort.
"""
import collections

import numpy as np
import pytest

from repro import trace
from repro.core.gj import group_rows


def rows(order, dups, ncols, dtype, m=300, seed=0):
    """``m`` key rows of ``ncols`` columns with negative values, few
    distinct ones where ``dups``, in the given row order."""
    rng = np.random.default_rng(seed)
    hi = 3 if dups else 1_000_000
    stacked = rng.integers(-hi, hi + 1, (m, ncols)).astype(dtype)
    if order != "shuffled":
        stacked = stacked[np.lexsort(stacked.T[::-1])]
        if order == "descending":
            stacked = stacked[::-1]
    return [np.ascontiguousarray(stacked[:, i]) for i in range(ncols)]


def grouped(cols):
    stats = collections.Counter()
    uniq, inv = group_rows(cols, stats)
    return uniq, inv, stats


def assert_like_unique(cols, uniq, inv):
    want_uniq, want_inv = np.unique(np.stack(cols, 1), axis=0,
                                    return_inverse=True)
    assert len(uniq) == len(cols)
    for c, u in zip(cols, uniq):
        assert u.dtype == c.dtype and u.ndim == 1
    assert np.array_equal(np.stack(uniq, 1).reshape(want_uniq.shape),
                          want_uniq)
    assert inv.shape == want_inv.shape == (len(cols[0]),)
    assert inv.dtype == want_inv.dtype
    assert np.array_equal(inv, want_inv)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("ncols", [1, 2, 3])
@pytest.mark.parametrize("dups", [False, True])
@pytest.mark.parametrize("order", ["ascending", "descending", "shuffled"])
def test_group_rows_matches_np_unique(order, dups, ncols, dtype):
    cols = rows(order, dups, ncols, dtype)
    uniq, inv, stats = grouped(cols)
    assert_like_unique(cols, uniq, inv)
    assert stats["group.rows"] == len(cols[0])
    presorted = len(cols[0]) if order == "ascending" else 0
    assert stats["group.presorted_rows"] == presorted


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("ncols", [1, 2, 3])
@pytest.mark.parametrize("m", [0, 1])
def test_group_rows_empty_and_single_row(m, ncols, dtype):
    cols = [np.arange(-m, 0, dtype=dtype) for _ in range(ncols)]
    uniq, inv, stats = grouped(cols)
    assert_like_unique(cols, uniq, inv)
    assert stats["group.rows"] == stats["group.presorted_rows"] == m


@pytest.mark.parametrize("cols, presorted", [
    # the first column in order, the second out of order within a tie
    (([0, 0, 1], [2, 1, 0]), False),
    (([0, 0, 1], [1, 2, 0]), True),
    # a later column out of order where an earlier one already orders
    (([-5, 0, 1], [7, -3, -9], [0, 0, 0]), True),
    (([0, 0, 0], [1, 1, 1], [4, 4, 2]), False),
    (([2, 2, 2], [3, 3, 3]), True),
])
def test_group_rows_orders_by_the_first_differing_column(cols, presorted):
    cols = [np.asarray(c, np.int32) for c in cols]
    uniq, inv, stats = grouped(cols)
    assert_like_unique(cols, uniq, inv)
    assert stats["group.presorted_rows"] == (len(cols[0]) if presorted
                                             else 0)


def test_group_rows_runs_inside_its_span():
    stats = collections.Counter()
    with trace.span("outer", stats):
        group_rows([np.array([1, 1, 2], np.int32)])
    assert stats["span.eh.group.calls"] == 1
    assert stats["span.outer.ns"] >= stats["span.eh.group.ns"] > 0
    assert stats["group.rows"] == stats["group.presorted_rows"] == 3
