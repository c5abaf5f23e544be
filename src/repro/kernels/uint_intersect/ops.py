"""Jit'd public wrapper for the uint-intersect kernel.

Takes ragged CSR pairs, pads the gathered neighbor sets to tile geometry,
and runs the Pallas membership-test kernel. Used by the execution engine for
similar-cardinality sparse-set batches (the SIMDShuffling regime); the
cardinality-skewed regime stays on the lockstep binary search.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.kernels.common import (LANE, SUBLANE, interpret_default,
                                  round_up)
from repro.kernels.uint_intersect.kernel import uint_intersect_kernel


def uint_intersect_count(a, b, *, interpret=None):
    """Counts for already-padded batches a [P, LA], b [P, LB] (pad = -1)."""
    if interpret is None:
        interpret = interpret_default()
    trace.upload(a, b)
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    p, la = a.shape
    lb = b.shape[1]
    if p == 0:
        return jnp.zeros((0,), jnp.int32)
    # the kernel puts one pair per lane: transpose, pad pairs to LANE and
    # set elements to the sublane tile (B to whole lb_blk blocks)
    ppad = round_up(p, LANE)
    lapad = round_up(max(la, 1), SUBLANE)
    lb_blk = min(LANE, round_up(max(lb, 1), SUBLANE))
    lbpad = round_up(max(lb, 1), lb_blk)
    a_t = jnp.full((lapad, ppad), -1, jnp.int32).at[:la, :p].set(a.T)
    b_t = jnp.full((lbpad, ppad), -1, jnp.int32).at[:lb, :p].set(b.T)
    out = uint_intersect_kernel(a_t, b_t, lb_blk=lb_blk,
                                interpret=interpret)
    return out[0, :p]


def intersect_count_csr(offsets, neighbors, u, v, *, interpret=None,
                        max_len: int = 512):
    """CSR front-end: gather + pad N(u_i), N(v_i) then run the kernel.

    Pairs whose min-degree exceeds ``max_len`` should be routed to the
    search path by the caller; here they are asserted against.
    """
    return intersect_count_csr_batched(offsets, neighbors, u, v,
                                       interpret=interpret, max_len=max_len)


def _gather_pad(offsets: np.ndarray, neighbors: np.ndarray,
                ids: np.ndarray, width: int) -> np.ndarray:
    """Vectorized ragged gather: rows = N(ids[i]) right-padded with -1."""
    deg = np.diff(offsets)[ids]
    rows = np.repeat(np.arange(len(ids), dtype=np.int64), deg)
    seg_start = np.repeat(np.cumsum(deg) - deg, deg)
    local = np.arange(len(rows), dtype=np.int64) - seg_start
    elem = np.repeat(offsets[ids], deg) + local
    out = np.full((len(ids), width), -1, np.int32)
    out[rows, local] = neighbors[elem]
    return out


def _contract_inputs():
    """Representative padded batches for the kernel contract checker
    (``repro.analysis.kernel_check``): ragged sorted rows, -1 padded."""
    rng = np.random.default_rng(0)
    def rows(p, width, universe):
        out = np.full((p, width), -1, np.int32)
        for i in range(p):
            k = int(rng.integers(1, width + 1))
            out[i, :k] = np.sort(rng.choice(universe, size=k, replace=False))
        return out
    # universe small enough that some rows MUST intersect — an all-zero
    # count vector would make the numeric oracle cross-check vacuous
    a, b = rows(5, 7, 12), rows(5, 9, 12)
    assert int(np.sum([len(np.intersect1d(r[r >= 0], s[s >= 0]))
                       for r, s in zip(a, b)])) > 0
    return a, b


def _contract_ref(a, b):
    from repro.kernels.uint_intersect.ref import uint_intersect_count_ref
    return uint_intersect_count_ref(jnp.asarray(a, jnp.int32),
                                    jnp.asarray(b, jnp.int32))


# Static contract (see repro.analysis.kernel_check.check_contract): the
# dispatch entry point, the pure-jnp oracle it must agree with, and
# inputs that exercise the padding paths.
CONTRACT = {
    "name": "uint_intersect",
    "entry": lambda a, b: uint_intersect_count(a, b, interpret=True),
    "ref": _contract_ref,
    "make_inputs": _contract_inputs,
}


def intersect_count_csr_batched(offsets, neighbors, u, v, *, interpret=None,
                                max_len: int = 512) -> np.ndarray:
    """Batched cohort entry point for the execution backend: one
    vectorized gather+pad (no per-pair Python loop) and ONE kernel launch
    for the whole sparse-cohort batch. Same contract as
    :func:`intersect_count_csr` — the caller routes pairs whose larger
    set exceeds ``max_len`` to the search path."""
    offsets = np.asarray(offsets)
    neighbors = np.asarray(neighbors)
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    if len(u) == 0:
        return np.zeros(0, np.int64)
    deg = np.diff(offsets)
    la = int(max(1, deg[u].max()))
    lb = int(max(1, deg[v].max()))
    assert max(la, lb) <= max_len, "route long sets to the search path"
    with trace.span("eh.pairs.expand"):
        a = _gather_pad(offsets, neighbors, u, la)
        b = _gather_pad(offsets, neighbors, v, lb)
    return np.asarray(uint_intersect_count(a, b, interpret=interpret),
                      np.int64)
