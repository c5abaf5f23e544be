"""Pallas TPU kernel: batched sorted-uint set intersection counts
(paper Section 4.2 ``UINT ∩ UINT``, SIMDShuffling side of Algorithm 2).

The CPU SIMDShuffling algorithm merges two sorted streams with cross-lane
shuffles; the TPU VPU has no cross-lane shuffle, so the adaptation is a
**membership test with one pair per lane**: the sets are laid out
transposed, so column ``p`` of a tile holds the elements of pair ``p``.
Each element row ``a[i, :]`` (one element of every pair in the tile) is
compared against the whole ``(lb_blk, LANE)`` tile of B with a sublane
broadcast, and a max over sublanes says which pairs hold that element.
Cost is O(LA * LB / 8) vreg ops per 128 pairs — the right regime for the
similar-cardinality sets this path handles (the 32:1 cardinality-skew
regime is routed to the lockstep binary search in ``core.intersect``,
which is the min-property / SIMDGalloping analogue).

Every block is 2-D with the pair axis on lanes, so the output is a
lane-dense ``(1, P)`` row: no rank-1 block, whose tiling Mosaic would
have to match against XLA's.

Shapes (transposed and padded by ops.py, sentinel = -1 which never
matches a valid id):

  a_t : [LA, P] int32, column p = sorted set A_p padded with -1
  b_t : [LB, P] int32, column p = sorted set B_p padded with -1
  out : [1, P]  int32 |A_p ∩ B_p|

Grid: (P / LANE, LB / lb_blk); the out block for pair-tile i is revisited
for every j, accumulating partial counts (init at j == 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, SUBLANE, cdiv


def _kernel(a_ref, b_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = b_ref[...]                                   # (lb_blk, LANE)

    def row(i, acc):
        ai = a_ref[pl.ds(i, 1), :]                   # (1, LANE)
        hit = jnp.max((ai == b).astype(jnp.int32), axis=0, keepdims=True)
        return acc + jnp.where(ai >= 0, hit, 0)

    out_ref[...] += lax.fori_loop(0, a_ref.shape[0], row,
                                  jnp.zeros(out_ref.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("lb_blk", "interpret"))
def uint_intersect_kernel(a_t, b_t, *, lb_blk: int = LANE,
                          interpret: bool = False):
    la, p = a_t.shape
    lb, _ = b_t.shape
    assert b_t.shape[1] == p
    assert p % LANE == 0 and la % SUBLANE == 0
    assert lb % lb_blk == 0 and lb_blk % SUBLANE == 0
    grid = (cdiv(p, LANE), cdiv(lb, lb_blk))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((la, LANE), lambda i, j: (0, i)),
            pl.BlockSpec((lb_blk, LANE), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, LANE), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.int32),
        interpret=interpret,
    )(a_t, b_t)
