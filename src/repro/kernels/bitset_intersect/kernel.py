"""Pallas TPU kernel: bitset AND + popcount (paper Section 4.2,
``BITSET ∩ BITSET``).

The paper's hot inner loop loads 256-bit AVX registers and ANDs them; the
TPU-native adaptation operates on (8, 128) int32 VREG tiles: one VPU op ANDs
8 * 128 * 32 = 32,768 set elements, two orders of magnitude wider than
AVX-256. Popcount is synthesized with the standard bit-twiddling sequence
(TPU exposes no popcnt instruction) — 11 int ops per word, amortized over the
lane width.

Inputs are the *pre-gathered* word rows of the matched blocks (the gather is
an XLA op in ops.py; see DESIGN.md §2 on why per-block scalar gathers are not
TPU-idiomatic). Shapes:

  wa, wb : [P, W] uint32  (W = words per bitset block, padded to 128 lanes)
  out    : [P]    int32   |a_i & b_i| summed over the W axis

The 1-D output block is 1024 rows: XLA tiles a rank-1 int32 array of
that length as ``T(1024)``, and Mosaic refuses an output block whose
layout does not match it (a 256-row block lowers as ``T(256)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, SUBLANE, cdiv


def _popcount_u32(x):
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _kernel(wa_ref, wb_ref, out_ref):
    """One grid step: AND a (rows, W) tile pair, popcount, row-reduce."""
    anded = wa_ref[...] & wb_ref[...]
    counts = _popcount_u32(anded)            # (rows, W) int32 on the VPU
    out_ref[...] = counts.sum(axis=1)        # lane reduction -> (rows,)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitset_and_popcount_kernel(wa, wb, *, block_rows: int = 1024,
                               interpret: bool = False):
    """``pallas_call`` wrapper; P padded to block_rows, W padded to LANE."""
    p, w = wa.shape
    assert wb.shape == (p, w)
    assert p % block_rows == 0 and w % LANE == 0, (p, w)
    assert block_rows % SUBLANE == 0
    grid = (cdiv(p, block_rows),)
    spec = pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((p,), jnp.int32),
        interpret=interpret,
    )(wa, wb)
