"""Plain-jnp fill chunk: the engine's default fill stage and the oracle
for the frontier-fill kernel.

The body of ``backend._extend_body``'s morsel ``while_loop``: invert the
exclusive-scan offsets back to source frontier rows, gather the seed
values, and probe every other constraining atom with the branch-free
lockstep search.  It is plain XLA, which the TPU compiler accepts (the
Pallas kernel in this package is refused there).  All arithmetic is
int32 and every comparison is integral, so the Pallas kernel's outputs
must match this path BIT-EXACTLY — ``kernel_check`` enforces equality,
and ``REPRO_FRONTIER_FILL=pallas`` runs the kernel in its place.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp

from repro.core import intersect as I


def fill_chunk_ref(c, total_c, offs, lo0, seed_values,
                   probes: Sequence[Tuple], *, morsel: int):
    """One morsel chunk of the count-then-fill expansion.

    ``probes`` lists ``(values_k, lo_k, hi_k)`` per probe atom with the
    per-ROW candidate bounds (``lo_k``/``hi_k`` are indexed by the
    recovered source row, not by output slot).  Returns
    ``(vals, row, p0, keep, poss)`` — the chunk's candidate values,
    source rows, absolute seed positions, combined liveness+membership
    mask, and each probe atom's absolute positions.
    """
    offs = jnp.asarray(offs)
    lo0 = jnp.asarray(lo0)
    seed_values = jnp.asarray(seed_values)
    probes = tuple((jnp.asarray(v), jnp.asarray(lo), jnp.asarray(hi))
                   for v, lo, hi in probes)
    cap_in = offs.shape[0]
    n0 = seed_values.shape[0]
    c = jnp.asarray(c, jnp.int32)
    j = c * morsel + jnp.arange(morsel, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(offs, j, side="right") - 1,
                   0, cap_in - 1).astype(jnp.int32)
    p0 = lo0[row] + (j - offs[row])
    live = j < total_c
    vals = seed_values[jnp.clip(p0, 0, max(n0 - 1, 0))]
    keep = live
    poss = []
    for vals_k, lo_k, hi_k in probes:
        pk, fk = I.segment_searchsorted(vals_k, lo_k[row], hi_k[row],
                                        vals)
        poss.append(pk.astype(jnp.int32))
        keep = keep & fk
    return vals, row, p0, keep, tuple(poss)
