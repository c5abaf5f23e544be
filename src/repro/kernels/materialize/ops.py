"""Jit'd public wrapper for the materializing bitset-intersection kernel.

``bitset_pair_materialize(bs, a_slots, b_slots)`` is the device twin of
:func:`repro.core.intersect.bitset_intersect_materialize`: same contract
(``(pair_id, values, rank_a, rank_b)``, pair-major, values ascending),
but the AND + rank arithmetic runs on device in ONE fused jitted call —
block-row gather, uint32→bit expansion, Pallas AND + triangular-matmul
ranks — and the ragged extraction is a device count-then-fill
(``_extract_pairs``): set bits scatter to a dense prefix sized by the
exact ``p * block_bits`` bound, so the single closing ``host_get``
carries already-compacted positions and ranks.  The host ``np.nonzero``
pass (and its full-plane transfer) is gone.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.kernels.common import host_get, interpret_default, round_up
from repro.kernels.materialize.kernel import bitset_materialize_kernel

_BLOCK_ROWS = 256

# strictly-upper-triangular ones (tri[s, t] = 1 iff s < t) per block_bits
_TRI_CACHE: Dict[int, jnp.ndarray] = {}


def _tri(block_bits: int) -> jnp.ndarray:
    t = _TRI_CACHE.get(block_bits)
    if t is None:
        t = jnp.asarray(np.triu(np.ones((block_bits, block_bits),
                                        np.float32), 1))
        _TRI_CACHE[block_bits] = t
    return t


@partial(jax.jit, static_argnames=("block_bits", "interpret"))
def _gather_expand_rank(words, pos_a, pos_b, tri, *, block_bits: int,
                        interpret: bool):
    """Gather matched block rows, expand words to bit planes, run the
    Pallas kernel. One device program — callers sync exactly once."""
    p = pos_a.shape[0]
    wpb = words.shape[1]
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, wpb, 32), 2)

    def expand(pos):
        w = words[pos]                                   # [P, wpb] uint32
        bits = (w[:, :, None] >> shifts) & jnp.uint32(1)
        return bits.reshape(p, wpb * 32).astype(jnp.int32)

    ppad = round_up(max(p, _BLOCK_ROWS), _BLOCK_ROWS)
    ba = jnp.zeros((ppad, block_bits), jnp.int32).at[:p].set(expand(pos_a))
    bb = jnp.zeros((ppad, block_bits), jnp.int32).at[:p].set(expand(pos_b))
    band, ra, rb = bitset_materialize_kernel(
        ba, bb, tri, block_rows=_BLOCK_ROWS, interpret=interpret)
    return band[:p], ra[:p], rb[:p]


def _device_words(bs) -> jnp.ndarray:
    """Device-resident copy of the cohort's bitvector blocks, uploaded
    once and cached on the BlockedBitset (identity-keyed, the
    ``TrieLevel.device_values`` idiom)."""
    cached = bs.__dict__.get("_dev_words")
    if cached is None or cached[0] is not bs.words:
        trace.upload(bs.words)
        cached = (bs.words, jnp.asarray(bs.words))
        bs._dev_words = cached
    return cached[1]


def bitset_pair_materialize(bs, a_slots, b_slots, *, interpret=None):
    """Materializing dense-cohort intersection via the Pallas kernel.

    ``bs`` is a :class:`repro.core.intersect.BlockedBitset`; slots index
    its cohort. Matches :func:`~repro.core.intersect.
    bitset_intersect_materialize` bit-for-bit (same values, same ranks,
    same order).
    """
    from repro.core.intersect import intersect_pairs_uint  # avoid cycle
    if interpret is None:
        interpret = interpret_default()
    a_slots = np.asarray(a_slots, np.int64)
    b_slots = np.asarray(b_slots, np.int64)
    pair_id, _blk, pos_a, pos_b = intersect_pairs_uint(
        bs.offsets, bs.block_ids, a_slots, b_slots)
    z = np.zeros(0, np.int64)
    if len(pair_id) == 0:
        return z, np.zeros(0, np.int32), z, z
    trace.upload(pos_a, pos_b)
    band, ra, rb = _gather_expand_rank(
        _device_words(bs), jnp.asarray(pos_a), jnp.asarray(pos_b),
        _tri(bs.block_bits), block_bits=bs.block_bits,
        interpret=bool(interpret))
    # device count-then-fill extraction (the exact p*block_bits bound
    # sizes the scatter, so it cannot overflow), then the ONE host
    # round-trip of already-compacted positions and ranks
    total, pos_c, ra_c, rb_c = _extract_pairs(band, ra, rb)
    total, pos_h, ra_h, rb_h = host_get((total, pos_c, ra_c, rb_c))
    n = int(total)
    with trace.span("eh.pairs.expand"):
        pos_h = np.asarray(pos_h)[:n].astype(np.int64)
        blk_row = pos_h // bs.block_bits
        bitpos = pos_h % bs.block_bits
        vals = (bs.block_ids[pos_a[blk_row]].astype(np.int64)
                * bs.block_bits + bitpos)
        rank_a = bs.index[pos_a[blk_row]] + np.asarray(ra_h)[:n]
        rank_b = bs.index[pos_b[blk_row]] + np.asarray(rb_h)[:n]
    return (pair_id[blk_row], vals.astype(np.int32),
            rank_a.astype(np.int64), rank_b.astype(np.int64))


@jax.jit
def _extract_pairs(band, ra, rb):
    """Compact the AND-ed bit plane's set bits to a dense prefix on
    device: flatten row-major (so (block-row, bit) order — hence pair-
    major, values-ascending — survives), exclusive-scan the mask into
    scatter targets, and gather each match's flat position and both
    ranks.  Replaces the host ``np.nonzero`` ragged extraction."""
    cap = band.size
    flat = band.reshape(-1) > 0
    widx = jnp.cumsum(flat.astype(jnp.int32)) - 1
    total = widx[-1] + 1
    scat = jnp.where(flat, widx, cap)
    j = jnp.arange(cap, dtype=jnp.int32)

    def compact(x):
        return jnp.zeros((cap,), x.dtype).at[scat].set(x, mode="drop")

    return (total, compact(j), compact(ra.reshape(-1)),
            compact(rb.reshape(-1)))


def _contract_inputs():
    rng = np.random.default_rng(0)
    p, b = _BLOCK_ROWS, 128   # one full tile: the raw kernel's minimum
    ba = rng.integers(0, 2, size=(p, b)).astype(np.int32)
    bb = rng.integers(0, 2, size=(p, b)).astype(np.int32)
    return ba, bb


def _contract_entry(ba, bb):
    return bitset_materialize_kernel(
        jnp.asarray(ba), jnp.asarray(bb), _tri(ba.shape[1]),
        block_rows=_BLOCK_ROWS, interpret=True)


def _contract_ref(ba, bb):
    from repro.kernels.materialize.ref import bitset_materialize_ref
    return bitset_materialize_ref(jnp.asarray(ba), jnp.asarray(bb))


# Static contract (see repro.analysis.kernel_check.check_contract): the
# raw kernel wrapper (the ragged host extraction above it needs a live
# BlockedBitset) against the pure-jnp band/rank oracle.
CONTRACT = {
    "name": "materialize",
    "entry": _contract_entry,
    "ref": _contract_ref,
    "make_inputs": _contract_inputs,
}


def as_materialize_kernel(interpret=None):
    """Adapter matching HybridSetStore's ``materialize_kernel`` callable
    (``(bs, a_slots, b_slots) -> (pair_id, values, rank_a, rank_b)``)."""
    def fn(bs, a_slots, b_slots):
        return bitset_pair_materialize(bs, a_slots, b_slots,
                                       interpret=interpret)
    return fn
