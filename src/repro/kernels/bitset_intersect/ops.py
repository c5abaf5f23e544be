"""Jit'd public wrapper for the bitset-intersect kernel.

``bitset_and_popcount(words, pos_a, pos_b)`` is the drop-in ``word_kernel``
for :class:`repro.core.layouts.HybridSetStore`: it gathers the matched block
rows (XLA gather), pads to hardware tile geometry, and runs the Pallas
AND+popcount kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.kernels.bitset_intersect.kernel import bitset_and_popcount_kernel
from repro.kernels.common import LANE, interpret_default, round_up

_BLOCK_ROWS = 1024   # the kernel's 1-D output block (see kernel.py)
# Block pairs per launch.  Each gathered row is padded to 128 lanes
# (512 B whatever the block width), so a launch's two operands take
# 2 x _MAX_PAIRS x 512 B = 1 GiB of HBM; an unchunked call at Kronecker
# scale 17 (6.5e7 block pairs) would need 33 GiB and fail to allocate.
_MAX_PAIRS = 1 << 20


def bitset_and_popcount(words, pos_a, pos_b, *, interpret=None):
    """out[i] = |block[pos_a[i]] & block[pos_b[i]]| (popcount of the AND).

    words : [B, W] uint32 bitvector blocks
    pos_a, pos_b : [P] int indices into the block table
    """
    if interpret is None:
        interpret = interpret_default()
    trace.upload(words, pos_a, pos_b)
    words = jnp.asarray(words)
    pos_a = jnp.asarray(pos_a)
    pos_b = jnp.asarray(pos_b)
    p = pos_a.shape[0]
    if p == 0:
        return jnp.zeros((0,), jnp.int32)
    w = words.shape[1]
    wpad = round_up(max(w, LANE), LANE)
    outs = []
    for lo in range(0, p, _MAX_PAIRS):
        pa = pos_a[lo:lo + _MAX_PAIRS]
        pb = pos_b[lo:lo + _MAX_PAIRS]
        n = pa.shape[0]
        ppad = round_up(n, _BLOCK_ROWS)
        wa = jnp.zeros((ppad, wpad), jnp.uint32).at[:n, :w].set(words[pa])
        wb = jnp.zeros((ppad, wpad), jnp.uint32).at[:n, :w].set(words[pb])
        out = bitset_and_popcount_kernel(wa, wb, block_rows=_BLOCK_ROWS,
                                         interpret=interpret)
        outs.append(out[:n])
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def as_word_kernel(interpret=None):
    """Adapter matching HybridSetStore's ``word_kernel`` callable."""
    def fn(words, pos_a, pos_b):
        return np.asarray(bitset_and_popcount(words, pos_a, pos_b,
                                              interpret=interpret))
    return fn


def _contract_inputs():
    rng = np.random.default_rng(0)
    words = rng.integers(0, 1 << 32, size=(6, 4), dtype=np.uint32)
    return (words, np.array([0, 2, 4], np.int64),
            np.array([1, 3, 5], np.int64))


def _contract_ref(words, pos_a, pos_b):
    from repro.kernels.bitset_intersect.ref import bitset_and_popcount_ref
    w = jnp.asarray(words)
    return bitset_and_popcount_ref(w[jnp.asarray(pos_a)],
                                   w[jnp.asarray(pos_b)])


# Static contract (see repro.analysis.kernel_check.check_contract).
CONTRACT = {
    "name": "bitset_intersect",
    "entry": lambda w, a, b: bitset_and_popcount(w, a, b, interpret=True),
    "ref": _contract_ref,
    "make_inputs": _contract_inputs,
}


def bitset_pair_count(bs, a_slots, b_slots, *, interpret=None,
                      word_kernel=None) -> np.ndarray:
    """Batched cohort entry point: |S_a ∩ S_b| for slot pairs of one
    :class:`~repro.core.intersect.BlockedBitset` cohort — block-id
    intersection (uint machinery) followed by the Pallas AND+popcount
    kernel over all matched blocks in one launch. Pass a prebuilt
    ``word_kernel`` (from :func:`as_word_kernel`) to reuse the adapter
    across calls."""
    from repro.core.intersect import bitset_intersect_count  # avoid cycle
    if word_kernel is None:
        word_kernel = as_word_kernel(interpret)
    return bitset_intersect_count(bs, np.asarray(a_slots),
                                  np.asarray(b_slots),
                                  word_and_popcount=word_kernel)
