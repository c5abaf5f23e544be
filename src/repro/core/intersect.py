"""Set-intersection operators (paper Section 4.2, Appendix B.2).

EmptyHeaded's profiling showed >95% of WCOJ runtime is set intersection, so
this module is the execution engine's hot path. Three intersection kinds are
implemented, mirroring the paper:

  * ``uint \\cap uint``   — vectorized binary-search intersection. On CPU-SIMD
    the paper switches SIMDShuffling <-> SIMDGalloping at a 32:1 cardinality
    ratio (Algorithm 2). The TPU VPU has no cross-lane shuffle, so the
    galloping side is adapted as a *lockstep branch-free binary search* of the
    smaller set into the larger (cost ∝ |smaller| * log|larger| — satisfies
    the **min property** of Section 2.1, preserving worst-case optimality).
  * ``bitset \\cap bitset`` — intersect block offsets (as uint sets), then AND
    the matched 2^k-bit blocks and popcount. The AND+popcount inner loop is
    the Pallas kernel in ``repro.kernels.bitset_intersect``.
  * ``uint \\cap bitset``  — probe each uint element into the bitset blocks;
    result is stored as uint ("at most as dense as the sparser set").

Pure-numpy twins (`*_np`) serve as oracles for tests and for the Pallas
kernels' ``ref.py`` modules.

The host arithmetic of the pair paths (expansion before a search, index
arithmetic after it) runs inside ``eh.pairs.expand`` spans
(``repro.trace``); each search counts its lanes (``pairs.searched``),
its matches (``pairs.found``) and its operands (``upload.bytes``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace

# Ratio at which Algorithm 2 switches to the min-property search algorithm.
GALLOP_RATIO = 32


# ----------------------------------------------------------------- popcount
def popcount_u32(x):
    """Branch-free popcount over uint32 lanes (TPU has no popcnt op)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def popcount_u32_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> 24).astype(np.int32)


# ------------------------------------------------- branch-free segment search
@partial(jax.jit, static_argnames=("iters",))
def segment_searchsorted(values, lo, hi, queries, iters: int = 34):
    """For each i: insertion index of queries[i] in sorted values[lo[i]:hi[i]].

    Branch-free lockstep binary search: all lanes run the same log-step loop
    (the TPU adaptation of SIMDGalloping). Returns (pos, found) where ``pos``
    is the insertion point (absolute index into ``values``) and ``found`` says
    values[pos] == query (within the segment).
    """
    values = jnp.asarray(values)
    size = values.shape[0]
    idx_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    lo = jnp.asarray(lo).astype(idx_dtype)
    hi0 = jnp.asarray(hi).astype(idx_dtype)
    q = jnp.asarray(queries)

    def body(_, state):
        lo_, hi_ = state
        mid = (lo_ + hi_) >> 1
        v = values[jnp.clip(mid, 0, size - 1)]
        open_ = lo_ < hi_
        right = v < q
        new_lo = jnp.where(open_ & right, mid + 1, lo_)
        new_hi = jnp.where(open_ & (~right), mid, hi_)
        return new_lo, new_hi

    lo_f, _ = jax.lax.fori_loop(0, iters, body, (lo, hi0))
    in_range = lo_f < hi0
    at = jnp.clip(lo_f, 0, size - 1)
    found = in_range & (values[at] == q)
    return lo_f, found


def _search(values, lo, hi, queries):
    """``segment_searchsorted`` for the host pair paths: counts the
    operands it uploads and its lanes, and returns ``found`` on the host
    (``pos`` stays on the device)."""
    trace.upload(values, lo, hi, queries)
    pos, found = segment_searchsorted(values, lo, hi, queries)
    found = np.asarray(found)
    trace.add("pairs.searched", len(found))
    trace.add("pairs.found", np.count_nonzero(found))
    return pos, found


def segment_searchsorted_np(values, lo, hi, queries):
    """Numpy oracle for segment_searchsorted (loop over queries)."""
    pos = np.empty(len(queries), dtype=np.int64)
    found = np.zeros(len(queries), dtype=bool)
    for i, (l, h, q) in enumerate(zip(lo, hi, queries)):
        p = l + np.searchsorted(values[l:h], q)
        pos[i] = p
        found[i] = p < h and values[p] == q
    return pos, found


# --------------------------------------------------------- uint ∩ uint pairs
def _expand_smaller(offsets: np.ndarray, neighbors: np.ndarray,
                    u: np.ndarray, v: np.ndarray):
    """Expansion step: for each pair (u_i, v_i) pick the smaller endpoint set
    (min property) and flatten its elements, remembering the pair id and the
    search segment of the larger set."""
    deg = np.diff(offsets)
    du, dv = deg[u], deg[v]
    swap = du > dv
    small = np.where(swap, v, u)
    large = np.where(swap, u, v)
    cnt = deg[small]
    pair_id = np.repeat(np.arange(len(u), dtype=np.int64), cnt)
    # element indices within each small set
    starts = offsets[small]
    base = np.repeat(starts, cnt)
    local = np.arange(len(pair_id), dtype=np.int64)
    seg_start = np.repeat(np.concatenate([[0], np.cumsum(cnt)])[:-1], cnt)
    elem_idx = base + (local - seg_start)
    q = neighbors[elem_idx]
    lo = offsets[large][pair_id]
    hi = offsets[large + 1][pair_id]
    return pair_id, elem_idx, q, lo, hi


def intersect_count_uint(offsets: np.ndarray, neighbors: np.ndarray,
                         u: np.ndarray, v: np.ndarray,
                         chunk: int = 1 << 22) -> np.ndarray:
    """|N(u_i) ∩ N(v_i)| for each pair, CSR inputs; hybrid search algorithm.

    Host-side expansion (data-dependent sizes) + device lockstep search.
    Processes in chunks to bound memory (sum of min-degrees can be large).
    """
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    out = np.zeros(len(u), dtype=np.int64)
    if len(u) == 0:
        return out
    trace.upload(neighbors)
    values_dev = jnp.asarray(neighbors)
    with trace.span("eh.pairs.expand"):
        pair_id, _, q, lo, hi = _expand_smaller(offsets, neighbors, u, v)
    for s in range(0, len(pair_id), chunk):
        e = min(s + chunk, len(pair_id))
        _, found = _search(values_dev, lo[s:e], hi[s:e], q[s:e])
        with trace.span("eh.pairs.expand"):
            np.add.at(out, pair_id[s:e], found.astype(np.int64))
    return out


def intersect_pairs_uint(offsets: np.ndarray, neighbors: np.ndarray,
                         u: np.ndarray, v: np.ndarray):
    """Materializing variant: returns (pair_id, value, pos_u, pos_v) for every
    element of N(u_i) ∩ N(v_i). Positions are absolute indices into
    ``neighbors`` for descent into deeper trie levels."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    if len(u) == 0:
        z = np.zeros(0, np.int64)
        return z, np.zeros(0, np.int32), z, z
    with trace.span("eh.pairs.expand"):
        deg = np.diff(offsets)
        swap = deg[u] > deg[v]
        pair_id, elem_idx, q, lo, hi = _expand_smaller(offsets, neighbors,
                                                       u, v)
    pos, found = _search(neighbors, lo, hi, q)
    pos = np.asarray(pos)
    with trace.span("eh.pairs.expand"):
        keep = found
        pair_id = pair_id[keep]
        vals = q[keep]
        small_pos = elem_idx[keep]
        large_pos = pos[keep]
        sw = swap[pair_id]
        pos_u = np.where(sw, large_pos, small_pos)
        pos_v = np.where(sw, small_pos, large_pos)
    return pair_id, vals, pos_u, pos_v


def intersect_count_uint_np(offsets, neighbors, u, v):
    """Numpy oracle (np.intersect1d per pair)."""
    out = np.zeros(len(u), dtype=np.int64)
    for i, (a, b) in enumerate(zip(u, v)):
        na = neighbors[offsets[a]:offsets[a + 1]]
        nb = neighbors[offsets[b]:offsets[b + 1]]
        out[i] = len(np.intersect1d(na, nb, assume_unique=True))
    return out


# -------------------------------------------------------------- blocked bitset
@dataclasses.dataclass
class BlockedBitset:
    """Paper Figure 6: a set is (offsets, bitvector-blocks, indices).

    ``block_ids`` play the role of the paper's offsets o_1..o_n (stored as a
    uint set, intersected with the uint algorithm); ``words`` are the
    bitvector blocks b_1..b_n; ``index`` mirrors the paper's i_1..i_n
    (cumulative cardinality before each block — used to address associated
    values / next-trie-level pointers).
    """

    block_bits: int
    set_ids: np.ndarray     # [S] original ids in this cohort, sorted
    offsets: np.ndarray     # [S+1] CSR over blocks
    block_ids: np.ndarray   # [B] int32 block numbers, sorted per set
    words: np.ndarray       # [B, block_bits//32] uint32
    index: np.ndarray       # [B] int64 cumulative cardinality before block
    slot_of: np.ndarray     # [n_ids] int32 -> slot in set_ids, or -1

    @property
    def words_per_block(self) -> int:
        return self.block_bits // 32

    def nbytes(self) -> int:
        return (self.block_ids.nbytes + self.words.nbytes + self.index.nbytes
                + self.offsets.nbytes + self.set_ids.nbytes)


def build_blocked_bitset(offsets: np.ndarray, neighbors: np.ndarray,
                         ids: np.ndarray, n_total: int,
                         block_bits: int = 256) -> BlockedBitset:
    """Render the neighbor sets of ``ids`` into the blocked-bitset layout."""
    ids = np.asarray(ids, dtype=np.int64)
    wpb = block_bits // 32
    deg = np.diff(offsets)
    cnt = deg[ids] if len(ids) else np.zeros(0, np.int64)
    set_idx = np.repeat(np.arange(len(ids), dtype=np.int64), cnt)
    starts = offsets[ids] if len(ids) else np.zeros(0, np.int64)
    base = np.repeat(starts, cnt)
    local = np.arange(len(set_idx), dtype=np.int64)
    seg_start = np.repeat(np.concatenate([[0], np.cumsum(cnt)])[:-1], cnt)
    elems = neighbors[base + (local - seg_start)].astype(np.int64)

    blk = elems // block_bits
    bit = elems % block_bits
    key = set_idx * ((n_total // block_bits) + 2) + blk
    uniq_key, block_of_elem = np.unique(key, return_inverse=True)
    n_blocks = len(uniq_key)
    words = np.zeros((n_blocks, wpb), dtype=np.uint32)
    w_idx = bit // 32
    mask = (np.uint32(1) << (bit % 32).astype(np.uint32)).astype(np.uint32)
    np.bitwise_or.at(words, (block_of_elem, w_idx), mask)

    blk_set = (uniq_key // ((n_total // block_bits) + 2)).astype(np.int64)
    blk_id = (uniq_key % ((n_total // block_bits) + 2)).astype(np.int32)
    counts = np.bincount(blk_set, minlength=len(ids))
    off = np.zeros(len(ids) + 1, dtype=np.int64)
    np.cumsum(counts, out=off[1:])
    # cumulative cardinality per block within each set
    card = popcount_u32_np(words).sum(axis=1).astype(np.int64)
    cum = np.cumsum(card) - card
    seg_base = np.repeat(cum[off[:-1]], counts) if n_blocks else cum
    index = cum - seg_base

    slot_of = np.full(n_total, -1, dtype=np.int32)
    slot_of[ids] = np.arange(len(ids), dtype=np.int32)
    return BlockedBitset(block_bits, ids, off, blk_id, words, index, slot_of)


def bitset_intersect_count(bs: BlockedBitset, a_slots: np.ndarray,
                           b_slots: np.ndarray,
                           word_and_popcount=None) -> np.ndarray:
    """|S_a ∩ S_b| for slot pairs, both sets in the bitset cohort.

    Step 1 intersects the block-id lists with the uint machinery (the paper:
    "we pack the offsets contiguously, which allows us to regard the offsets
    as a uint layout"). Step 2 ANDs matched blocks and popcounts — that inner
    op is pluggable so the Pallas kernel can be injected.
    """
    pair_id, _, pos_a, pos_b = intersect_pairs_uint(
        bs.offsets, bs.block_ids, np.asarray(a_slots, np.int64),
        np.asarray(b_slots, np.int64))
    if len(pair_id) == 0:
        return np.zeros(len(a_slots), dtype=np.int64)
    if word_and_popcount is None:
        word_and_popcount = _word_and_popcount_jnp
        trace.upload(bs.words, pos_a, pos_b)
    per_block = np.asarray(word_and_popcount(bs.words, pos_a, pos_b))
    out = np.zeros(len(a_slots), dtype=np.int64)
    with trace.span("eh.pairs.expand"):
        np.add.at(out, pair_id, per_block.astype(np.int64))
    return out


@jax.jit
def _word_and_popcount_jnp(words, pos_a, pos_b):
    wa = words[pos_a]
    wb = words[pos_b]
    return popcount_u32(wa & wb).sum(axis=1)


def bitset_intersect_materialize(bs: BlockedBitset, a_slots: np.ndarray,
                                 b_slots: np.ndarray):
    """Materializing bitset∩bitset: every element of S_a ∩ S_b plus its
    RANK (position) within each endpoint's sorted set.

    Step 1 intersects the block-id lists with the uint machinery (as in
    :func:`bitset_intersect_count`); step 2 ANDs the matched blocks and
    extracts the set bits; ranks come from the paper's per-block ``index``
    (Figure 6 i_k: cumulative cardinality before the block) plus a
    popcount of the endpoint's own word bits below the element — which is
    exactly what the index field exists for ("used to address associated
    values / next-trie-level pointers").

    Returns ``(pair_id, values, rank_a, rank_b)``, pair-major with values
    ascending within each pair (the canonical expansion order of the
    search path).
    """
    a_slots = np.asarray(a_slots, np.int64)
    b_slots = np.asarray(b_slots, np.int64)
    pair_id, _blk, pos_a, pos_b = intersect_pairs_uint(
        bs.offsets, bs.block_ids, a_slots, b_slots)
    z = np.zeros(0, np.int64)
    if len(pair_id) == 0:
        return z, np.zeros(0, np.int32), z, z
    with trace.span("eh.pairs.expand"):
        wa = bs.words[pos_a]                      # [B', wpb] uint32
        wb = bs.words[pos_b]
        wand = wa & wb
        # extract set bits of each AND-ed block: little-endian unpack keeps
        # (block, bit-position) row-major, so matches come out
        # block-ascending then value-ascending; uint8 unpack avoids the 32x
        # uint32 broadcast blow-up on large dense frontiers
        flat = np.unpackbits(wand.view(np.uint8), axis=1, bitorder="little")
        blk_row, bitpos = np.nonzero(flat)
        word_idx = bitpos >> 5
        bit_idx = bitpos & 31
        vals = (bs.block_ids[pos_a[blk_row]].astype(np.int64) * bs.block_bits
                + bitpos)
        below = (np.uint32(1) << bit_idx.astype(np.uint32)) - np.uint32(1)

        def rank(words, pos):
            per_word = popcount_u32_np(words)             # [B', wpb]
            cum = np.cumsum(per_word, axis=1) - per_word  # exclusive per word
            return (bs.index[pos[blk_row]]
                    + cum[blk_row, word_idx]
                    + popcount_u32_np(words[blk_row, word_idx] & below))

        return (pair_id[blk_row], vals.astype(np.int32),
                rank(wa, pos_a).astype(np.int64),
                rank(wb, pos_b).astype(np.int64))


def uint_bitset_intersect_count(offsets, neighbors, u: np.ndarray,
                                bs: BlockedBitset, b_slots: np.ndarray) -> np.ndarray:
    """uint ∩ bitset (Section 4.2): probe each uint element into the bitset.

    Masks the low bits of each element to get its block id, searches the
    block-id (uint) list, then tests the bit. Min property holds with a
    constant set by the block size."""
    u = np.asarray(u, dtype=np.int64)
    b_slots = np.asarray(b_slots, dtype=np.int64)
    with trace.span("eh.pairs.expand"):
        deg = np.diff(offsets)
        cnt = deg[u]
        pair_id = np.repeat(np.arange(len(u), dtype=np.int64), cnt)
        starts = offsets[u]
        base = np.repeat(starts, cnt)
        local = np.arange(len(pair_id), dtype=np.int64)
        seg_start = np.repeat(np.concatenate([[0], np.cumsum(cnt)])[:-1],
                              cnt)
        elems = neighbors[base + (local - seg_start)].astype(np.int64)

        blk = (elems // bs.block_bits).astype(np.int32)
        lo = bs.offsets[b_slots][pair_id]
        hi = bs.offsets[b_slots + 1][pair_id]
    pos, found = _search(bs.block_ids, lo, hi, blk)
    pos = np.asarray(pos)
    with trace.span("eh.pairs.expand"):
        bit = elems % bs.block_bits
        w = bs.words[np.clip(pos, 0, len(bs.block_ids) - 1), bit // 32]
        hit = found & (((w >> (bit % 32).astype(np.uint32)) & 1)
                       .astype(bool))
        out = np.zeros(len(u), dtype=np.int64)
        np.add.at(out, pair_id, hit.astype(np.int64))
    return out
