"""Execution backends: where sets live and who intersects them.

EmptyHeaded's algorithm layer (Generic-Join over GHD bags, ``core.gj``)
is decoupled from the data-placement/intersection layer following the
GraphIt algorithm/backend split (Zhang et al. 2018):

  * :class:`NumpyBackend` — the seed behaviour: trie levels stay host
    numpy, each probe atom's lockstep binary search is a separate jitted
    call with its own host round-trip. Kept as the differential-testing
    oracle.
  * :class:`DeviceBackend` — trie levels are uploaded to device once
    (cached on the :class:`~repro.core.trie.TrieLevel`, so multi-rule and
    seminaive programs reuse the upload across iterations), every
    attribute extension runs all probe atoms in ONE fused jitted call
    with at most one host sync, and terminal-fold intersections are
    partitioned into bitset/uint cohorts via the Algorithm-3
    :class:`~repro.core.layouts.LayoutDecision` and dispatched to the
    Pallas kernels (uint×uint membership test, bitset×bitset
    AND+popcount, uint×bitset probe).

Backend selection: ``Engine(backend=...)`` accepts a backend instance or
the names ``"numpy"`` / ``"device"``; when unset, the
``REPRO_ENGINE_BACKEND`` environment variable decides, and without it the
platform does: ``"device"`` where JAX's default backend is a TPU,
``"numpy"`` elsewhere.

Every backend carries ``stats``, a flat counter recording which kernel
handled each intersection (``intersect.*`` keys count pairs) and the
host-sync discipline of the extension loop (``extend.calls`` vs
``extend.host_syncs``).  The static verification layer
(:mod:`repro.analysis`) rides the same counter: ``analysis.plans_verified``
/ ``analysis.candidates_verified`` count validator runs and
``analysis.sanitize_checks`` counts passed ``REPRO_SANITIZE`` dispatch
assertions, so the benchmark artifact's dispatch gate also proves
verification stayed on.  ``upload.bytes`` counts the bytes put on the
device.  The engine's spans (``repro.trace``) add their ``span.*`` wall
times to ``stats`` as well; ``dispatch_summary()`` leaves them out, so
it holds counts alone.
"""
from __future__ import annotations

import collections
import dataclasses
import os
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import trace
from repro.core import intersect as I
from repro.core.layouts import engine_store_for
from repro.core.semiring import Semiring
from repro.kernels.bitset_intersect.ops import as_word_kernel
from repro.kernels.common import audit_avals, host_get, interpret_default
from repro.kernels.frontier_fill import ops as ff_ops
from repro.kernels.frontier_fill import ref as ff_ref
from repro.kernels.materialize.ops import as_materialize_kernel
from repro.kernels.uint_intersect.ops import intersect_count_csr_batched

# Pairs whose larger set exceeds this stay on the lockstep binary search
# (the SIMDGalloping analogue); shorter pairs take the membership-test
# kernel (the SIMDShuffling analogue) — Algorithm 2's regime split.
UINT_KERNEL_MAX_LEN = 256

# Index dtype of the device-resident pipeline (positions, counts,
# offsets) — mirrors intersect.segment_searchsorted's choice.
_IDX = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
_IDX_NP = np.int64 if jax.config.jax_enable_x64 else np.int32
# Without x64, on-device counts are int32: the pipeline only engages when
# the exact cross-product bound of the extension stays below this, so the
# counting pass cannot wrap around.
_COUNT_LIMIT = (1 << 62) if jax.config.jax_enable_x64 else (1 << 31) - 1

_FALSEY = frozenset({"0", "off", "false", "no"})


def _env_on(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in _FALSEY


class PipelineOverflow(RuntimeError):
    """A pipelined frontier buffer was undersized (the stats-informed
    capacity under-estimated the true expansion).  Raised at the single
    closing sync, BEFORE any join state was mutated.  ``needed`` carries
    the counting pass's exact per-variable output totals fetched with
    that same sync — the caller retries device-resident with buffers
    sized from the measured truth (falling back to the per-extension-
    sync host path only if that second attempt overflows too, which can
    happen when an upstream overflow truncated the rows the later
    counts were taken over)."""

    def __init__(self, msg: str, needed: Optional[Dict[str, int]] = None):
        super().__init__(msg)
        self.needed = needed or {}


class ExecBackend:
    """Protocol for the Generic-Join execution backend.

    ``extend(infos, F)`` receives the per-atom candidate descriptors of
    one attribute extension — ``infos`` is a list of
    ``(atom, values, lo, hi, mass)`` tuples sorted by total candidate
    mass (the min-property seed first) — and returns
    ``(row_id, vals, pos)`` exactly like the seed-expand-probe loop:
    ``pos`` maps ``id(atom)`` to absolute positions into that atom's
    current trie level.

    ``pair_count(trie, u, v)`` is the binary terminal-fold fast path:
    layout-routed ``|N(u_i) ∩ N(v_i)|`` counts, or ``None`` when the
    store is bypassed (layout mode "off"). ``has_pair_store(trie)`` lets
    the caller skip the frontier gathers entirely in the bypassed case.
    """

    name = "abstract"

    def __init__(self):
        self.stats: collections.Counter = collections.Counter()
        self._dtype_cache: Dict[str, np.dtype] = {}

    # jnp is resolved once at module import (not per GJ call); the per-
    # semiring canonical numpy dtype is cached per backend instance.
    def dtype_of(self, sr: Semiring) -> np.dtype:
        dt = self._dtype_cache.get(sr.name)
        if dt is None:
            dt = np.dtype(jnp.zeros((), sr.dtype).dtype)
            self._dtype_cache[sr.name] = dt
        return dt

    def extend(self, infos: Sequence[Tuple], F: int):
        raise NotImplementedError

    @staticmethod
    def _expand_seed(lo0: np.ndarray, hi0: np.ndarray, F: int):
        """Min-property seed expansion shared by both backends: flatten
        every frontier row's seed segment, returning (row_id, p0) with
        ``p0`` absolute positions into the seed level's values."""
        cnt = (hi0 - lo0).astype(np.int64)
        row_id = np.repeat(np.arange(F, dtype=np.int64), cnt)
        seg_start = np.repeat(np.concatenate([[0], np.cumsum(cnt)])[:-1], cnt)
        flat = np.arange(len(row_id), dtype=np.int64)
        p0 = np.repeat(lo0, cnt) + (flat - seg_start)
        return row_id, p0

    def _pair_store(self, trie, threshold: Optional[float] = None):
        raise NotImplementedError

    def has_pair_store(self, trie,
                       threshold: Optional[float] = None) -> bool:
        return self._pair_store(trie, threshold) is not None

    def pair_count(self, trie, u: np.ndarray, v: np.ndarray,
                   threshold: Optional[float] = None):
        """Binary terminal-fold fast path. ``threshold`` is the plan IR's
        statistics-driven Algorithm-3 density threshold (None lets the
        layout store profile the trie itself).  Returns host arrays, so
        the device work it launches ends inside its ``eh.pairs`` span."""
        with trace.span("eh.pairs", self.stats):
            store = self._pair_store(trie, threshold)
            if store is None:
                return None
            self.stats["fold.pair_count_calls"] += 1
            return store.intersect_count(u, v)

    def pair_materialize(self, trie, u: np.ndarray, v: np.ndarray,
                         threshold: Optional[float] = None):
        """Binary MATERIALIZING extension fast path (the plan IR's
        ``Extend.routing == "pair_store"``): cohort-routed
        ``N(u_i) ∩ N(v_i)`` with positions for trie descent, or ``None``
        when the layout store is bypassed."""
        with trace.span("eh.pairs", self.stats):
            store = self._pair_store(trie, threshold)
            if store is None:
                return None
            self.stats["extend.pair_materialize_calls"] += 1
            return store.intersect_materialize(u, v)

    def dispatch_summary(self) -> Dict[str, int]:
        """``stats`` without the spans' wall times: counts only."""
        return {k: v for k, v in self.stats.items()
                if not k.startswith("span.")}

    def trace_count(self) -> int:
        """Number of distinct traced program shapes this backend has
        compiled — the retrace-proof counter for the serving layer's
        no-recompile-on-rebind invariant. Host backends trace nothing."""
        return 0


class NumpyBackend(ExecBackend):
    """Seed behaviour: host-side expansion, one search (and one host
    round-trip) per probe atom, layout store only on the binary terminal
    fold with the plain-jnp word kernel."""

    name = "numpy"

    def extend(self, infos, F: int):
        a0, v0, lo0, hi0, _ = infos[0]
        row_id, p0 = self._expand_seed(lo0, hi0, F)
        vals = v0[p0]
        pos = {id(a0): p0}
        self.stats["extend.calls"] += 1
        for a, values, lo, hi, _m in infos[1:]:
            p, found = I.segment_searchsorted(values, lo[row_id], hi[row_id],
                                              vals)
            p = np.asarray(p); found = np.asarray(found)
            self.stats["extend.host_syncs"] += 1
            keep = found
            row_id = row_id[keep]
            vals = vals[keep]
            for k in pos:
                pos[k] = pos[k][keep]
            pos[id(a)] = p[keep]
        return row_id, vals, pos

    def _pair_store(self, trie, threshold=None):
        return engine_store_for(trie, counter=self.stats, cache_tag="host",
                                threshold=threshold)


class DeviceBackend(ExecBackend):
    """Device-resident set store: upload trie levels once, fuse every
    extension's probes into one jitted call (one host sync per attribute
    extension), and route terminal-fold intersections to the
    layout-cohort Pallas kernels."""

    name = "device"

    def __init__(self, interpret: Optional[bool] = None,
                 uint_max_len: int = UINT_KERNEL_MAX_LEN,
                 pipeline: Optional[bool] = None):
        super().__init__()
        # Pallas interpret mode, resolved ONCE for every kernel this
        # backend launches: compiled on a TPU, interpreted elsewhere
        interpret = (interpret_default() if interpret is None
                     else bool(interpret))
        self._interpret = interpret
        self._word_kernel = as_word_kernel(interpret=interpret)
        self._materialize_kernel = as_materialize_kernel(interpret=interpret)
        self._uint_max_len = uint_max_len
        # Zero-sync extension pipeline (count-then-fill): on by default,
        # REPRO_DEVICE_PIPELINE=off pins the per-extension-sync path as
        # the differential oracle (Engine(device_pipeline=...) overrides).
        self.pipeline_enabled = (_env_on("REPRO_DEVICE_PIPELINE", True)
                                 if pipeline is None else bool(pipeline))
        # Whole-bag fusion: record each bag's pipelined extension chain
        # and trace it as ONE jitted composite (``run_bag``), so XLA
        # fuses step k's compaction with step k+1's counting pass and a
        # bag costs a single launch.  REPRO_FUSED_BAG=off falls back to
        # one launch per attribute step (Engine(fused_bags=...)
        # overrides).
        self.fuse_bags = _env_on("REPRO_FUSED_BAG", True)
        # Fill-stage path: the plain-jnp fill (kernels/frontier_fill/
        # ref.py) is the default because it is what the TPU compiler
        # accepts.  REPRO_FRONTIER_FILL=pallas opts into the frontier-fill
        # Pallas kernel, which the TPU compiler refuses (it gathers with
        # 1-D vector indices inside the kernel and stages whole trie
        # levels in VMEM), so it runs only in interpret mode.
        fm = os.environ.get("REPRO_FRONTIER_FILL", "jnp").strip().lower()
        self.fill_mode = "pallas" if fm == "pallas" else "jnp"
        # trace keys of every program shape dispatched (``trace_count``)
        self._traced: set = set()
        # engine-lifetime pipeline-cap feedback: bag shape -> the
        # counting pass's measured per-variable totals from an
        # overflow-retried execution, so repeated queries size their
        # frontier buffers right the first time (see GenericJoin.run)
        self.cap_feedback: Dict[Tuple, Dict[str, int]] = {}
        # trace-level audit hook (repro.analysis.jaxpr_audit): when a
        # list, run_bag/run_bag_batched append an abstract record of
        # every dispatched program — (kind, name, prog, operand avals,
        # cursor avals, ann aval, ...) — so the auditor can retrace the
        # exact jaxprs the engine ran without holding device buffers.
        self.audit_log: Optional[List[tuple]] = None

        def uint_kernel(offsets, neighbors, u, v):
            return intersect_count_csr_batched(
                offsets, neighbors, u, v, interpret=interpret,
                max_len=uint_max_len)

        self._uint_kernel = uint_kernel

    # ------------------------------------------------------------- uploads
    def _dev_values(self, atom) -> jnp.ndarray:
        lv = atom.trie.levels[atom.depth]
        return lv.device_values(self._put, on_upload=self._count_upload)

    def _count_upload(self):
        self.stats["upload.levels"] += 1

    def _put(self, arr) -> jnp.ndarray:
        """Upload one host array, counting its bytes on the device."""
        arr = np.asarray(arr)
        self.stats["upload.bytes"] += trace.device_nbytes(arr)
        return jnp.asarray(arr)

    def _up_idx(self, arr) -> jnp.ndarray:
        return self._put(np.asarray(arr, dtype=_IDX_NP))

    def _dev_sideways(self, bs):
        """Device copies of a blocked bitset's DIRECTORY (slot router,
        block CSR, block ids) for the counting pass's sideways block
        intersection — the words themselves stay host-side.  Cached on
        the bitset instance, invalidated if the bitset was rebuilt."""
        cached = getattr(bs, "_dev_sideways_cache", None)
        if cached is not None and cached[0] is bs.block_ids:
            return cached[1]
        dev = (self._put(np.asarray(bs.slot_of, np.int32)),
               self._up_idx(bs.offsets),
               self._put(np.asarray(bs.block_ids, np.int32)))
        bs._dev_sideways_cache = (bs.block_ids, dev)
        self.stats["upload.bitset_dirs"] += 1
        return dev

    def trace_count(self) -> int:
        return len(self._traced)

    def _sideways_dev(self, cons):
        """Per-probe device sideways tuples + static block_bits for an
        extension's constraining atoms (seed excluded)."""
        sw_t, bits_t = [], []
        for c in cons[1:]:
            sw = c[3]
            if sw is None:
                sw_t.append(None)
                bits_t.append(None)
            else:
                l0, bs = sw
                l0v = l0.device_values(self._put,
                                       on_upload=self._count_upload)
                slot_d, boffs_d, bids_d = self._dev_sideways(bs)
                sw_t.append((l0v, slot_d, boffs_d, bids_d))
                bits_t.append(int(bs.block_bits))
        return tuple(sw_t), tuple(bits_t)

    # ------------------------------------------------------------- extend
    def extend(self, infos, F: int):
        self.stats["extend.calls"] += 1
        a0, v0, lo0, hi0, _ = infos[0]
        row_id, p0 = self._expand_seed(lo0, hi0, F)
        if len(row_id) == 0:
            z = np.zeros(0, np.int64)
            return z, np.zeros(0, np.int32), {id(a): z for a, *_ in infos}
        if len(infos) == 1:
            # unary extension: no probes, so the host copy already has the
            # answer — zero device traffic
            return row_id, v0[p0], {id(a0): p0}
        vals_dev = self._dev_values(a0)[p0]

        values_t = tuple(self._dev_values(a) for a, *_ in infos[1:])
        lo_t = tuple(info[2][row_id] for info in infos[1:])
        hi_t = tuple(info[3][row_id] for info in infos[1:])
        self.stats["upload.bytes"] += trace.device_nbytes(p0, *lo_t, *hi_t)
        pos_t, found = _fused_probe(values_t, lo_t, hi_t, vals_dev)
        # the ONLY host round-trip of this extension: every probe atom's
        # positions + the combined membership mask come back together.
        pos_h, found_h, vals_h = host_get((pos_t, found, vals_dev))
        self.stats["extend.host_syncs"] += 1
        keep = np.asarray(found_h)
        out_row = row_id[keep]
        out_vals = np.asarray(vals_h)[keep]
        pos = {id(a0): p0[keep]}
        for (a, *_), p in zip(infos[1:], pos_h):
            pos[id(a)] = np.asarray(p)[keep]
        return out_row, out_vals, pos

    # ------------------------------------------------------ terminal folds
    def _pair_store(self, trie, threshold=None):
        return engine_store_for(trie, word_kernel=self._word_kernel,
                                 uint_kernel=self._uint_kernel,
                                 materialize_kernel=self._materialize_kernel,
                                 uint_max_len=self._uint_max_len,
                                 counter=self.stats, cache_tag="device",
                                 threshold=threshold)

    # ---------------------------------------------- zero-sync pipeline
    # The frontier stays device-resident between attribute extensions:
    # each step is ONE jitted count-then-fill program (counting probe →
    # exclusive scan → morsel-chunked fill → compaction) into a
    # static-shaped buffer, with NO host round-trip.  The join "lands"
    # once per query (``pipeline_land``'s host_get) when it reaches the
    # first host-needing step — so ``extend.host_syncs`` is zero and
    # ``extend.closing_syncs`` is one for non-materializing queries.

    def pipeline_begin(self, cursors0: Dict[int, np.ndarray],
                       ann0: Optional[np.ndarray]) -> "DeviceFrontier":
        cursors = {k: self._up_idx(c) for k, c in cursors0.items()}
        ann = self._put(ann0) if ann0 is not None else None
        return DeviceFrontier(
            cap=1, count=jnp.asarray(1, _IDX),
            overflow=jnp.asarray(False),
            morsels=jnp.asarray(0, _IDX),
            cols={}, cursors=cursors, ann=ann, level_counts=[],
            needed=[])

    def pipeline_extend(self, state: "DeviceFrontier", var: str,
                        cons: Sequence[Tuple], cap_out: int,
                        morsel: int) -> "DeviceFrontier":
        """One pipelined attribute extension.  ``cons`` lists
        ``(cursor_key, trie_level, depth0, sideways)`` per constraining
        atom, the estimated-min-property seed first; ``sideways`` is
        ``(level0, blocked_bitset)`` when the counting pass should also
        intersect that probe atom's bitset blocks (dense cohorts —
        prunes before expansion, not just clips), else None.  Returns
        the successor state; nothing touches the host."""
        self.stats["extend.calls"] += 1
        self.stats["extend.pipeline_extends"] += 1
        self.stats["pipeline.launches"] += 1
        if len(cons) > 1:
            self.stats["pipeline.sip_extends"] += 1
        if any(c[3] is not None for c in cons[1:]):
            self.stats["pipeline.sideways_extends"] += 1

        def triple(key, lv, d0, _sw):
            vals = lv.device_values(self._put,
                                    on_upload=self._count_upload)
            if d0:
                return (vals, None, None)
            offs = lv.device_offsets(self._up_idx,
                                     on_upload=self._count_upload)
            return (vals, offs, state.cursors[key])

        seed = triple(*cons[0])
        probes = tuple(triple(*c) for c in cons[1:])
        probe_d0 = tuple(bool(c[2]) for c in cons[1:])
        sideways, sideways_bits = self._sideways_dev(cons)
        cons_keys = {c[0] for c in cons}
        col_keys = list(state.cols)
        cur_keys = [k for k in state.cursors if k not in cons_keys]
        carry = (tuple(state.cols[k] for k in col_keys)
                 + tuple(state.cursors[k] for k in cur_keys)
                 + ((state.ann,) if state.ann is not None else ()))

        self._traced.add(("step", state.cap, int(cap_out), int(morsel),
                          probe_d0, sideways_bits, len(carry)))
        with trace.span("eh.bag.launch", self.stats):
            (count, overflow, chunks, total, vals_c, p0_c, pos_c,
             carry_c) = _pipeline_step(
                state.count, state.overflow, seed, probes, sideways, carry,
                cap_in=state.cap, cap_out=int(cap_out), morsel=int(morsel),
                seed_d0=bool(cons[0][2]), probe_d0=probe_d0,
                sideways_bits=sideways_bits, fill_mode=self.fill_mode,
                fill_interpret=self._interpret)

        it = iter(carry_c)
        cols = {k: next(it) for k in col_keys}
        cursors = {k: next(it) for k in cur_keys}
        ann = next(it) if state.ann is not None else None
        cols[var] = vals_c
        cursors[cons[0][0]] = p0_c
        for (k, _lv, _d0, _sw), p in zip(cons[1:], pos_c):
            cursors[k] = p
        return DeviceFrontier(
            cap=int(cap_out), count=count, overflow=overflow,
            morsels=state.morsels + chunks, cols=cols, cursors=cursors,
            ann=ann, level_counts=state.level_counts + [(var, count)],
            needed=state.needed + [(var, total)])

    def pipeline_terminal_fold(self, state: "DeviceFrontier", var: str,
                               cons: Sequence[Tuple], sr: Semiring,
                               morsel: int) -> "DeviceFrontier":
        """Device-resident early aggregation of the last attribute: the
        expansion is folded per source row (no materialization, so no
        output buffer and no overflow) and rows whose candidate
        intersection is empty are compacted away — mirroring the host
        loop's ``_terminal_fold`` support semantics without a sync.

        ``cons`` lists ``(cursor_key, trie_level, depth0, leaf_ann)``
        per constraining atom, estimated-min-property seed first;
        ``leaf_ann`` is the atom's annotation vector (or None) —
        terminal atoms always exhaust their attrs here, so it is
        multiplied into each candidate's contribution at its position.
        """
        self.stats["fold.calls"] += 1
        self.stats["pipeline.device_folds"] += 1
        self.stats["pipeline.launches"] += 1

        def triple(key, lv, d0, _ann):
            vals = lv.device_values(self._put,
                                    on_upload=self._count_upload)
            if d0:
                return (vals, None, None)
            offs = lv.device_offsets(self._up_idx,
                                     on_upload=self._count_upload)
            return (vals, offs, state.cursors[key])

        def leaf(c):
            if c[3] is None:
                return None
            return c[3].device_annotation(self._put,
                                          on_upload=self._count_upload)

        seed = triple(*cons[0])
        probes = tuple(triple(*c) for c in cons[1:])
        probe_d0 = tuple(bool(c[2]) for c in cons[1:])
        leaf_anns = tuple(leaf(c) for c in cons)
        col_keys = list(state.cols)
        cur_keys = list(state.cursors)
        carry = (tuple(state.cols[k] for k in col_keys)
                 + tuple(state.cursors[k] for k in cur_keys))

        self._traced.add(("fold", state.cap, int(morsel), probe_d0,
                          sr.name, len(carry)))
        with trace.span("eh.bag.launch", self.stats):
            count, chunks, ann_c, carry_c = _pipeline_fold(
                state.count, seed, probes, state.ann, leaf_anns, carry,
                cap_in=state.cap, morsel=int(morsel),
                seed_d0=bool(cons[0][2]), probe_d0=probe_d0, sr=sr)

        it = iter(carry_c)
        cols = {k: next(it) for k in col_keys}
        cursors = {k: next(it) for k in cur_keys}
        return DeviceFrontier(
            cap=state.cap, count=count, overflow=state.overflow,
            morsels=state.morsels + chunks, cols=cols, cursors=cursors,
            ann=ann_c, level_counts=state.level_counts + [(var, count)],
            needed=state.needed)

    def pipeline_ann_mul(self, state: "DeviceFrontier", sr: Semiring,
                         trie, cursor_key: int) -> None:
        """Multiply an exhausted atom's annotation into the device-
        resident frontier annotation (eager jnp ops: async dispatch, no
        sync).  Mirrors the host loop's leaf-annotation multiply."""
        ann_dev = trie.device_annotation(self._put,
                                         on_upload=self._count_upload)
        cur = state.cursors[cursor_key]
        n = ann_dev.shape[0]
        leaf = ann_dev[jnp.clip(cur, 0, max(n - 1, 0))]
        state.ann = sr.mul(state.ann, leaf.astype(state.ann.dtype))

    def run_bag(self, cursors0: Dict[int, np.ndarray],
                ann0: Optional[np.ndarray],
                steps: Sequence[Tuple]) -> "DeviceFrontier":
        """Execute ONE bag's whole recorded extension chain as a single
        jitted composite — the fused counterpart of calling
        ``pipeline_begin`` + per-attribute ``pipeline_extend`` /
        ``pipeline_terminal_fold`` / ``pipeline_ann_mul``.

        ``steps`` is the host-recorded plan (one tuple per attribute:
        ``("extend", var, cons, cap_out, morsel)``,
        ``("fold", var, cons, sr, morsel)`` or
        ``("annmul", cursor_key, trie, sr)`` with the same ``cons``
        descriptors the per-step methods take).  The chain is lowered to
        a pure hashable program over a flat deduplicated operand list,
        so ``_bag_program`` retraces only when the bag SHAPE changes —
        and XLA sees step k's compaction and step k+1's counting pass in
        one module, fusing across the attribute boundary.  One launch
        per bag; the closing ``pipeline_land`` stays the only transfer.
        Lowering and dispatch run inside the span ``eh.bag.launch``.
        """
        with trace.span("eh.bag.launch", self.stats):
            self.stats["pipeline.launches"] += 1
            prog_t, arrays, canon, cap = self._lower_bag(steps, cursors0)
            cur_canon = {canon[k]: self._up_idx(c)
                         for k, c in cursors0.items()}
            ann = self._put(ann0) if ann0 is not None else None
            if self.audit_log is not None:
                self.audit_log.append(
                    ("bag", "bag", prog_t, audit_avals(tuple(arrays)),
                     audit_avals(cur_canon), audit_avals(ann),
                     self.fill_mode, self._interpret))
            self._traced.add(("bag", prog_t, self.fill_mode))
            (count, overflow, morsels, lcounts, needs, cols, cursors,
             ann_o) = _bag_program(
                tuple(arrays), cur_canon, ann,
                prog=prog_t, fill_mode=self.fill_mode,
                fill_interpret=self._interpret)
        id_of = {v: k for k, v in canon.items()}
        lvars = [s[1] for s in prog_t if s[0] in ("extend", "fold")]
        evars = [s[1] for s in prog_t if s[0] == "extend"]
        return DeviceFrontier(
            cap=cap, count=count, overflow=overflow, morsels=morsels,
            cols=dict(cols),
            cursors={id_of[c]: cur for c, cur in cursors.items()},
            ann=ann_o, level_counts=list(zip(lvars, lcounts)),
            needed=list(zip(evars, needs)))

    def _lower_bag(self, steps: Sequence[Tuple],
                   cursors0: Dict[int, np.ndarray]):
        """Lower a host-recorded bag chain to the pure hashable program
        ``_bag_program`` consumes: ``(prog, arrays, canon, final cap)``.
        Shared by the single-query ``run_bag`` and the vmapped
        ``run_bag_batched`` — the program is identical; only the cursor
        rank differs.  Dispatch counters for the chain's steps are
        charged here, once per lowered chain."""
        canon: Dict[int, int] = {}

        def ckey(k):
            if k not in canon:
                canon[k] = len(canon)
            return canon[k]

        for k in cursors0:
            ckey(k)
        arrays: List = []
        seen: Dict[int, int] = {}

        def aref(x):
            if x is None:
                return -1
            i = seen.get(id(x))
            if i is None:
                i = len(arrays)
                arrays.append(x)
                seen[id(x)] = i
            return i

        def upload(lv, d0):
            vals_i = aref(lv.device_values(self._put,
                                           on_upload=self._count_upload))
            offs_i = -1 if d0 else aref(lv.device_offsets(
                self._up_idx, on_upload=self._count_upload))
            return vals_i, offs_i

        prog = []
        cap = 1
        for step in steps:
            kind = step[0]
            if kind == "extend":
                _, var, cons, cap_out, morsel = step
                self.stats["extend.calls"] += 1
                self.stats["extend.pipeline_extends"] += 1
                if len(cons) > 1:
                    self.stats["pipeline.sip_extends"] += 1
                if any(c[3] is not None for c in cons[1:]):
                    self.stats["pipeline.sideways_extends"] += 1
                cdescs = []
                for i, (key, lv, d0, sw) in enumerate(cons):
                    vals_i, offs_i = upload(lv, d0)
                    swt = None
                    if sw is not None and i > 0:
                        l0, bs = sw
                        l0v = l0.device_values(
                            self._put, on_upload=self._count_upload)
                        slot_d, boffs_d, bids_d = self._dev_sideways(bs)
                        swt = (aref(l0v), aref(slot_d), aref(boffs_d),
                               aref(bids_d), int(bs.block_bits))
                    cdescs.append((ckey(key), vals_i, offs_i, swt))
                prog.append(("extend", var, int(cap_out), int(morsel),
                             tuple(cdescs)))
                cap = int(cap_out)
            elif kind == "fold":
                _, var, cons, sr, morsel = step
                self.stats["fold.calls"] += 1
                self.stats["pipeline.device_folds"] += 1
                cdescs = []
                for key, lv, d0, ann_trie in cons:
                    vals_i, offs_i = upload(lv, d0)
                    ann_i = -1
                    if ann_trie is not None:
                        ann_i = aref(ann_trie.device_annotation(
                            self._put, on_upload=self._count_upload))
                    cdescs.append((ckey(key), vals_i, offs_i, ann_i))
                prog.append(("fold", var, int(morsel), sr,
                             tuple(cdescs)))
            elif kind == "annmul":
                _, key, trie, sr = step
                ann_i = aref(trie.device_annotation(
                    self._put, on_upload=self._count_upload))
                prog.append(("annmul", ckey(key), ann_i, sr))
            else:
                raise ValueError(f"unknown bag step {kind!r}")
        return tuple(prog), arrays, canon, cap

    def run_bag_batched(self, cursors0: Dict[int, np.ndarray],
                        ann0: Optional[np.ndarray],
                        steps: Sequence[Tuple]) -> "BatchedFrontier":
        """Execute B same-shape bag instances as ONE fused device launch.

        ``cursors0`` maps each pre-bound atom to a ``[B, 1]`` cursor
        stack — one row per query.  The chain is lowered through the
        SAME ``_lower_bag`` as the single-query path, then dispatched
        through ``_bag_program_batch``: ``jax.vmap`` maps the leading
        batch dimension over the cursors while the operand arrays (trie
        levels — shared by every query) stay unbatched.  One launch
        (``pipeline.launches`` += 1, ``pipeline.batched_launches`` += 1)
        serves all B probes; ``pipeline_land_batched`` is the single
        closing transfer.

        The fill stage is pinned to the plain-jnp reference path:
        ``lax.while_loop`` under vmap is fine (the cond becomes
        any-active), but the frontier-fill Pallas kernel is not vetted
        under a batching rule — and the reference is bit-identical by
        the kernel contract, so batched-vs-sequential parity stays
        EXACT.
        """
        b = next(iter(cursors0.values())).shape[0]
        with trace.span("eh.bag.launch", self.stats):
            self.stats["pipeline.launches"] += 1
            self.stats["pipeline.batched_launches"] += 1
            self.stats["pipeline.batched_queries"] += int(b)
            prog_t, arrays, canon, cap = self._lower_bag(steps, cursors0)
            cur_canon = {canon[k]: self._up_idx(c)
                         for k, c in cursors0.items()}
            ann = self._put(ann0) if ann0 is not None else None
            if self.audit_log is not None:
                self.audit_log.append(
                    ("bag_batch", "bag_batch", prog_t,
                     audit_avals(tuple(arrays)), audit_avals(cur_canon),
                     audit_avals(ann), int(b), self._interpret))
            self._traced.add(("bag_batch", prog_t, int(b)))
            (count, overflow, morsels, lcounts, needs, cols, cursors,
             ann_o) = _bag_program_batch(
                tuple(arrays), cur_canon, ann,
                prog=prog_t, fill_interpret=self._interpret)
        id_of = {v: k for k, v in canon.items()}
        lvars = [s[1] for s in prog_t if s[0] in ("extend", "fold")]
        evars = [s[1] for s in prog_t if s[0] == "extend"]
        return BatchedFrontier(
            batch=int(b), cap=cap, count=count, overflow=overflow,
            morsels=morsels, cols=dict(cols),
            cursors={id_of[c]: cur for c, cur in cursors.items()},
            ann=ann_o, level_counts=list(zip(lvars, lcounts)),
            needed=list(zip(evars, needs)))

    def pipeline_land_batched(self, state: "BatchedFrontier"):
        """THE closing sync of a batched bag run: every query's compacted
        frontier, per-level counts and overflow flag in ONE transfer
        (``extend.closing_syncs`` += 1 for the whole batch).  The slots
        landed (B x cap) and the rows live in them add to
        ``pipeline.batched_slots`` / ``pipeline.batched_rows``."""
        scal = jnp.stack(
            [state.count.astype(_IDX), state.overflow.astype(_IDX),
             state.morsels.astype(_IDX)]
            + [c.astype(_IDX) for _v, c in state.level_counts]
            + [t.astype(_IDX) for _v, t in state.needed])   # [3+nl+nn, B]
        col_keys = list(state.cols)
        cur_keys = list(state.cursors)
        vecs = ([state.cols[k].astype(_IDX) for k in col_keys]
                + [state.cursors[k] for k in cur_keys])
        packed = jnp.stack(vecs) if vecs else None          # [nv, B, cap]
        scal_h, packed_h, ann = host_get((scal, packed, state.ann))
        self.stats["extend.closing_syncs"] += 1
        nl = len(state.level_counts)
        counts = np.asarray(scal_h[0], dtype=np.int64)
        self.stats["pipeline.batched_slots"] += state.batch * state.cap
        self.stats["pipeline.batched_rows"] += int(counts.sum())
        overflows = np.asarray(scal_h[1]).astype(bool)
        self.stats["pipeline.morsels"] += int(np.asarray(scal_h[2]).sum())
        # worst case over the batch per variable: the retry loop sizes
        # ONE shared buffer shape for every query in the batch
        needed = {v: int(np.asarray(t).max(initial=0)) for (v, _), t in
                  zip(state.needed, scal_h[3 + nl:])}
        cols = {k: np.asarray(packed_h[i]) for i, k in enumerate(col_keys)}
        cursors = {k: np.asarray(packed_h[len(col_keys) + i])
                   for i, k in enumerate(cur_keys)}
        ann = np.asarray(ann) if ann is not None else None
        return (counts, overflows, cols, cursors, ann, needed)

    def pipeline_land(self, state: "DeviceFrontier"):
        """THE closing sync: fetch the compacted frontier (columns,
        cursors, annotation), the per-level counts and the overflow flag
        in one transfer.  Counted as ``extend.closing_syncs``."""
        # pack the payload into three leaves (scalars / int vectors /
        # annotation) before the transfer: per-array materialization
        # overhead would otherwise dominate the single sync on small
        # frontiers.  Every live vector shares the final capacity, so
        # one stacked matrix carries them all.
        scal = jnp.stack(
            [state.count.astype(_IDX), state.overflow.astype(_IDX),
             state.morsels.astype(_IDX)]
            + [c.astype(_IDX) for _v, c in state.level_counts]
            + [t.astype(_IDX) for _v, t in state.needed])
        col_keys = list(state.cols)
        cur_keys = list(state.cursors)
        vecs = ([state.cols[k].astype(_IDX) for k in col_keys]
                + [state.cursors[k] for k in cur_keys])
        packed = jnp.stack(vecs) if vecs else None
        scal_h, packed_h, ann = host_get((scal, packed, state.ann))
        self.stats["extend.closing_syncs"] += 1
        nl = len(state.level_counts)
        count, overflow, morsels = (int(scal_h[0]), bool(scal_h[1]),
                                    int(scal_h[2]))
        self.stats["pipeline.morsels"] += morsels
        levels = [(v, int(c)) for (v, _), c in
                  zip(state.level_counts, scal_h[3:3 + nl])]
        needed = {v: int(t) for (v, _), t in
                  zip(state.needed, scal_h[3 + nl:])}
        cols = {k: packed_h[i] for i, k in enumerate(col_keys)}
        cursors = {k: packed_h[len(col_keys) + i]
                   for i, k in enumerate(cur_keys)}
        return (count, overflow, cols, cursors, ann, levels, needed)


@dataclasses.dataclass
class DeviceFrontier:
    """Device-resident Generic-Join frontier between pipelined
    extensions.  All buffers are static-shaped ``[cap]``; ``count`` (a
    device scalar) marks the live prefix and slots past it hold garbage
    that every consumer masks.  ``overflow`` is sticky: set when a
    counting pass found more rows than the buffer holds, read exactly
    once at the closing sync."""

    cap: int                            # static buffer capacity
    count: jnp.ndarray                  # [] live rows
    overflow: jnp.ndarray               # [] bool, sticky
    morsels: jnp.ndarray                # [] fill chunks actually run
    cols: Dict[str, jnp.ndarray]        # var -> int32 [cap]
    cursors: Dict[int, jnp.ndarray]     # id(atom) -> positions [cap]
    ann: Optional[jnp.ndarray]          # semiring annotation [cap]
    level_counts: List                  # [(var, count snapshot)]
    needed: List                        # [(var, counting-pass total)]


@dataclasses.dataclass
class BatchedFrontier:
    """``DeviceFrontier`` with a leading batch dimension: B same-shape
    bag instances executed by one vmapped program.  Every per-query
    field gains axis 0 of extent ``batch``; ``cap`` stays the (shared)
    static buffer capacity."""

    batch: int                          # B
    cap: int                            # static buffer capacity (shared)
    count: jnp.ndarray                  # [B] live rows per query
    overflow: jnp.ndarray               # [B] bool, sticky per query
    morsels: jnp.ndarray                # [B] fill chunks per query
    cols: Dict[str, jnp.ndarray]        # var -> int32 [B, cap]
    cursors: Dict[int, jnp.ndarray]     # id(atom) -> positions [B, cap]
    ann: Optional[jnp.ndarray]          # semiring annotation [B, cap]
    level_counts: List                  # [(var, [B] counts)]
    needed: List                        # [(var, [B] counting totals)]


@partial(jax.jit, static_argnames=("prog", "fill_interpret"))
def _bag_program_batch(arrays, cursors0, ann, *, prog: Tuple,
                       fill_interpret: bool):
    """B same-shape bag instances as ONE traced program: ``jax.vmap``
    over the leading cursor axis of ``_bag_program``'s body.  The
    operand ``arrays`` (trie levels, annotations, bitset directories)
    are closed over un-batched — every query reads the same resident
    relations — so XLA sees one module whose only batched inputs are the
    ``[B, 1]`` pre-bound cursors.  The trace key is (bag shape, B): a
    re-bound batch of the same size relaunches without retracing."""
    def one(cur):
        return _bag_program(arrays, cur, ann, prog=prog,
                            fill_mode="jnp",
                            fill_interpret=fill_interpret)
    return jax.vmap(one)(cursors0)


def _bounds(values, offsets, cursor, cap_in, valid):
    """Per-row candidate bounds [cap_in] of one atom, on device: the
    whole level at depth 0 (no cursor), else the cursor's CSR segment.
    Dead rows get an empty segment."""
    n = values.shape[0]
    if cursor is None:
        lo = jnp.zeros(cap_in, _IDX)
        hi = jnp.full(cap_in, n, _IDX)
    else:
        c = jnp.clip(cursor, 0, offsets.shape[0] - 2)
        lo = offsets[c]
        hi = offsets[c + 1]
    lo = jnp.where(valid, lo, 0)
    hi = jnp.where(valid, hi, 0)
    return lo, hi


@partial(jax.jit, static_argnames=("cap_in", "cap_out", "morsel",
                                   "seed_d0", "probe_d0",
                                   "sideways_bits", "fill_mode",
                                   "fill_interpret"))
def _pipeline_step(count, overflow, seed, probes, sideways, carry, *,
                   cap_in: int, cap_out: int, morsel: int,
                   seed_d0: bool, probe_d0: Tuple[bool, ...],
                   sideways_bits: Tuple, fill_mode: str,
                   fill_interpret: bool):
    """Per-step (unfused) jitted wrapper around ``_extend_body`` — one
    launch per attribute extension.  Whole-bag fusion calls the body
    directly from ``_bag_program`` instead."""
    return _extend_body(count, overflow, seed, probes, sideways, carry,
                        cap_in=cap_in, cap_out=cap_out, morsel=morsel,
                        sideways_bits=sideways_bits, fill_mode=fill_mode,
                        fill_interpret=fill_interpret)


def _extend_body(count, overflow, seed, probes, sideways, carry, *,
                 cap_in: int, cap_out: int, morsel: int,
                 sideways_bits: Tuple, fill_mode: str,
                 fill_interpret: bool):
    """One zero-sync attribute extension: count-then-fill in one program.

    1. counting probe: per-row seed-segment sizes, narrowed by sideways
       min/max information from every later (probe) atom — and, for
       probe atoms with a ``sideways`` bitset directory, by intersecting
       the probe row's POPULATED bitset blocks with the envelope (dense
       cohorts prune before expansion, not just clip);
    2. exclusive scan -> per-row output offsets + total (the overflow
       check against the static capacity);
    3. fill: ``morsel``-sized chunks invert the offsets (searchsorted)
       to seed positions, gather values and probe every other atom with
       the branch-free lockstep search — the plain-jnp fill per chunk
       (``fill_mode="pallas"`` swaps in the bit-identical
       ``frontier_fill`` kernel, interpret mode only), and oversized
       frontiers just spill to the next chunk of the same loop instead
       of a host round-trip;
    4. compaction: scatter surviving rows to a dense prefix and gather
       the previous frontier's columns/cursors/annotation through them.

    Output ordering (frontier-row-major, values ascending within a row)
    is identical to the host path's, so results match exactly.
    """
    seed_values, seed_offsets, seed_cursor = seed
    n0 = seed_values.shape[0]
    valid = jnp.arange(cap_in, dtype=_IDX) < count
    lo0, hi0 = _bounds(seed_values, seed_offsets, seed_cursor, cap_in,
                       valid)

    # ---- sideways information passing: clip the seed segment to the
    # [max(mins), min(maxs)] envelope of the probe atoms' candidate
    # ranges — rows outside it would fail every probe anyway, so the
    # result set (and ordering) is unchanged while the expansion shrinks.
    bounds = []
    alive = valid
    gmin = gmax = None
    cur_ks = []
    for vals_k, offs_k, cur_k in probes:
        nk = vals_k.shape[0]
        lo_k, hi_k = _bounds(vals_k, offs_k, cur_k, cap_in, valid)
        alive = alive & (lo_k < hi_k)
        mn = vals_k[jnp.clip(lo_k, 0, nk - 1)]
        mx = vals_k[jnp.clip(hi_k - 1, 0, nk - 1)]
        gmin = mn if gmin is None else jnp.maximum(gmin, mn)
        gmax = mx if gmax is None else jnp.minimum(gmax, mx)
        bounds.append((vals_k, lo_k, hi_k))
        cur_ks.append(cur_k)

    # ---- bitset sideways pass: a dense-cohort probe atom's candidate
    # set is exactly the union of its POPULATED bitset blocks, so the
    # envelope can only contain matches inside blocks the directory
    # lists.  Search the row's block-id segment for the envelope's
    # block range: rows with no populated block in range die here
    # (their expansion would fail that probe for every candidate), and
    # the envelope snaps inward to the first/last populated block.
    # Rows routed to the sparse cohort (slot_of < 0) pass through
    # untouched — pure narrowing, results unchanged.
    for sw, bbits, cur_k in zip(sideways, sideways_bits, cur_ks):
        if sw is None or cur_k is None:
            continue
        l0v, slot_of, boffs, bids = sw
        nl0 = l0v.shape[0]
        nid = slot_of.shape[0]
        ns = boffs.shape[0] - 1
        nb = bids.shape[0]
        ids = l0v[jnp.clip(cur_k, 0, max(nl0 - 1, 0))]
        slot = slot_of[jnp.clip(ids, 0, max(nid - 1, 0))]
        in_bs = alive & (ids >= 0) & (ids < nid) & (slot >= 0)
        s = jnp.clip(slot, 0, max(ns - 1, 0)).astype(_IDX)
        blo = jnp.where(in_bs, boffs[s], 0)
        bhi = jnp.where(in_bs, boffs[s + 1], 0)
        qlo = (gmin // bbits).astype(bids.dtype)
        qhi = (gmax // bbits).astype(bids.dtype)
        p_lo, _ = I.segment_searchsorted(bids, blo, bhi, qlo)
        p_hi, f_hi = I.segment_searchsorted(bids, blo, bhi, qhi)
        last = p_hi + f_hi - 1
        has = in_bs & (p_lo <= last)
        alive = alive & (~in_bs | has)
        fb = bids[jnp.clip(p_lo, 0, max(nb - 1, 0))]
        lb = bids[jnp.clip(last, 0, max(nb - 1, 0))]
        gmin = jnp.where(has, jnp.maximum(gmin, fb * bbits), gmin)
        gmax = jnp.where(has, jnp.minimum(gmax, (lb + 1) * bbits - 1),
                         gmax)

    if probes:
        p_lo, _ = I.segment_searchsorted(seed_values, lo0, hi0, gmin)
        p_hi, f_hi = I.segment_searchsorted(seed_values, lo0, hi0, gmax)
        lo0 = p_lo.astype(_IDX)
        hi0 = (p_hi + f_hi).astype(_IDX)

    # ---- counting pass + exclusive scan
    cnt = jnp.where(alive, jnp.maximum(hi0 - lo0, 0), 0).astype(_IDX)
    offs = jnp.cumsum(cnt) - cnt
    total = offs[-1] + cnt[-1]
    overflow = overflow | (total > cap_out)
    total_c = jnp.minimum(total, cap_out)

    # ---- fill: morsel-chunked expand-and-probe into static buffers
    nchunks = cap_out // morsel
    bufs = (jnp.zeros(cap_out, jnp.int32),              # values
            jnp.zeros(cap_out, _IDX),                   # source row
            jnp.zeros(cap_out, _IDX),                   # seed positions
            tuple(jnp.zeros(cap_out, _IDX) for _ in probes),
            jnp.zeros(cap_out, jnp.bool_))              # keep mask

    def cond(st):
        c = st[0]
        return (c < nchunks) & (c * morsel < total_c)

    def body(st):
        c, vals_b, row_b, p0_b, pos_bs, keep_b = st
        if fill_mode == "pallas":
            vals, row, p0, keep, poss = ff_ops.fill_chunk(
                c, total_c, offs, lo0, seed_values, tuple(bounds),
                morsel=morsel, interpret=fill_interpret)
        else:
            vals, row, p0, keep, poss = ff_ref.fill_chunk_ref(
                c, total_c, offs, lo0, seed_values, tuple(bounds),
                morsel=morsel)
        row = row.astype(_IDX)
        p0 = p0.astype(_IDX)
        poss = tuple(p.astype(_IDX) for p in poss)
        at = (c * morsel,)
        vals_b = lax.dynamic_update_slice(vals_b, vals, at)
        row_b = lax.dynamic_update_slice(row_b, row, at)
        p0_b = lax.dynamic_update_slice(p0_b, p0, at)
        pos_bs = tuple(lax.dynamic_update_slice(b, p, at)
                       for b, p in zip(pos_bs, poss))
        keep_b = lax.dynamic_update_slice(keep_b, keep, at)
        return (c + 1, vals_b, row_b, p0_b, pos_bs, keep_b)

    st = lax.while_loop(cond, body, (jnp.asarray(0, _IDX),) + bufs)
    chunks, vals_b, row_b, p0_b, pos_bs, keep = st

    # ---- compaction: dense prefix of surviving rows (order-preserving)
    widx = jnp.cumsum(keep.astype(_IDX)) - 1
    new_count = (widx[-1] + 1).astype(_IDX)
    scat = jnp.where(keep, widx, cap_out)

    def compact(x):
        return jnp.zeros((cap_out,), x.dtype).at[scat].set(x, mode="drop")

    vals_c = compact(vals_b)
    row_c = compact(row_b)
    p0_c = compact(p0_b)
    pos_c = tuple(compact(p) for p in pos_bs)
    rowg = jnp.clip(row_c, 0, cap_in - 1)
    carry_c = tuple(g[rowg] for g in carry)
    # ``total`` is the UNCAPPED counting-pass truth: landed with the
    # closing sync so an overflow retry can size this buffer exactly
    return new_count, overflow, chunks, total, vals_c, p0_c, pos_c, carry_c


@partial(jax.jit, static_argnames=("cap_in", "morsel", "seed_d0",
                                   "probe_d0", "sr"))
def _pipeline_fold(count, seed, probes, ann, leaf_anns, carry, *,
                   cap_in: int, morsel: int, seed_d0: bool,
                   probe_d0: Tuple[bool, ...], sr: Semiring):
    """Per-step (unfused) jitted wrapper around ``_fold_body`` — see
    ``_pipeline_step``."""
    return _fold_body(count, seed, probes, ann, leaf_anns, carry,
                      cap_in=cap_in, morsel=morsel, sr=sr)


def _fold_body(count, seed, probes, ann, leaf_anns, carry, *,
               cap_in: int, morsel: int, sr: Semiring):
    """Terminal-fold companion of ``_extend_body``: identical counting
    pass and morsel-chunked expand-and-probe, but each surviving
    candidate's semiring contribution is segment-reduced straight onto
    its source row — nothing is materialized, so no output capacity and
    no overflow.  Returns the support-compacted frontier (rows with an
    empty candidate intersection are NOT derived — same rule as the host
    fold, which Table 7's SSSP catches when violated)."""
    seed_values, seed_offsets, seed_cursor = seed
    n0 = seed_values.shape[0]
    valid = jnp.arange(cap_in, dtype=_IDX) < count
    lo0, hi0 = _bounds(seed_values, seed_offsets, seed_cursor, cap_in,
                       valid)

    bounds = []
    alive = valid
    gmin = gmax = None
    for vals_k, offs_k, cur_k in probes:
        nk = vals_k.shape[0]
        lo_k, hi_k = _bounds(vals_k, offs_k, cur_k, cap_in, valid)
        alive = alive & (lo_k < hi_k)
        mn = vals_k[jnp.clip(lo_k, 0, nk - 1)]
        mx = vals_k[jnp.clip(hi_k - 1, 0, nk - 1)]
        gmin = mn if gmin is None else jnp.maximum(gmin, mn)
        gmax = mx if gmax is None else jnp.minimum(gmax, mx)
        bounds.append((vals_k, lo_k, hi_k))
    if probes:
        p_lo, _ = I.segment_searchsorted(seed_values, lo0, hi0, gmin)
        p_hi, f_hi = I.segment_searchsorted(seed_values, lo0, hi0, gmax)
        lo0 = p_lo.astype(_IDX)
        hi0 = (p_hi + f_hi).astype(_IDX)

    cnt = jnp.where(alive, jnp.maximum(hi0 - lo0, 0), 0).astype(_IDX)
    offs = jnp.cumsum(cnt) - cnt
    total = offs[-1] + cnt[-1]

    plain = not probes and all(la is None for la in leaf_anns)
    if plain and sr.name == "count":
        # counting a bare segment needs no expansion at all: the
        # counting pass IS the fold (e.g. lollipop's pendant edge)
        folded = cnt.astype(sr.dtype)
        supp = cnt
        chunks = jnp.asarray(0, _IDX)
    else:
        zero = jnp.asarray(sr.zero, dtype=sr.dtype)

        def cond(st):
            c = st[0]
            return c * morsel < total

        def body(st):
            c, folded_b, supp_b = st
            j = c * morsel + jnp.arange(morsel, dtype=_IDX)
            row = jnp.clip(jnp.searchsorted(offs, j, side="right") - 1,
                           0, cap_in - 1).astype(_IDX)
            p0 = lo0[row] + (j - offs[row])
            live = j < total
            vals = seed_values[jnp.clip(p0, 0, max(n0 - 1, 0))]
            keep = live
            poss = [p0]
            for vals_k, lo_k, hi_k in bounds:
                pk, fk = I.segment_searchsorted(vals_k, lo_k[row],
                                                hi_k[row], vals)
                poss.append(pk.astype(_IDX))
                keep = keep & fk
            contrib = sr.lift(morsel)
            for la, pos in zip(leaf_anns, poss):
                if la is None:
                    continue
                nl = la.shape[0]
                at = la[jnp.clip(pos, 0, max(nl - 1, 0))]
                contrib = sr.mul(contrib, at.astype(sr.dtype))
            contrib = jnp.where(keep, contrib, zero)
            seg = row.astype(jnp.int32)
            folded_b = sr.add(folded_b,
                              sr.segment_reduce(contrib, seg, cap_in))
            supp_b = supp_b + jax.ops.segment_sum(
                keep.astype(_IDX), seg, num_segments=cap_in)
            return (c + 1, folded_b, supp_b)

        st = lax.while_loop(
            cond, body,
            (jnp.asarray(0, _IDX),
             jnp.full((cap_in,), sr.zero, dtype=sr.dtype),
             jnp.zeros(cap_in, _IDX)))
        chunks, folded, supp = st

    ann_new = sr.mul(ann, folded.astype(ann.dtype))
    support = supp > 0

    # ---- support compaction (order-preserving dense prefix)
    widx = jnp.cumsum(support.astype(_IDX)) - 1
    new_count = jnp.where(support.any(), widx[-1] + 1, 0).astype(_IDX)
    scat = jnp.where(support, widx, cap_in)

    def compact(x):
        return jnp.zeros((cap_in,), x.dtype).at[scat].set(x, mode="drop")

    ann_c = compact(ann_new)
    carry_c = tuple(compact(g) for g in carry)
    return new_count, chunks, ann_c, carry_c


@partial(jax.jit, static_argnames=("prog", "fill_mode",
                                   "fill_interpret"))
def _bag_program(arrays, cursors0, ann, *, prog: Tuple,
                 fill_mode: str, fill_interpret: bool):
    """ONE bag's whole extension chain as a single traced program.

    ``prog`` is the pure hashable lowering built by ``run_bag``: per
    step the constraining atoms reference operands by index into the
    flat deduplicated ``arrays`` tuple and cursors by canonical ordinal,
    so the trace key is exactly the bag SHAPE (chain of capacities,
    morsels, atom structure, sideways directories, semirings) — two
    executions of the same query shape hit the cache regardless of
    which relation instances flow through.  The Python loop below runs
    at trace time; at run time the whole chain is one XLA module, one
    launch, zero transfers.
    """
    count = jnp.asarray(1, _IDX)
    overflow = jnp.asarray(False)
    morsels = jnp.asarray(0, _IDX)
    cap = 1
    cols: Dict[str, jnp.ndarray] = {}
    cursors = dict(cursors0)
    lcounts = []
    needs = []
    for step in prog:
        kind = step[0]
        if kind == "extend":
            _, var, cap_out, morsel, cons = step

            def trip(c):
                key, vi, oi = c[0], c[1], c[2]
                if oi < 0:
                    return (arrays[vi], None, None)
                return (arrays[vi], arrays[oi], cursors[key])

            seed = trip(cons[0])
            probes = tuple(trip(c) for c in cons[1:])
            sideways = tuple(
                None if c[3] is None else
                (arrays[c[3][0]], arrays[c[3][1]], arrays[c[3][2]],
                 arrays[c[3][3]])
                for c in cons[1:])
            # c[3][4] is already a Python int (run_bag lowered it), so
            # no coercion happens inside this traced program
            sideways_bits = tuple(
                None if c[3] is None else c[3][4]
                for c in cons[1:])
            cons_keys = {c[0] for c in cons}
            col_keys = list(cols)
            cur_keys = [k for k in cursors if k not in cons_keys]
            carry = (tuple(cols[k] for k in col_keys)
                     + tuple(cursors[k] for k in cur_keys)
                     + ((ann,) if ann is not None else ()))
            (count, overflow, chunks, total, vals_c, p0_c, pos_c,
             carry_c) = _extend_body(
                count, overflow, seed, probes, sideways, carry,
                cap_in=cap, cap_out=cap_out, morsel=morsel,
                sideways_bits=sideways_bits, fill_mode=fill_mode,
                fill_interpret=fill_interpret)
            it = iter(carry_c)
            cols = {k: next(it) for k in col_keys}
            cursors = {k: next(it) for k in cur_keys}
            if ann is not None:
                ann = next(it)
            cols[var] = vals_c
            cursors[cons[0][0]] = p0_c
            for c, p in zip(cons[1:], pos_c):
                cursors[c[0]] = p
            cap = cap_out
            morsels = morsels + chunks
            lcounts.append(count)
            needs.append(total)
        elif kind == "fold":
            _, var, morsel, sr, cons = step

            def tripf(c):
                key, vi, oi = c[0], c[1], c[2]
                if oi < 0:
                    return (arrays[vi], None, None)
                return (arrays[vi], arrays[oi], cursors[key])

            seed = tripf(cons[0])
            probes = tuple(tripf(c) for c in cons[1:])
            leaf_anns = tuple(None if c[3] < 0 else arrays[c[3]]
                              for c in cons)
            col_keys = list(cols)
            cur_keys = list(cursors)
            carry = (tuple(cols[k] for k in col_keys)
                     + tuple(cursors[k] for k in cur_keys))
            count, chunks, ann, carry_c = _fold_body(
                count, seed, probes, ann, leaf_anns, carry,
                cap_in=cap, morsel=morsel, sr=sr)
            it = iter(carry_c)
            cols = {k: next(it) for k in col_keys}
            cursors = {k: next(it) for k in cur_keys}
            morsels = morsels + chunks
            lcounts.append(count)
        else:  # annmul
            _, key, ai, sr = step
            la = arrays[ai]
            n = la.shape[0]
            leaf = la[jnp.clip(cursors[key], 0, max(n - 1, 0))]
            ann = sr.mul(ann, leaf.astype(ann.dtype))
    return (count, overflow, morsels, tuple(lcounts), tuple(needs),
            cols, cursors, ann)


@jax.jit
def _fused_probe(values_t, lo_t, hi_t, queries):
    """Probe ``queries`` into every atom's candidate segment in one jitted
    program. Each atom's search is independent of the others' outcomes
    (positions don't depend on which rows survive), so computing all
    searches then AND-ing the membership masks is equivalent to the
    sequential filter — but costs one device round-trip instead of one
    per atom."""
    poss = []
    found_all = None
    for values, lo, hi in zip(values_t, lo_t, hi_t):
        pos, found = I.segment_searchsorted(values, lo, hi, queries)
        poss.append(pos)
        found_all = found if found_all is None else (found_all & found)
    return tuple(poss), found_all


# -------------------------------------------------------------- selection
_BY_NAME = {"numpy": NumpyBackend, "host": NumpyBackend,
            "device": DeviceBackend}
_DEFAULT: Optional[ExecBackend] = None


def make_backend(spec=None) -> ExecBackend:
    """Resolve ``spec`` (instance | name | None) to a fresh backend.
    ``None`` defers to ``REPRO_ENGINE_BACKEND``; unset, the platform
    decides: "device" where JAX's default backend is a TPU, else
    "numpy"."""
    if isinstance(spec, ExecBackend):
        return spec
    if spec is None:
        spec = os.environ.get("REPRO_ENGINE_BACKEND") or (
            "device" if jax.default_backend() == "tpu" else "numpy")
    spec = str(spec).lower()
    if spec not in _BY_NAME:
        raise ValueError(f"unknown backend {spec!r}; "
                         f"expected one of {sorted(_BY_NAME)}")
    return _BY_NAME[spec]()


def default_backend() -> ExecBackend:
    """Process-wide backend for GenericJoin instances constructed without
    an explicit backend (honours REPRO_ENGINE_BACKEND at first use)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = make_backend(None)
    return _DEFAULT
