"""Recursive query evaluation (paper Sections 3.1/3.3).

EmptyHeaded supports Kleene-star rules with two evaluation strategies:

  * **naive** — re-apply the rule body to the full relation each iteration
    (used when every iteration rewrites every annotation, e.g. PageRank);
    convergence = fixed iteration count or float differential.
  * **seminaive** — only propagate from tuples whose annotation changed in
    the previous iteration; selected automatically when the aggregation is
    monotone MIN/MAX (e.g. SSSP).

The shared primitive is the semiring SpMV ``y[u] = ⨁_v A(u,v) ⊗ x[v]`` — a
one-step join-aggregate `Out(x) :- Edge(x,z), X(z)`. Its jitted form is used
by the GNN substrate too and is the PageRank inner loop; a caller may
inject another SpMV (e.g. the ELL-blocked Pallas kernel
``repro.kernels.spmv_ell``, interpret mode only: the TPU compiler refuses
its 1-D gather).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core.semiring import MIN_PLUS, SUM_F32, Semiring
from repro.core.trie import CSRGraph
from repro.kernels.common import audit_avals, host_get

# trace-level audit hook (repro.analysis.jaxpr_audit): when a list, the
# device fixpoint entry points append an abstract record — (kind, name,
# static params, operand avals) — before dispatching, so the auditor can
# retrace the exact fixpoint jaxprs without re-running queries.
AUDIT_LOG: Optional[list] = None


# ------------------------------------------------------------------- spmv
def csr_row_ids(csr: CSRGraph) -> np.ndarray:
    return np.repeat(np.arange(csr.n, dtype=np.int32), csr.degrees)


@partial(jax.jit, static_argnames=("sr", "n"))
def semiring_spmv(sr: Semiring, n: int, row: jnp.ndarray, col: jnp.ndarray,
                  ann: Optional[jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    """y[u] = ⨁_{(u,v) in E} ann(u,v) ⊗ x[v] over any semiring."""
    contrib = x[col]
    if ann is not None:
        contrib = sr.mul(ann, contrib)
    return sr.segment_reduce(contrib, row, n)


# ---------------------------------------------------------------- pagerank
def pagerank(csr: CSRGraph, iters: int = 5, damping: float = 0.85,
             spmv_fn: Optional[Callable] = None) -> np.ndarray:
    """Paper Table 2 PageRank: naive recursion, fixed iteration count.

        N(;w)        :- Edge(x,y); w=<<COUNT(x)>>
        PageRank(x;y):- Edge(x,z); y=1/N.
        PageRank(x;y)*[i=5] :- Edge(x,z),PageRank(z),InvDeg(z);
                               y=0.15+0.85*<<SUM(z)>>.

    The body is a (+,*) join-aggregate = SpMV with InvDeg folded into the
    propagated value, and the whole fixpoint stays on device inside one
    ``fori_loop``. ``spmv_fn`` lets benchmarks inject another SpMV;
    default is the jitted segment-sum SpMV. (The datalog engine's
    PageRank program evaluates through general naive recursion and does
    not route here.)
    """
    n = csr.n
    row = jnp.asarray(csr_row_ids(csr))
    col = jnp.asarray(csr.neighbors)
    out_deg = np.maximum(csr.degrees, 1).astype(np.float32)
    inv_deg = jnp.asarray(1.0 / out_deg)

    x = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    base = (1.0 - damping) / n

    if spmv_fn is None:
        def spmv_fn(x_scaled):
            return semiring_spmv(SUM_F32, n, row, col, None, x_scaled)

    def body(_, x):
        return base + damping * spmv_fn(x * inv_deg)

    x = jax.lax.fori_loop(0, iters, body, x)
    return np.asarray(x)


def pagerank_np(csr: CSRGraph, iters: int = 5, damping: float = 0.85) -> np.ndarray:
    """Numpy oracle."""
    n = csr.n
    row = csr_row_ids(csr)
    col = csr.neighbors
    inv_deg = 1.0 / np.maximum(csr.degrees, 1)
    x = np.full(n, 1.0 / n, dtype=np.float64)
    for _ in range(iters):
        y = np.zeros(n, dtype=np.float64)
        np.add.at(y, row, x[col] * inv_deg[col])
        x = (1 - damping) / n + damping * y
    return x.astype(np.float32)


# -------------------------------------------------------------------- sssp
def sssp(csr: CSRGraph, source: int, weights: Optional[np.ndarray] = None,
         max_iters: Optional[int] = None) -> np.ndarray:
    """Paper Table 2 SSSP: seminaive evaluation of the (min,+) recursion.

        SSSP(x;y) :- Edge("start",x); y=1.
        SSSP(x;y)* :- Edge(w,x),SSSP(w); y=<<MIN(w)>>+1.

    Monotone MIN aggregation triggers seminaive mode: each round relaxes only
    edges out of the frontier (vertices whose distance improved last round).
    The TPU-vectorized form masks non-frontier contributions to +inf inside a
    ``lax.while_loop`` — semantically seminaive (no stale work propagates)
    while keeping fixed shapes for the device.
    """
    n = csr.n
    row = jnp.asarray(csr_row_ids(csr))  # edge source u of (u -> v)
    col = jnp.asarray(csr.neighbors)
    w = jnp.asarray(weights.astype(np.float32)) if weights is not None \
        else jnp.ones((csr.m,), jnp.float32)
    if max_iters is None:
        max_iters = n

    inf = jnp.float32(jnp.inf)
    dist0 = jnp.full((n,), inf).at[source].set(0.0)
    frontier0 = jnp.zeros((n,), jnp.bool_).at[source].set(True)

    def cond(state):
        _, frontier, it = state
        return jnp.logical_and(frontier.any(), it < max_iters)

    def body(state):
        dist, frontier, it = state
        # seminaive: only edges whose source is in the frontier contribute
        src_d = jnp.where(frontier[row], dist[row], inf)
        cand = MIN_PLUS.segment_reduce(src_d + w, col, n)
        new = jnp.minimum(dist, cand)
        return new, new < dist, it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, frontier0, jnp.int32(0)))
    return np.asarray(dist)


def sssp_np(csr: CSRGraph, source: int, weights: Optional[np.ndarray] = None) -> np.ndarray:
    """Numpy seminaive oracle with true work elimination (frontier gathers).

    Termination: Bellman–Ford shortest paths use at most ``n - 1`` edges,
    so improvements can only occur in rounds 1..n-1 (round k finds paths
    of exactly k edges). One extra round is allowed as the detection
    pass: any improvement there implies a negative cycle reachable from
    the source, and the oracle raises instead of relaxing forever.
    """
    n = csr.n
    w = weights if weights is not None else np.ones(csr.m, np.float32)
    dist = np.full(n, np.inf, dtype=np.float64)
    dist[source] = 0.0
    frontier = np.array([source])
    it = 0
    while len(frontier):
        if it >= n:
            # the frontier is non-empty after the round-n detection pass:
            # a path with >= n edges improved some distance
            raise ValueError(
                "sssp_np: improvements after round n imply a negative "
                "cycle reachable from the source")
        it += 1
        # gather out-edges of the frontier only (the seminaive delta)
        segs = [(csr.offsets[u], csr.offsets[u + 1]) for u in frontier]
        idx = np.concatenate([np.arange(a, b) for a, b in segs]) if segs else np.zeros(0, np.int64)
        if len(idx) == 0:
            break
        srcs = np.repeat(frontier, [b - a for a, b in segs])
        dsts = csr.neighbors[idx]
        cand = dist[srcs] + w[idx]
        order = np.argsort(dsts, kind="stable")
        dsts_s, cand_s = dsts[order], cand[order]
        first = np.ones(len(dsts_s), bool)
        first[1:] = dsts_s[1:] != dsts_s[:-1]
        seg_id = np.cumsum(first) - 1
        best = np.full(seg_id[-1] + 1 if len(seg_id) else 0, np.inf)
        np.minimum.at(best, seg_id, cand_s)
        uniq = dsts_s[first]
        improved = best < dist[uniq]
        dist[uniq[improved]] = best[improved]
        frontier = uniq[improved]
    return dist.astype(np.float32)


# ------------------------------------- engine device-resident recursion
# The datalog engine's recursive rules (``Engine._seminaive`` /
# ``Engine._naive``) historically rebuilt a host delta trie and re-ran the
# whole Generic-Join pipeline every round.  When the rule body is a
# semiring SpMV — one binary atom E(h,r) or E(r,h), the recursive atom
# Rec(r), and optional unary annotated atoms A_i(r) — the entire fixpoint
# can instead run on device with fixed shapes: the frontier/delta is a
# masked vector over the vertex domain (mirroring :func:`sssp`) and every
# round is one step of a jitted ``lax.while_loop`` / ``fori_loop``.  The
# engine recognizes the shape and calls these entry points; anything else
# falls back to the host loop (the differential oracle).


class ExprFn:
    """Hashable, jit-stable wrapper around ``datalog.eval_expr`` with the
    scalar-relation environment snapshotted at construction.  Hash/eq key
    on (expr repr, scalar values) so ``jax.jit`` treats repeated rounds —
    and repeated queries over unchanged scalars — as the same static
    argument instead of recompiling."""

    def __init__(self, expr, scalars):
        from repro.core.datalog import eval_expr  # cycle-free at call time
        self._eval = eval_expr
        self.expr = expr
        self.scalars = {k: float(v) for k, v in scalars.items()}
        self._key = (repr(expr),
                     tuple(sorted(self.scalars.items())))

    def __call__(self, agg_value):
        return self._eval(self.expr, agg_value, self.scalars)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, ExprFn) and self._key == other._key


@partial(jax.jit, static_argnames=("sr", "apply_expr", "max_rounds", "n"))
def _seminaive_device(sr: Semiring, apply_expr, max_rounds: int, n: int,
                      gather, scatter, edge_ann, state0, frontier0):
    """Whole seminaive fixpoint on device: fixed-shape masked delta.

    ``state`` is the annotation vector over the dense vertex domain
    (``sr.zero`` = "not derived"); ``frontier`` masks the vertices whose
    annotation improved last round (the seminaive delta).  One round:
    propagate frontier annotations along edges (gather → ⊗ edge
    annotation → segment-⨁ into the head vertex), apply the rule's
    annotation expression to derived candidates only, and merge with ⨁.
    Returns ``(state, rounds)``; nothing crosses the host boundary until
    the caller's single final ``device_get``.
    """
    zero = jnp.asarray(sr.zero, dtype=sr.dtype)

    def cond(s):
        _, frontier, it = s
        return jnp.logical_and(frontier.any(), it < max_rounds)

    def body(s):
        state, frontier, it = s
        src = jnp.where(frontier[gather], state[gather], zero)
        contrib = src if edge_ann is None else sr.mul(edge_ann, src)
        agg = sr.segment_reduce(contrib, scatter, n)
        derived = agg != zero
        cand = jnp.where(derived, apply_expr(agg).astype(sr.dtype), zero)
        new = sr.add(state, cand)
        return new, new != state, it + 1

    state, _, rounds = jax.lax.while_loop(
        cond, body, (state0, frontier0, jnp.int32(0)))
    return state, rounds


def seminaive_device_fixpoint(sr: Semiring, apply_expr: ExprFn,
                              gather: np.ndarray, scatter: np.ndarray,
                              edge_ann: Optional[np.ndarray], n: int,
                              keys0: np.ndarray, ann0: np.ndarray,
                              max_rounds: int):
    """Host entry point: densify the initial relation over [0, n), run the
    jitted while-loop, and sparsify the result back to (keys, ann).
    Exactly ONE host sync happens, after the loop.  The operands are put
    on the device inside the span ``eh.recursion.prepare`` and counted
    in ``upload.bytes``."""
    dt = jnp.zeros((), sr.dtype).dtype
    with trace.span("eh.recursion.prepare"):
        trace.upload(keys0, ann0, gather, scatter, edge_ann)
        k0 = jnp.asarray(keys0)
        state0 = jnp.full((n,), sr.zero, dtype=dt)
        state0 = state0.at[k0].set(jnp.asarray(ann0).astype(dt))
        frontier0 = jnp.zeros((n,), jnp.bool_).at[k0].set(True)
        ea = None if edge_ann is None else jnp.asarray(edge_ann).astype(dt)
        g, sc = jnp.asarray(gather), jnp.asarray(scatter)
    if AUDIT_LOG is not None:
        AUDIT_LOG.append(
            ("seminaive", "seminaive", sr, apply_expr, int(max_rounds),
             int(n), audit_avals((g, sc, ea, state0, frontier0))))
    state, rounds = _seminaive_device(
        sr, apply_expr, int(max_rounds), int(n), g, sc, ea, state0,
        frontier0)
    state_h, rounds_h = host_get((state, rounds))  # the one sync
    state_h = np.asarray(state_h, dtype=np.float64)
    derived = state_h != float(np.asarray(sr.zero))
    keys = np.flatnonzero(derived).astype(np.int64)
    return keys, state_h[keys], int(rounds_h)


@partial(jax.jit, static_argnames=("sr", "apply_expr", "iters", "tol",
                                   "max_rounds", "k", "factor_kinds"))
def _naive_device(sr: Semiring, apply_expr, iters: Optional[int],
                  tol: Optional[float], max_rounds: int, k: int,
                  factor_kinds: Tuple[str, ...],
                  out_idx, rec_idx, factor_anns, ann0):
    """Whole naive fixpoint on device: the head key set is FIXED across
    rounds (naive recursion re-derives every annotation), so one round is
    a fixed-shape gather → ⊗-chain → segment-⨁ → expression rewrite over
    the key positions.  ``factor_kinds`` mirrors the body-atom order of
    every annotated atom ("rec" = the recursive atom's live state,
    "static" = a round-invariant annotation gather), so the ⊗-chain
    multiplies in exactly the order the Generic-Join fold would.
    Convergence: fixed iteration count (``fori_loop``) or float
    differential checked ON DEVICE every round inside the while-loop —
    zero per-round host syncs either way."""

    assert "rec" in factor_kinds, "naive round needs the recursive factor"

    def round_body(ann):
        contrib = None
        si = 0
        for kind in factor_kinds:
            if kind == "rec":
                f = ann[rec_idx]
            else:
                f = factor_anns[si]
                si += 1
            contrib = f if contrib is None else sr.mul(contrib, f)
        agg = sr.segment_reduce(contrib, out_idx, k)
        return apply_expr(agg).astype(ann0.dtype)

    if iters is not None:
        ann = jax.lax.fori_loop(0, iters, lambda _, a: round_body(a), ann0)
        return ann, jnp.int32(iters)

    def cond(s):
        _, diff, it = s
        return jnp.logical_and(it < max_rounds, diff > tol)

    def body(s):
        ann, _, it = s
        new = round_body(ann)
        return new, jnp.max(jnp.abs(new - ann)), it + 1

    ann, _, rounds = jax.lax.while_loop(
        cond, body, (ann0, jnp.asarray(jnp.inf, ann0.dtype), jnp.int32(0)))
    return ann, rounds


def naive_device_fixpoint(sr: Semiring, apply_expr: ExprFn,
                          out_idx: np.ndarray, rec_idx: np.ndarray,
                          factor_kinds: Tuple[str, ...],
                          factor_anns: List[np.ndarray], k: int,
                          ann0: np.ndarray, iters: Optional[int],
                          tol: Optional[float], max_rounds: int):
    """Host entry point for the device naive loop; ONE final sync.  The
    operands are put on the device inside the span
    ``eh.recursion.prepare`` and counted in ``upload.bytes``."""
    dt = jnp.zeros((), sr.dtype).dtype
    with trace.span("eh.recursion.prepare"):
        trace.upload(out_idx, rec_idx, ann0, *factor_anns)
        anns = tuple(jnp.asarray(a).astype(dt) for a in factor_anns)
        oi, ri = jnp.asarray(out_idx), jnp.asarray(rec_idx)
        a0 = jnp.asarray(ann0).astype(dt)
    if AUDIT_LOG is not None:
        AUDIT_LOG.append(
            ("naive", "naive", sr, apply_expr, iters, tol,
             int(max_rounds), int(k), tuple(factor_kinds),
             audit_avals((oi, ri, anns, a0))))
    ann, rounds = _naive_device(
        sr, apply_expr, iters, tol, int(max_rounds), int(k),
        tuple(factor_kinds), oi, ri, anns, a0)
    ann_h, rounds_h = host_get((ann, rounds))
    return np.asarray(ann_h, dtype=np.float64), int(rounds_h)


# ----------------------------------------------------- generic fixpoint API
def fixpoint(step: Callable, x0, *, iters: Optional[int] = None,
             tol: Optional[float] = None, max_iters: int = 10_000,
             check_every: int = 8, backend=None):
    """Driver matching the paper's convergence criteria: a fixed number of
    iterations (i=K) or a float differential (c=eps).

    The tolerance path no longer forces a host sync per iteration: steps
    run in blocks of ``check_every`` with the per-step differentials
    computed on device and ONE host read per block (the sync that used to
    happen every round).  The returned value is still the FIRST iterate
    at-or-past convergence — later block members are discarded, so the
    result is identical to the per-iteration check.  ``backend`` (an
    ``ExecBackend``) records the sync discipline in its dispatch counters
    (``fixpoint.host_syncs`` vs ``fixpoint.steps``).
    """
    stats = getattr(backend, "stats", None)

    def bump(key, v=1):
        if stats is not None:
            stats[key] += v

    if iters is not None:
        x = x0
        for _ in range(iters):
            x = step(x)
        bump("fixpoint.steps", iters)
        return x
    assert tol is not None
    check_every = max(1, int(check_every))
    x = x0
    done = 0
    while done < max_iters:
        block = min(check_every, max_iters - done)
        xs = [x]
        for _ in range(block):
            xs.append(step(xs[-1]))
        diffs = jnp.stack([jnp.max(jnp.abs(jnp.asarray(xs[i + 1])
                                           - jnp.asarray(xs[i])))
                           for i in range(block)])
        hit = np.asarray(diffs <= tol)  # the block's single host sync
        bump("fixpoint.host_syncs")
        done += block
        if hit.any():
            first = int(np.argmax(hit))
            bump("fixpoint.steps", first + 1)
            return xs[first + 1]
        bump("fixpoint.steps", block)
        x = xs[-1]
    return x
