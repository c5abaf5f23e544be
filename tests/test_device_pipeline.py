"""Zero-host-sync Generic Join: the device-resident count-then-fill
pipeline (ROADMAP item 3).

Three layers of proof:

* **counter proofs** — ``extend.host_syncs`` is exactly zero for the
  paper queries on the DeviceBackend, with >= 1 ``extend.closing_syncs``
  (the single landing per join) — and stays zero under morsel spill and
  overflow retry;
* **differential oracles** — the pipelined path against both the
  NumpyBackend and the pinned per-extension-sync device path
  (``Engine(device_pipeline=False)`` / ``REPRO_DEVICE_PIPELINE=off``),
  exact listing parity included;
* **buffer-sizing guards** — ``frontier_capacity`` clamps the
  stats-informed AGM target to the true cross-product bound, rejects
  un-sizable estimates loudly, and a skewed high-fanout trie (the case
  mean-fanout statistics under-estimate) still answers exactly via the
  overflow retry.

``hypothesis`` is not available in this environment, so the property
test is a seeded-random sweep over small acyclic query shapes — same
oracle discipline, deterministic seeds.
"""
import numpy as np
import pytest

from conftest import random_undirected_graph
from repro.core import statistics as S
from repro.core import workload as W
from repro.core.engine import Engine
from repro.core.gj import GenericJoin
from repro.core.plan_ir import BagHints
from repro.core.semiring import COUNT
from repro.core.trie import Trie

ALIASES = W.ALIASES

PAPER_QUERIES = {
    "triangle_count": W.TRIANGLE_COUNT,
    "triangle_list": W.TRIANGLE_LIST,
    "4clique": W.FOUR_CLIQUE,
    "lollipop": W.LOLLIPOP,
    "barbell": W.BARBELL,
    "pagerank": W.pagerank_program(iters=5),
    "sssp": W.sssp_program("{s}"),
}


def make_engine(src, dst, backend, **kw):
    eng = Engine(backend=backend, **kw)
    eng.load_edges("Edge", src, dst)
    for a in ALIASES:
        eng.alias(a, "Edge")
    return eng


def assert_same_result(r1, r2):
    assert r1.vars == r2.vars
    for v in r1.vars:
        np.testing.assert_array_equal(np.asarray(r1.columns[v]),
                                      np.asarray(r2.columns[v]))
    if r1.annotation is None:
        assert r2.annotation is None
    else:
        np.testing.assert_allclose(np.asarray(r1.annotation, np.float64),
                                   np.asarray(r2.annotation, np.float64),
                                   rtol=1e-6, atol=1e-7)


def sync_delta(eng, q):
    before = dict(eng.backend.stats)
    res = eng.query(q)
    d = {k: eng.backend.stats.get(k, 0) - before.get(k, 0)
         for k in set(eng.backend.stats) | set(before)}
    return res, {k: v for k, v in d.items() if v}


# ------------------------------------------------------ counter proofs
@pytest.mark.parametrize("qname", sorted(PAPER_QUERIES))
def test_paper_queries_zero_host_syncs_on_device(qname):
    """THE acceptance criterion: no per-extension host round-trips —
    statically impossible paths aside, the dynamic counter must be 0
    with at least one closing sync per executed join."""
    src, dst, _ = random_undirected_graph(30, 0.3, 7)
    q = PAPER_QUERIES[qname].replace("{s}", str(int(src[0])))
    eng = make_engine(src, dst, "device")
    assert eng.device_pipeline        # on by default
    _, d = sync_delta(eng, q)
    assert d.get("extend.host_syncs", 0) == 0, (qname, d)
    if d.get("extend.calls", 0):
        assert d.get("extend.closing_syncs", 0) >= 1, (qname, d)
        assert (d.get("extend.pipeline_extends", 0)
                == d.get("extend.calls", 0)), (qname, d)


def test_closing_syncs_bounded_by_joins():
    """One landing per GenericJoin attempt — never one per extension."""
    src, dst, _ = random_undirected_graph(30, 0.3, 7)
    eng = make_engine(src, dst, "device")
    _, d = sync_delta(eng, PAPER_QUERIES["4clique"])
    assert (d.get("extend.closing_syncs", 0)
            <= d.get("extend.pipeline_extends", 0)
            + d.get("pipeline.device_folds", 0)), d


def test_morsel_spill_keeps_parity_and_zero_syncs(monkeypatch):
    """A tiny REPRO_MORSEL_SIZE forces frontiers to spill across many
    fill chunks of the same device loop — more morsels than extensions,
    still exact, still zero host syncs."""
    monkeypatch.setenv("REPRO_MORSEL_SIZE", "8")
    src, dst, _ = random_undirected_graph(26, 0.35, 3)
    oracle = make_engine(src, dst, "numpy").query(
        PAPER_QUERIES["triangle_list"])
    eng = make_engine(src, dst, "device")
    res, d = sync_delta(eng, PAPER_QUERIES["triangle_list"])
    assert_same_result(oracle, res)
    assert d.get("extend.host_syncs", 0) == 0, d
    assert d.get("pipeline.morsels", 0) > d.get("extend.pipeline_extends",
                                                0), d


def test_env_escape_hatch(monkeypatch):
    """REPRO_DEVICE_PIPELINE=off pins the per-extension-sync oracle."""
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "off")
    src, dst, _ = random_undirected_graph(20, 0.3, 5)
    eng = make_engine(src, dst, "device")
    assert not eng.device_pipeline
    _, d = sync_delta(eng, PAPER_QUERIES["triangle_list"])
    assert d.get("extend.host_syncs", 0) == d.get("extend.calls", 0) > 0
    assert d.get("extend.closing_syncs", 0) == 0


# -------------------------------------------------- differential oracle
@pytest.mark.parametrize("qname", sorted(PAPER_QUERIES))
def test_pipeline_matches_pinned_sync_path(qname):
    """Satellite 6: Engine(device_pipeline=False) is the differential
    oracle — exact parity on every paper query."""
    src, dst, _ = random_undirected_graph(28, 0.25, 11)
    q = PAPER_QUERIES[qname].replace("{s}", str(int(src[0])))
    r_off = make_engine(src, dst, "device",
                        device_pipeline=False).query(q)
    r_on = make_engine(src, dst, "device", device_pipeline=True).query(q)
    assert_same_result(r_off, r_on)


# ------------------------------------------------------ overflow retry
def test_overflow_retries_device_resident_with_exact_caps():
    """A hint that lies about the frontier size trips the overflow flag
    at landing; the join must retry device-resident with buffers sized
    from the aborted attempt's counting-pass totals — right answer,
    still zero host syncs.  Steps downstream of the first overflow
    counted over a truncated frontier, so their measurements firm up
    one retry at a time: with every cap lied about, convergence takes
    one retry per overflowing level, never the host path."""
    src, dst, _ = random_undirected_graph(24, 0.4, 9)
    cols = [np.asarray(src, np.int64), np.asarray(dst, np.int64)]
    ta = Trie.build("E0", ("x", "y"), cols)
    tb = Trie.build("E1", ("y", "z"), cols)
    tc = Trie.build("E2", ("x", "z"), cols)
    hints = BagHints(extend_caps={"x": 1.0, "y": 1.0, "z": 1.0}, morsel=8)

    def run(backend, h):
        gj = GenericJoin(
            [(ta, ("x", "y")), (tb, ("y", "z")), (tc, ("x", "z"))],
            ("x", "y", "z"), ("x", "y", "z"), backend=backend, hints=h)
        return gj.run()

    from repro.core.backend import DeviceBackend, NumpyBackend
    oracle = run(NumpyBackend(), None)
    dev = DeviceBackend()
    res = run(dev, hints)
    assert_same_result(oracle, res)
    st = dict(dev.stats)
    assert 1 <= st.get("pipeline.retries", 0) <= 2, st
    assert st.get("extend.host_syncs", 0) == 0, st
    # every attempt stayed device-resident (no host-path extends)
    assert st.get("extend.calls") == st.get("extend.pipeline_extends"), st
    # the converged measurements were recorded as engine-lifetime
    # feedback: re-running the SAME bag shape on the same backend sizes
    # its buffers right the first time — zero further retries
    assert dev.cap_feedback, dict(dev.stats)
    before = st.get("pipeline.retries", 0)
    res2 = run(dev, hints)
    assert_same_result(oracle, res2)
    assert dev.stats.get("pipeline.retries", 0) == before, dict(dev.stats)
    assert dev.stats.get("extend.host_syncs", 0) == 0, dict(dev.stats)


def test_skewed_high_fanout_trie_regression():
    """Satellite 2: a hub graph (one vertex adjacent to everything)
    makes mean-fanout statistics drastically under-estimate the
    expansion; the AGM-capped allocation must clamp to the true
    cross-product bound / retry rather than drop rows."""
    n = 40
    hub_src = np.concatenate([np.zeros(n - 1, np.int64),
                              np.arange(1, n, dtype=np.int64),
                              np.arange(1, n - 1, dtype=np.int64)])
    hub_dst = np.concatenate([np.arange(1, n, dtype=np.int64),
                              np.zeros(n - 1, np.int64),
                              np.arange(2, n, dtype=np.int64)])
    oracle = make_engine(hub_src, hub_dst, "numpy").query(
        PAPER_QUERIES["triangle_list"])
    eng = make_engine(hub_src, hub_dst, "device")
    res, d = sync_delta(eng, PAPER_QUERIES["triangle_list"])
    assert_same_result(oracle, res)
    assert d.get("extend.host_syncs", 0) == 0, d


# -------------------------------------------------- buffer-sizing guard
def test_frontier_capacity_clamps_to_cross_bound():
    # est far above the exact bound: the bound wins (plus bucketing)
    assert S.frontier_capacity(10**9, 100, 64) == 128
    # est below: est + morsel slack, bucketed to a power-of-two multiple
    cap = S.frontier_capacity(100, 10**9, 64)
    assert cap >= 100 and cap % 64 == 0 and (cap & (cap - 1)) == 0


def test_frontier_capacity_respects_max_buffer():
    assert S.frontier_capacity(10**12, 10**12, 256) \
        <= S.PIPELINE_MAX_BUFFER


def test_frontier_capacity_tiny_estimates_stay_tiny():
    # regression: sizing slack off the MORSEL ballooned an est≈1
    # extension to a full morsel-sized buffer (256x over-allocation
    # zeroed and scattered every step); slack now scales with the
    # estimate and the bucket floor is PIPELINE_MIN_BUCKET
    assert S.frontier_capacity(0, 10**6, 256) == S.PIPELINE_MIN_BUCKET
    assert S.frontier_capacity(1, 10**6, 2048) == S.PIPELINE_MIN_BUCKET
    # capacity still covers the true bound when it is small
    assert S.frontier_capacity(0, 3, 256) >= 3
    # and keeps real estimate-scaled headroom for non-tiny frontiers
    assert S.frontier_capacity(1000, 10**9, 64) >= 1500


def test_frontier_capacity_rejects_unsizable_estimates():
    for bad in (None, float("nan"), float("inf"), -1.0):
        with pytest.raises(ValueError):
            S.frontier_capacity(bad, 1000, 256)
    with pytest.raises(ValueError):
        S.frontier_capacity(100, -5, 256)
    with pytest.raises(ValueError):
        S.frontier_capacity(100, 1000, 0)


def test_frontier_capacity_huge_inputs_no_overflow():
    # python-int arithmetic: must not wrap or raise on astronomical bounds
    cap = S.frontier_capacity(float(2**80), 2**90, 1024)
    assert 0 < cap <= S.PIPELINE_MAX_BUFFER


# --------------------------------------------- seeded property sweep
# hypothesis is not installed in this environment (and adding deps is
# off the table), so: deterministic seeds over random acyclic shapes.
_SHAPES = [
    # (head vars, body as (rel_vars, ...)) — all acyclic, <= 4 atoms
    (("x", "y"), (("x", "y"),)),
    (("x", "z"), (("x", "y"), ("y", "z"))),
    (("x", "y", "z"), (("x", "y"), ("y", "z"))),
    (("x", "w"), (("x", "y"), ("y", "z"), ("z", "w"))),
    (("x", "y", "z", "w"), (("x", "y"), ("x", "z"), ("z", "w"))),
    (("x", "y", "z", "w"), (("x", "y"), ("y", "z"), ("y", "w"))),
    (("y", "z", "w"), (("x", "y"), ("x", "z"), ("x", "w"))),
]


def _program(head, body, agg):
    rels = ["R", "S", "T", "U"]
    atoms = ", ".join(f"{rels[i]}({a},{b})"
                      for i, (a, b) in enumerate(body))
    if agg:
        return f"Q(;c:long) :- {atoms}; c=<<COUNT(*)>>."
    return f"Q({','.join(head)}) :- {atoms}."


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_acyclic_queries_match_numpy_oracle(seed):
    """Satellite 3: random small graphs x random acyclic query shapes,
    listing and COUNT flavors, against the NumpyBackend — on BOTH device
    paths, with the zero-sync counter proof on the pipelined one."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 26))
    m = int(rng.integers(n, 4 * n))
    src = rng.integers(0, n, m).astype(np.int64)
    dst = rng.integers(0, n, m).astype(np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]

    e_np = make_engine(src, dst, "numpy")
    e_on = make_engine(src, dst, "device")
    e_off = make_engine(src, dst, "device", device_pipeline=False)
    for i, (head, body) in enumerate(_SHAPES):
        agg = (seed + i) % 2 == 0
        q = _program(head, body, agg)
        oracle = e_np.query(q)
        res_on, d = sync_delta(e_on, q)
        res_off = e_off.query(q)
        assert_same_result(oracle, res_on)
        assert_same_result(oracle, res_off)
        assert d.get("extend.host_syncs", 0) == 0, (q, d)


# ------------------------------------------------- whole-bag fusion (PR 8)
def test_fused_bag_is_one_launch_per_join():
    """THE launch-budget criterion: with fusion on (the default), every
    executed bag is ONE jitted program — ``pipeline.launches`` equals
    ``extend.closing_syncs`` (one landing per join attempt), and is
    exactly 1 for the single-bag triangle queries."""
    src, dst, _ = random_undirected_graph(30, 0.3, 7)
    for qname in ("triangle_count", "triangle_list"):
        eng = make_engine(src, dst, "device")
        assert eng.fused_bags          # on by default
        _, d = sync_delta(eng, PAPER_QUERIES[qname])
        assert d.get("pipeline.launches", 0) == 1, (qname, d)
        assert d.get("extend.closing_syncs", 0) == 1, (qname, d)
        assert d.get("extend.host_syncs", 0) == 0, (qname, d)


@pytest.mark.parametrize("qname", sorted(PAPER_QUERIES))
def test_fused_launches_equal_closing_syncs(qname):
    """The invariant generalizes to every paper query, multi-bag and
    recursive ones included: launches == landings, never one launch per
    attribute step."""
    src, dst, _ = random_undirected_graph(30, 0.3, 7)
    q = PAPER_QUERIES[qname].replace("{s}", str(int(src[0])))
    eng = make_engine(src, dst, "device")
    _, d = sync_delta(eng, q)
    assert (d.get("pipeline.launches", 0)
            == d.get("extend.closing_syncs", 0)), (qname, d)


@pytest.mark.parametrize("qname", sorted(PAPER_QUERIES))
def test_fused_matches_per_step_pipeline(qname):
    """Satellite 3: Engine(fused_bags=False) pins the per-attribute-step
    pipeline as the differential oracle — exact parity on every paper
    query, with the unfused leg paying one launch per step."""
    src, dst, _ = random_undirected_graph(28, 0.25, 13)
    q = PAPER_QUERIES[qname].replace("{s}", str(int(src[0])))
    e_unf = make_engine(src, dst, "device", fused_bags=False)
    assert not e_unf.fused_bags
    r_unf, d_unf = sync_delta(e_unf, q)
    r_fus = make_engine(src, dst, "device", fused_bags=True).query(q)
    assert_same_result(r_unf, r_fus)
    # unfused: one launch per pipelined step, not per bag
    assert d_unf.get("pipeline.launches", 0) == (
        d_unf.get("extend.pipeline_extends", 0)
        + d_unf.get("pipeline.device_folds", 0)), (qname, d_unf)


@pytest.mark.parametrize("seed", [4, 5])
def test_random_acyclic_queries_fused_parity(seed):
    """The seeded-random sweep, fused leg: random graphs x the acyclic
    shapes, fused vs unfused vs the NumpyBackend — exact, zero host
    syncs, and never more launches fused than unfused."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 26))
    m = int(rng.integers(n, 4 * n))
    src = rng.integers(0, n, m).astype(np.int64)
    dst = rng.integers(0, n, m).astype(np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]

    e_np = make_engine(src, dst, "numpy")
    e_fus = make_engine(src, dst, "device", fused_bags=True)
    e_unf = make_engine(src, dst, "device", fused_bags=False)
    for i, (head, body) in enumerate(_SHAPES):
        agg = (seed + i) % 2 == 0
        q = _program(head, body, agg)
        oracle = e_np.query(q)
        r_fus, d_fus = sync_delta(e_fus, q)
        r_unf, d_unf = sync_delta(e_unf, q)
        assert_same_result(oracle, r_fus)
        assert_same_result(oracle, r_unf)
        assert d_fus.get("extend.host_syncs", 0) == 0, (q, d_fus)
        assert (d_fus.get("pipeline.launches", 0)
                <= d_unf.get("pipeline.launches", 0)), (q, d_fus, d_unf)


def test_fused_overflow_retry_one_launch_per_attempt():
    """Overflow retry under fusion: a lying cap hint trips the sticky
    overflow flag at landing and the bag re-traces with count-informed
    sizes — each ATTEMPT is still one launch (launches == landings),
    zero host syncs, exact answer."""
    src, dst, _ = random_undirected_graph(24, 0.4, 9)
    cols = [np.asarray(src, np.int64), np.asarray(dst, np.int64)]
    ta = Trie.build("E0", ("x", "y"), cols)
    tb = Trie.build("E1", ("y", "z"), cols)
    tc = Trie.build("E2", ("x", "z"), cols)
    hints = BagHints(extend_caps={"x": 1.0, "y": 1.0, "z": 1.0}, morsel=8)

    from repro.core.backend import DeviceBackend, NumpyBackend

    def run(backend, h):
        gj = GenericJoin(
            [(ta, ("x", "y")), (tb, ("y", "z")), (tc, ("x", "z"))],
            ("x", "y", "z"), ("x", "y", "z"), backend=backend, hints=h)
        return gj.run()

    oracle = run(NumpyBackend(), None)
    dev = DeviceBackend()
    assert dev.fuse_bags
    res = run(dev, hints)
    assert_same_result(oracle, res)
    st = dict(dev.stats)
    assert st.get("pipeline.retries", 0) >= 1, st
    assert st.get("extend.host_syncs", 0) == 0, st
    assert (st.get("pipeline.launches", 0)
            == st.get("extend.closing_syncs", 0)), st


def test_fused_env_escape_hatch(monkeypatch):
    """REPRO_FUSED_BAG=off pins the per-step pipeline — still exact,
    still zero host syncs, one launch per step."""
    monkeypatch.setenv("REPRO_FUSED_BAG", "off")
    src, dst, _ = random_undirected_graph(20, 0.3, 5)
    oracle = make_engine(src, dst, "numpy").query(
        PAPER_QUERIES["triangle_list"])
    eng = make_engine(src, dst, "device")
    assert not eng.fused_bags
    res, d = sync_delta(eng, PAPER_QUERIES["triangle_list"])
    assert_same_result(oracle, res)
    assert d.get("extend.host_syncs", 0) == 0, d
    assert d.get("pipeline.launches", 0) == (
        d.get("extend.pipeline_extends", 0)
        + d.get("pipeline.device_folds", 0)) > 1, d


def test_frontier_fill_jnp_mode_parity(monkeypatch):
    """The jnp fill is the default fill stage (the one the TPU compiler
    accepts); REPRO_FRONTIER_FILL=pallas swaps in the frontier-fill
    kernel, in interpret mode, inside the same traced program — both are
    bit-identical to the oracle."""
    src, dst, _ = random_undirected_graph(22, 0.3, 3)
    oracle = make_engine(src, dst, "numpy").query(PAPER_QUERIES["4clique"])
    for mode in ("jnp", "pallas"):
        if mode == "pallas":
            monkeypatch.setenv("REPRO_FRONTIER_FILL", "pallas")
        eng = make_engine(src, dst, "device")
        assert eng.backend.fill_mode == mode
        res, d = sync_delta(eng, PAPER_QUERIES["4clique"])
        assert_same_result(oracle, res)
        assert d.get("extend.host_syncs", 0) == 0, d


def test_bag_launch_compiles_once_per_shape():
    """The first triangle query on a new bag shape compiles inside the
    ``eh.bag.launch`` span; re-dispatching the same traced program (a
    fresh bag cache, so the bag launches again) compiles nothing there.
    Span totals stay out of the exact-compared dispatch summary."""
    import jax
    from repro.core.executor import BagResultCache
    jax.clear_caches()          # every bag shape is new to this process
    src, dst, _ = random_undirected_graph(20, 0.3, 5)
    eng = make_engine(src, dst, "device")
    key = "span.eh.bag.launch.compiles"
    eng.query(PAPER_QUERIES["triangle_count"])
    first = eng.backend.stats[key]
    assert first >= 1, dict(eng.backend.stats)
    eng.bag_cache = BagResultCache()
    launches = eng.backend.stats["pipeline.launches"]
    eng.query(PAPER_QUERIES["triangle_count"])
    assert eng.backend.stats["pipeline.launches"] > launches
    assert eng.backend.stats[key] == first, dict(eng.backend.stats)
    assert key not in eng.dispatch_summary()


# ------------------------------------------- bitset sideways filtering
def _complete_graph(n):
    s, d = np.nonzero(~np.eye(n, dtype=bool))
    return s.astype(np.int64), d.astype(np.int64)


def test_sideways_bitset_fires_on_dense_graph(monkeypatch):
    """Tentpole leg 3: on a dense graph the planner annotates depth-1
    probes ``sideways='bitset'`` and the counting pass intersects
    Figure-6 block directories — counter-proven (``pipeline.sideways_
    extends`` + one bitset-directory upload), exact against both the
    numpy oracle and the REPRO_SIDEWAYS_BITSET=off leg."""
    src, dst = _complete_graph(14)
    oracle = make_engine(src, dst, "numpy").query(PAPER_QUERIES["4clique"])
    eng = make_engine(src, dst, "device")
    res, d = sync_delta(eng, PAPER_QUERIES["4clique"])
    assert_same_result(oracle, res)
    assert d.get("pipeline.sideways_extends", 0) >= 1, d
    assert d.get("upload.bitset_dirs", 0) >= 1, d
    assert d.get("extend.host_syncs", 0) == 0, d

    monkeypatch.setenv("REPRO_SIDEWAYS_BITSET", "off")
    eng2 = make_engine(src, dst, "device")
    res2, d2 = sync_delta(eng2, PAPER_QUERIES["4clique"])
    assert_same_result(oracle, res2)
    assert d2.get("pipeline.sideways_extends", 0) == 0, d2


def test_sideways_stays_off_on_sparse_graph():
    """The statistics density gate: adjacency sets whose neighbors are
    scattered across a wide ID range (inverse density above the
    Algorithm-3 threshold) fall in the sparse cohort, so the planner
    must not annotate sideways filtering.  NB small-universe graphs
    don't exercise this — a degree-1 set has span 1 and is trivially
    'dense' — hence the deliberately spread-out degree-2 graph."""
    rng = np.random.default_rng(17)
    n = 4000
    src = np.repeat(np.arange(n, dtype=np.int64), 2)
    dst = rng.integers(0, n, 2 * n).astype(np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    eng = make_engine(src, dst, "device")
    _, d = sync_delta(eng, PAPER_QUERIES["4clique"])
    assert d.get("pipeline.sideways_extends", 0) == 0, d
    from repro.core.plan_ir import Extend
    assert all(s.sideways is None
               for b in eng.last_physical.bag_ops
               for s in b.steps if isinstance(s, Extend))


def test_sideways_parity_unfused_and_listing(monkeypatch):
    """Sideways filtering composes with both execution modes: the dense
    graph's 4-clique LISTING answers identically with fusion off, and
    the annotation survives into the per-step pipeline too."""
    src, dst = _complete_graph(12)
    q = "Q(x,y,z,w) :- R(x,y),S(x,z),T(x,w),U(y,z),X(y,w),Y(z,w)."
    oracle = make_engine(src, dst, "numpy").query(q)
    r_fus = make_engine(src, dst, "device").query(q)
    e_unf = make_engine(src, dst, "device", fused_bags=False)
    r_unf, d_unf = sync_delta(e_unf, q)
    assert_same_result(oracle, r_fus)
    assert_same_result(oracle, r_unf)
    assert d_unf.get("pipeline.sideways_extends", 0) >= 1, d_unf
