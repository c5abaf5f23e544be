"""The engine's spans and counters (``repro.trace``).

* **span arithmetic** — totals, self time net of children, counter
  inheritance, no counting outside a span, device byte sizes;
* **compile attribution** — each XLA compilation lands on the innermost
  open span;
* **engine spans** — a device-backend triangle query records the layer
  spans, and the dispatch summary stays counts only;
* **engine counters** — pair-search lanes and steps, upload bytes,
  batch fill;
* **recursion** — a device-backend PageRank records the recursion,
  aggregation and materialisation spans, counts the edges and keys its
  fixpoint's rounds read and the bytes of its operands, and agrees with
  the benchmark's plain reference;
* **grouping** — PageRank's key rows reach ``eh.group`` in order, a
  triangle count groups nothing, and count-distinct programs whose rows
  come out of order agree across backends.
"""
import collections
import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hub_csr, random_undirected_graph
from repro import trace
from repro.core import workload as W
from repro.core.engine import Engine
from repro.core import gj, recursion
from repro.core.layouts import HybridSetStore, decide_relation_level
from repro.serve import QueryServer

ANCHORED = "C(;w:long) :- R(0,y),S(y,z),T(0,z); w=<<COUNT(*)>>."


def make_engine(backend, n=40, p=0.3, seed=3):
    src, dst, _ = random_undirected_graph(n, p, seed)
    eng = Engine(backend=backend)
    eng.load_edges("Edge", src, dst)
    for al in W.ALIASES:
        eng.alias(al, "Edge")
    return eng


# ----------------------------------------------------------- span arithmetic
def test_span_totals_self_time_and_inheritance():
    c = collections.Counter()
    with trace.span("outer", c):
        with trace.span("inner"):
            pass
        with trace.span("inner"):
            pass
    assert c["span.outer.calls"] == 1 and c["span.inner.calls"] == 2
    assert c["span.inner.ns"] >= c["span.inner.self_ns"] >= 0
    assert c["span.outer.self_ns"] == (c["span.outer.ns"]
                                       - c["span.inner.ns"])


def test_a_span_given_its_own_counter_keeps_to_it():
    a, b = collections.Counter(), collections.Counter()
    with trace.span("root", a):
        with trace.span("mine", b):
            trace.add("hits", 3)
    assert b["span.mine.calls"] == 1 and b["hits"] == 3
    assert "span.mine.calls" not in a and "hits" not in a


def test_add_outside_any_span_counts_nothing():
    trace.add("hits", 5)
    trace.upload(np.zeros(4, np.int32))
    with trace.span("anonymous"):       # no counter anywhere
        trace.add("hits", 1)


def test_device_nbytes_follows_the_device_dtype():
    want64 = 8 if jax.config.jax_enable_x64 else 4
    assert trace.device_nbytes(np.zeros(10, np.int64)) == 10 * want64
    assert trace.device_nbytes(np.zeros(10, np.int32),
                               np.zeros(3, bool)) == 43
    assert trace.device_nbytes(jnp.zeros(10), [1, 2]) == 0


def test_span_closes_on_exception():
    c = collections.Counter()
    with pytest.raises(ValueError):
        with trace.span("boom", c):
            raise ValueError("x")
    assert c["span.boom.calls"] == 1
    with trace.span("after", c):
        pass
    assert c["span.after.self_ns"] == c["span.after.ns"]


def test_compile_lands_on_the_innermost_span():
    c = collections.Counter()
    fresh = jax.jit(lambda x: x * 3 + 1)
    with trace.span("outer", c):
        with trace.span("inner"):
            fresh(jnp.arange(7)).block_until_ready()
        fresh(jnp.arange(7)).block_until_ready()   # cached: no compile
    assert c["span.inner.compiles"] >= 1
    assert c["span.outer.compiles"] == 0


# -------------------------------------------------------------- engine spans
def test_device_triangle_query_records_its_layers():
    eng = make_engine("device")
    eng.query(W.TRIANGLE_COUNT)
    st = eng.backend.stats
    for name in ("eh.query", "eh.plan", "eh.bag.launch", "eh.land",
                 "eh.pairs", "eh.pairs.expand", "eh.finalize"):
        assert st[f"span.{name}.calls"] > 0, (name, dict(st))
        assert 0 <= st[f"span.{name}.self_ns"] <= st[f"span.{name}.ns"]
    assert st["span.eh.query.calls"] == 1
    assert st["span.eh.query.ns"] >= (st["span.eh.plan.ns"]
                                      + st["span.eh.pairs.ns"])
    summary = eng.dispatch_summary()
    assert not [k for k in summary if k.startswith("span.")], summary
    assert not [k for k in eng.backend.dispatch_summary()
                if k.startswith("span.")]


# ----------------------------------------------------------- engine counters
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_pair_searches_count_lanes_and_matches(backend):
    eng = make_engine(backend)
    eng.query(W.TRIANGLE_COUNT)
    st = eng.backend.stats
    assert st["pairs.searched"] >= st["pairs.found"] > 0, dict(st)
    assert st["pairs.search_steps"] >= st["pairs.searched"]
    assert (eng.backend.dispatch_summary()["pairs.search_steps"]
            == st["pairs.search_steps"])
    assert st["upload.bytes"] > 0


@pytest.mark.parametrize("cohort, steps", [("uint", 7), ("bitset", 4)])
def test_pair_search_steps_follow_the_longest_segment(cohort, steps):
    """Each lane runs bit_length(longest segment) steps: 64 CSR elements
    for the uint cohort, 8 blocks for the bitset's block ids."""
    csr = hub_csr()
    store = HybridSetStore.build(csr,
                                 decision=decide_relation_level(csr, cohort))
    offsets = csr.offsets if cohort == "uint" else store.bitset.offsets
    assert int(np.diff(offsets).max()).bit_length() == steps
    rng = np.random.default_rng(2)
    u = np.concatenate([[0], rng.integers(1, 200, 40)])
    v = np.concatenate([[5], rng.integers(1, 200, 40)])
    c = collections.Counter()
    with trace.span("pairs", c):
        store.intersect_count(u, v)
    assert c["pairs.searched"] > 0
    assert c["pairs.search_steps"] == c["pairs.searched"] * steps


def test_device_uploads_count_trie_bytes():
    """Trie levels upload once: a second query adds only per-query
    operands (cursors, pair-search operands), never the levels again."""
    from repro.core.executor import BagResultCache
    eng = make_engine("device")
    eng.query(W.TRIANGLE_COUNT)
    first = eng.backend.stats["upload.bytes"]
    levels = eng.backend.stats["upload.levels"]
    eng.bag_cache = BagResultCache()
    eng.query(W.TRIANGLE_COUNT)
    second = eng.backend.stats["upload.bytes"] - first
    assert eng.backend.stats["upload.levels"] == levels
    assert 0 < second < first


def test_drain_spans_and_batch_fill():
    srv = QueryServer(backend="device")
    src, dst, _ = random_undirected_graph(24, 0.3, seed=1)
    srv.load_graph("acme", "R", src, dst)
    for al in ("S", "T"):
        srv.alias("acme", al, "R")
    tickets = [srv.submit("acme", ANCHORED, v) for v in (0, 1, 2, 3)]
    assert [t.seq for t in tickets] == [0, 1, 2, 3]
    srv.drain()
    st = srv.backend.stats
    for name in ("eh.serve.drain", "eh.bind", "eh.bag.launch", "eh.land",
                 "eh.finalize"):
        assert st[f"span.{name}.calls"] >= 1, (name, dict(st))
    assert st["span.eh.serve.drain.calls"] == 1
    slots, rows = st["pipeline.batched_slots"], st["pipeline.batched_rows"]
    assert slots % 4 == 0 and 0 < rows <= slots, dict(st)
    assert not [k for k in srv.dispatch_summary() if k.startswith("span.")]


# ------------------------------------------------------------------ recursion
RECURSION_SPANS = ("eh.recursion", "eh.recursion.prepare", "eh.aggregate",
                   "eh.materialize")


def pagerank_reference():
    """The benchmark's plain PageRank reference (``bench/references``)."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "references" / "pagerank.py")
    spec = importlib.util.spec_from_file_location("pagerank_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def csr_graph(adj):
    """The reference's view of a graph: vertex count, CSR offsets and
    sorted neighbours."""
    src, dst = np.nonzero(adj)
    offsets = np.zeros(len(adj) + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=len(adj)), out=offsets[1:])
    return types.SimpleNamespace(n=len(adj), offsets=offsets,
                                 neighbors=dst.astype(np.int64),
                                 degrees=np.diff(offsets))


def pagerank_engine(monkeypatch, n=60, p=0.1, seed=5):
    """A device engine over a random graph whose fixpoints record the
    upload bytes counted while each ran."""
    src, dst, adj = random_undirected_graph(n, p, seed)
    eng = Engine(backend="device")
    eng.load_edges("Edge", src, dst)
    fixpoint_uploads = []
    real = recursion.naive_device_fixpoint

    def spy(*args, **kw):
        before = eng.backend.stats["upload.bytes"]
        out = real(*args, **kw)
        fixpoint_uploads.append(eng.backend.stats["upload.bytes"] - before)
        return out

    monkeypatch.setattr(recursion, "naive_device_fixpoint", spy)
    return eng, adj, fixpoint_uploads


def test_device_pagerank_records_its_recursion_layers(monkeypatch):
    eng, adj, _ = pagerank_engine(monkeypatch)
    eng.query(W.pagerank_program(5))
    st = eng.backend.stats
    for name in RECURSION_SPANS:
        assert st[f"span.{name}.calls"] > 0, (name, dict(st))
        assert 0 <= st[f"span.{name}.self_ns"] <= st[f"span.{name}.ns"]
    assert st["span.eh.recursion.calls"] == 1
    assert st["span.eh.recursion.ns"] >= st["span.eh.recursion.prepare.ns"]
    # N and InvDeg are count-distinct rules; every keyed head is a trie
    assert st["span.eh.aggregate.calls"] == 2
    assert st["recursion.host_rounds"] == 0
    assert not [k for k in eng.dispatch_summary() if k.startswith("span.")]


def test_device_pagerank_counts_edge_and_key_rounds(monkeypatch):
    eng, adj, _ = pagerank_engine(monkeypatch)
    eng.query(W.pagerank_program(5))
    st = eng.backend.stats
    edges, keys = int(adj.sum()), int(np.count_nonzero(adj.any(axis=1)))
    assert st["recursion.device_fixpoints"] == 1
    assert st["recursion.device_rounds"] == 5
    assert st["recursion.edge_rounds"] == st["recursion.device_rounds"] * edges
    assert st["recursion.key_rounds"] == st["recursion.device_rounds"] * keys


def test_device_pagerank_uploads_count_the_fixpoint_operands(monkeypatch):
    """The loop's operands reach ``upload.bytes``: a key position for
    each end of every edge, the inverse degree gathered per edge, and
    one starting rank per key, 4 bytes each on the device."""
    eng, adj, fixpoint_uploads = pagerank_engine(monkeypatch)
    eng.query(W.pagerank_program(5))
    edges, keys = int(adj.sum()), int(np.count_nonzero(adj.any(axis=1)))
    assert fixpoint_uploads == [4 * (3 * edges + keys)]
    assert eng.backend.stats["upload.bytes"] > fixpoint_uploads[0]


def test_device_pagerank_agrees_with_the_plain_reference(monkeypatch):
    ref = pagerank_reference()
    eng, adj, _ = pagerank_engine(monkeypatch, n=80, p=0.08, seed=9)
    got = ref.answer(eng.query(W.pagerank_program(5)))
    want = ref.reference(csr_graph(adj))
    assert len(want[0]) > 0
    assert ref.compare(got, want) == 0


def test_device_sssp_counts_its_seminaive_rounds():
    """The seminaive loop reads every edge and the whole vertex domain
    each round, and its operands are counted in ``upload.bytes``."""
    src, dst, adj = random_undirected_graph(40, 0.1, seed=4)
    eng = Engine(backend="device")
    eng.load_edges("Edge", src, dst)
    eng.query(W.sssp_program(int(src[0])))
    st = eng.backend.stats
    assert st["recursion.device_fixpoints"] == 1
    assert st["recursion.host_rounds"] == 0
    rounds = st["recursion.device_rounds"]
    assert rounds > 0
    assert st["recursion.edge_rounds"] == rounds * len(src)
    n = int(max(src.max(), dst.max())) + 1
    assert st["recursion.key_rounds"] == rounds * n
    for name in ("eh.recursion", "eh.recursion.prepare", "eh.materialize"):
        assert st[f"span.{name}.calls"] > 0, name


def test_triangle_query_records_no_recursion_span():
    eng = make_engine("device")
    eng.query(W.TRIANGLE_COUNT)
    st = eng.backend.stats
    assert not [k for k in st if k.startswith("span.eh.recursion")]
    assert "recursion.edge_rounds" not in st
    assert st["span.eh.materialize.calls"] == 1


# ------------------------------------------------------------------- grouping
def test_device_pagerank_groups_presorted_rows_inside_its_layers(monkeypatch):
    """Two groupings a query: ``N``'s body projected to ``x`` (under
    ``eh.finalize``) and ``InvDeg``'s count-distinct (under
    ``eh.aggregate``); the base ``PageRank`` body reuses ``N``'s bag.
    Both key columns come out in trie order, so neither sorts."""
    eng, _, _ = pagerank_engine(monkeypatch)
    parents = []
    real = gj._run_starts

    def spy(cols):
        parents.append([s.name for s in trace._stack()][-2:])
        return real(cols)

    monkeypatch.setattr(gj, "_run_starts", spy)
    eng.query(W.pagerank_program(5))
    st = eng.backend.stats
    assert sorted(parents) == [["eh.aggregate", "eh.group"],
                               ["eh.finalize", "eh.group"]]
    assert st["span.eh.group.calls"] == 2
    assert st["group.rows"] > 0
    assert st["group.presorted_rows"] == st["group.rows"]


def test_triangle_query_records_no_grouping():
    eng = make_engine("device")
    eng.query(W.TRIANGLE_COUNT)
    st = eng.backend.stats
    assert not [k for k in st if k.startswith(("span.eh.group", "group."))]


@pytest.mark.parametrize("program", [
    "D(z;w:int) :- Edge(x,z); w=<<COUNT(x)>>.",
    "P(x,y;w:int) :- Edge(x,z),Edge(z,y); w=<<COUNT(z)>>."])
def test_unordered_count_distinct_agrees_across_backends(program):
    """Key rows that reach the grouping out of trie order (one column:
    the 1-D unique; two: the lexsort) give the numpy backend's answer on
    the device."""
    results = {}
    for backend in ("numpy", "device"):
        eng = make_engine(backend)
        res = eng.query(program)
        st = eng.backend.stats
        assert st["span.eh.group.calls"] == 1
        assert st["group.presorted_rows"] < st["group.rows"]
        results[backend] = res
    want, got = results["numpy"], results["device"]
    assert got.vars == want.vars
    for v in want.vars:
        assert np.array_equal(got.columns[v], want.columns[v])
    assert np.array_equal(got.annotation, want.annotation)
    if want.vars == ("z",):
        src, dst, _ = random_undirected_graph(40, 0.3, 3)
        indeg = np.bincount(dst)
        assert np.array_equal(want.columns["z"], np.flatnonzero(indeg))
        assert np.array_equal(want.annotation, indeg[indeg > 0])
