"""The engine's spans and counters (``repro.trace``).

* **span arithmetic** — totals, self time net of children, counter
  inheritance, no counting outside a span, device byte sizes;
* **compile attribution** — each XLA compilation lands on the innermost
  open span;
* **engine spans** — a device-backend triangle query records the layer
  spans, and the dispatch summary stays counts only;
* **engine counters** — pair-search lanes and steps, upload bytes,
  batch fill.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hub_csr, random_undirected_graph
from repro import trace
from repro.core import workload as W
from repro.core.engine import Engine
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
