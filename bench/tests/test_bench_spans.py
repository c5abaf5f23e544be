"""The readers of the engine's own spans and counters: their arithmetic
on a synthetic run record, None on a record that holds nothing for them
(as a run of an engine without the spans does), and the spans
themselves in a profile the harness takes."""
import benchpath  # noqa: F401
import pytest

from ehbench import harness, registry
from ehbench import trace as T
from ehbench.common import RunRecord

BENCH = registry.load_benchmark()
MS = 1_000_000
NEW = ["pairs_ms.query", "pairs_expand_ms.query", "pairs_idle.query",
       "pairs_yield.query", "land_ms.query", "upload_mb.query",
       "plan_ms.query", "land_ms.serve", "bind_ms.serve",
       "finalize_ms.serve", "serve_fill.serve"]


def ev(name, start_ms, end_ms):
    return T.Event(name, start_ms * MS, end_ms * MS)


def empty():
    return RunRecord(loop="repeat", setup_s=1.0, window_s=10.0,
                     completed=0, latencies_s=[], waits_s=[], counters={})


def synthetic():
    """Four queries; the traced one has two ``eh.pairs`` spans over
    [10, 30) and [50, 60) ms, with device operations over [0, 20) and
    [55, 70) ms."""
    counters = {"span.eh.pairs.ns": 8_000 * MS,
                "span.eh.pairs.expand.ns": 2_000 * MS,
                "span.eh.land.ns": 1_200 * MS, "span.eh.plan.ns": 4 * MS,
                "span.eh.bind.ns": 2 * MS, "span.eh.finalize.ns": 6 * MS,
                "upload.bytes": 800_000_000, "pairs.searched": 1000,
                "pairs.found": 250, "pipeline.batched_slots": 400,
                "pipeline.batched_rows": 30}
    ops = [ev("%while", 0, 20), ev("%fusion", 55, 70)]
    spans = [ev("bench.window", 0, 100), ev("eh.query", 0, 90),
             ev("eh.pairs", 10, 30), ev("eh.pairs", 50, 60),
             ev("eh.pairs.expand", 12, 18)]
    tr = T.Trace(ops=ops, modules=[], spans=spans, devices=1)
    return RunRecord(loop="repeat", setup_s=1.0, window_s=10.0, completed=4,
                     latencies_s=[2.5] * 4, waits_s=[], counters=counters,
                     trace=tr, trace_window=(0, 100 * MS), traced_units=1)


def read(name, run):
    return registry.metric_reader(name)(run)


def test_every_new_metric_is_declared_once():
    """The serving cell's new metrics are declared; those of the
    triangle cell wait for the benchmark's own test record to carry
    their spans and counters (``test_bench_metrics.py`` checks that
    every declared ``tri`` metric reads on it)."""
    declared = [m["name"] for m in BENCH["per_layer"]]
    for name in NEW:
        want = 1 if name.endswith(".serve") else 0
        assert declared.count(name) == want, name


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_an_empty_record(name):
    assert read(name, empty()) is None


@pytest.mark.parametrize("name,want", [
    ("pairs_ms.query", 2000.0),
    ("pairs_expand_ms.query", 500.0),
    # 30 ms under eh.pairs, device busy for 10 + 5 of them
    ("pairs_idle.query", 0.5),
    ("pairs_yield.query", 0.25),
    ("land_ms.query", 300.0),
    ("upload_mb.query", 200.0),
    ("plan_ms.query", 1.0),
    ("land_ms.serve", 300.0),
    ("bind_ms.serve", 0.5),
    ("finalize_ms.serve", 1.5),
    ("serve_fill.serve", 0.075),
])
def test_reader_arithmetic(name, want):
    assert read(name, synthetic()) == pytest.approx(want)


def test_idle_share_merges_overlapping_spans():
    run = synthetic()
    run.trace.spans.append(ev("eh.pairs", 25, 40))
    # eh.pairs now covers [10, 40) and [50, 60): 40 ms, busy 10 + 5
    assert read("pairs_idle.query", run) == pytest.approx(25 / 40)


def test_engine_spans_nest_under_the_harness_window():
    """A device-backend triangle query inside the harness's profiler
    window: the engine's ``eh.*`` spans reach the trace the harness
    loads, inside ``bench.window`` and under ``eh.query``."""
    import numpy as np

    from repro.core import workload as W
    from repro.core.engine import Engine
    from repro.core.executor import BagResultCache

    rng = np.random.default_rng(5)
    a = np.triu(rng.random((24, 24)) < 0.3, 1)
    src, dst = np.nonzero(a | a.T)
    eng = Engine(backend="device")
    eng.load_edges("Edge", src, dst)
    for al in W.ALIASES:
        eng.alias(al, "Edge")
    eng.query(W.TRIANGLE_COUNT)            # compile outside the profile
    eng.bag_cache = BagResultCache()       # so the bag launches again
    tracer = harness._Tracer()
    with tracer:
        eng.query(W.TRIANGLE_COUNT)
    tr, (lo, hi) = tracer.result()
    eh = [s for s in tr.spans if s.name.startswith("eh.")]
    names = {s.name for s in eh}
    assert {"eh.query", "eh.plan", "eh.bag.launch", "eh.land",
            "eh.pairs"} <= names, names
    assert all(lo <= s.start and s.end <= hi for s in eh)
    (q,) = [s for s in eh if s.name == "eh.query"]
    assert all(q.start <= s.start and s.end <= q.end for s in eh)
