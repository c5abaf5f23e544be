"""The cell ``pagerank.g500-s17``: its configuration and traffic files
drive a whole run of the harness on CPU at a small scale, and each of
its per-layer readers reads a synthetic run record, or None where the
record lacks its span, counter or trace."""
import time

import pytest

import benchpath  # noqa: F401
from ehbench import fixpoint, harness, registry
from ehbench import trace as T
from ehbench.common import RunRecord

BENCH = registry.load_benchmark()
CELL = "pagerank.g500-s17"
SCALE = 8
MS = 1_000_000
SPAN_METRICS = {"recursion_ms.pagerank": "eh.recursion",
                "recursion_prep_ms.pagerank": "eh.recursion.prepare",
                "aggregate_ms.pagerank": "eh.aggregate",
                "materialize_ms.pagerank": "eh.materialize"}


def cell_parts():
    cell = registry.workload(BENCH, CELL)
    return (cell, registry.config(BENCH, cell["config"]),
            registry.traffic(cell["traffic"]))


def test_traffic_runs_the_papers_pagerank():
    from repro.core.workload import pagerank_program
    cell, cfg, traffic = cell_parts()
    assert traffic["query"] == pagerank_program(5)
    assert traffic["loop"] == "repeat" and traffic["reference"] == "pagerank"
    assert cell["chips"] == 1 and cfg["scale"] == 17
    assert cfg["graph_seed"] == 17 and cfg["relation"] == {
        "name": "Edge", "aliases": []}


def test_cell_is_correct_at_a_small_scale():
    cell, cfg, traffic = cell_parts()
    res = harness.run_cell(cell, dict(cfg, scale=SCALE), traffic,
                           2**31 + 77, 0.5, False, time.perf_counter(),
                           require_tpu=False)
    checks = {c.name: c.value for c in res["checks"]}
    assert all(c.ok for c in res["checks"]), res["checks"]
    assert checks["entry_gap"] == 0 and checks["host_rounds"] == 0
    assert res["attempted"] >= 2 and res["failed"] == 0
    counters = res["record"].counters
    assert counters["recursion.device_fixpoints"] == res["record"].completed
    for span in SPAN_METRICS.values():
        assert counters[f"span.{span}.ns"] > 0, span


def ev(name, start_ms, end_ms):
    return T.Event(name, start_ms * MS, end_ms * MS)


def traced():
    ops = [ev("%fusion", 0, 30), ev("%while", 60, 80)]
    mods = [ev("jit__bag_program(1)", 0, 30),
            ev("jit__naive_device(2)", 60, 80)]
    return T.Trace(ops=ops, modules=mods,
                   spans=[ev("bench.window", 0, 100)], devices=1)


def record(trace=None, **counters):
    base = {"span.eh.recursion.ns": 900 * MS,
            "span.eh.recursion.prepare.ns": 300 * MS,
            "span.eh.aggregate.ns": 1_200 * MS,
            "span.eh.materialize.ns": 600 * MS,
            "upload.bytes": 60_000_000,
            "recursion.edge_rounds": 3_000_000_000,
            "recursion.key_rounds": 1_000_000_000}
    base.update(counters)
    return RunRecord(loop="repeat", setup_s=20.0, window_s=45.0,
                     completed=3, latencies_s=[15.0] * 3, waits_s=[],
                     counters={k: v for k, v in base.items()
                               if v is not None},
                     trace=trace,
                     trace_window=(0, 100 * MS) if trace else None,
                     traced_units=1 if trace else 0)


def read(name, run):
    return registry.metric_reader(name)(run)


@pytest.fixture
def v5e(monkeypatch):
    monkeypatch.setattr(fixpoint, "device_kind", lambda: "TPU v5 lite")


@pytest.mark.parametrize("name, span", sorted(SPAN_METRICS.items()))
def test_span_readers(name, span):
    want = record().counters[f"span.{span}.ns"] / MS / 3
    assert read(name, record()) == pytest.approx(want)
    assert read(name, record(**{f"span.{span}.ns": None})) is None


def test_upload_reader():
    assert read("upload_mb.pagerank", record()) == pytest.approx(20.0)
    assert read("upload_mb.pagerank", record(**{"upload.bytes": None})) \
        is None


def test_device_readers():
    run = record(traced())
    assert read("fixpoint_ms.pagerank", run) == pytest.approx(20.0)
    assert read("device_idle.pagerank", run) == pytest.approx(0.5)
    assert read("fixpoint_ms.pagerank", record()) is None
    assert read("device_idle.pagerank", record()) is None


def test_pull_spmv_bytes_by_hand():
    """Two rounds over a path 0-1-2 stored both ways: 4 edges and 3
    keys a round, each edge a 4-byte id and a 4-byte term, each key a
    row bound, an inverse degree and a rank."""
    assert fixpoint.pull_spmv_bytes(2 * 4, 2 * 3) == 8 * 8 + 12 * 6 == 136


def test_roofline_reader(v5e):
    """The window's 3e9 edge rounds and 1e9 key rounds move 36 GB over
    3 queries, 12 GB a query; in the traced query's 20 ms of fixpoint
    that is 6e11 bytes/s, 73.26% of 819e9."""
    got = read("fixpoint_roofline.pagerank", record(traced()))
    assert got == pytest.approx(73.26, abs=0.01)


@pytest.mark.parametrize("missing", ["recursion.edge_rounds",
                                     "recursion.key_rounds"])
def test_roofline_reader_needs_the_counters(missing, monkeypatch):
    def no_device():
        raise AssertionError("looked for the device with nothing to read")

    monkeypatch.setattr(fixpoint, "device_kind", no_device)
    assert read("fixpoint_roofline.pagerank",
                record(traced(), **{missing: None})) is None
    assert read("fixpoint_roofline.pagerank", record()) is None


def test_roofline_reader_needs_the_fixpoint_in_the_trace(v5e):
    tr = traced()
    tr.modules = [m for m in tr.modules if "naive" not in m.name]
    assert read("fixpoint_roofline.pagerank", record(tr)) is None


def test_cell_reports_every_metric_it_declares(v5e):
    got = harness.metrics(BENCH, CELL, record(traced()), trace=True)
    want = {m["name"] for m in registry.metrics_for(BENCH, CELL, True)}
    assert set(got) == want and len(want) == 8
    got = harness.metrics(BENCH, CELL, record(), trace=False)
    assert set(got) == {"query_s", "setup_s"}
