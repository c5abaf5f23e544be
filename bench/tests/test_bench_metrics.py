"""Every metric reader of ``BENCHMARK.json`` on a synthetic run record:
its arithmetic, and None where the run holds nothing for it."""
import benchpath  # noqa: F401
import pytest

from ehbench import harness, registry
from ehbench import trace as T
from ehbench.common import RunRecord

BENCH = registry.load_benchmark()
MS = 1_000_000


def ev(name, start_ms, end_ms):
    return T.Event(name, start_ms * MS, end_ms * MS)


def traced():
    ops = [ev("%while", 0, 40), ev("%fusion", 30, 50), ev("%copy", 80, 90)]
    mods = [ev("jit__bag_program(1)", 0, 20),
            ev("jit__bag_program_batch(2)", 25, 50),
            ev("jit_bitset_and_popcount_kernel(3)", 80, 84),
            ev("jit_uint_intersect_kernel(4)", 85, 86)]
    spans = [ev("bench.window", 0, 100), ev("eh.pairs", 0, 20)]
    return T.Trace(ops=ops, modules=mods, spans=spans, devices=1)


def record(loop, trace=None):
    return RunRecord(
        loop=loop, setup_s=12.5, window_s=10.0, completed=4,
        latencies_s=[0.1, 0.2, 0.3, 0.4, 1.0], waits_s=[0.0, 0.1, 0.2],
        counters={"compiles": 2, "compiles.first_serve": 5,
                  "pipeline.batched_queries": 9,
                  "pipeline.batched_launches": 3,
                  "span.eh.pairs.ns": 8_000 * MS,
                  "span.eh.pairs.expand.ns": 2_000 * MS,
                  "span.eh.land.ns": 1_200 * MS, "span.eh.plan.ns": 4 * MS,
                  "upload.bytes": 800_000_000, "pairs.searched": 1000,
                  "pairs.found": 250},
        trace=trace, trace_window=(0, 100 * MS) if trace else None,
        traced_units=2 if trace else 0)


def read(name, run):
    return registry.metric_reader(name)(run)


def test_end_to_end_readers():
    rep, opn = record("repeat"), record("open")
    assert read("setup_s", rep) == 12.5
    assert read("query_s", rep) == 2.5
    assert read("query_s", opn) is None
    assert read("serve_p50_ms", opn) == pytest.approx(300.0)
    assert read("serve_p95_ms.serve", opn) == pytest.approx(880.0)
    assert read("serve_p50_ms", rep) is None


def test_device_readers():
    run = record("repeat", traced())
    assert read("device_idle.query", run) == pytest.approx(0.4)
    assert read("device_idle.serve", run) == pytest.approx(0.4)
    assert read("bag_program_ms.query", run) == pytest.approx(10.0)
    assert read("bag_program_ms.serve", run) == pytest.approx(22.5)
    assert read("pair_kernel_ms.query", run) == pytest.approx(2.5)


def test_counter_readers():
    run = record("open")
    assert read("serve_batch.serve", run) == 3.0
    assert read("serve_wait_ms.serve", run) == pytest.approx(100.0)
    assert read("compiles.query", run) == 2
    assert read("compiles.serve", run) == 7
    run.counters = {}
    assert read("serve_batch.serve", run) is None
    assert read("compiles.serve", run) is None


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]
                                  if m["source"] == "device_trace"])
def test_device_readers_need_a_trace(name):
    assert read(name, record("repeat")) is None


def test_harness_leaves_out_what_is_not_read():
    cell = "tri.g500-s14"
    got = harness.metrics(BENCH, cell, record("repeat"), trace=True)
    assert "compiles.query" in got
    assert "device_idle.query" not in got
    got = harness.metrics(BENCH, cell, record("repeat", traced()),
                          trace=True)
    want = {m["name"] for m in registry.metrics_for(BENCH, cell, True)}
    assert set(got) == want
    assert all(set(v) == {"value", "unit"} for v in got.values())


def test_breakdown_lists_ops_and_gaps():
    run = record("repeat", traced())
    b = harness.breakdown(run)
    # the while op outlasts every launch, so it keeps its bare name
    assert b["device_ops"][0] == ["while", 0.04]
    assert dict(b["idle_gaps"]) == {"bench.window": pytest.approx(0.04)}
    assert harness.breakdown(record("repeat")) is None
