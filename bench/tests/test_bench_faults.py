"""Each cell's comparison, driven through a whole run on CPU at a small
size with the look for a chip skipped: sound runs come out correct, and
the control and every planted fault the cell can have come out not
correct."""
import time

import pytest

import benchpath  # noqa: F401
from ehbench import controls, harness, registry

BENCH = registry.load_benchmark()
SCALE = {"tri.g500-s14": 8, "serve-zipf.g500-s17": 9}
SECONDS = 1.0


def setup(cell_name):
    cell = registry.workload(BENCH, cell_name)
    cfg = registry.config(BENCH, cell["config"])
    cfg["scale"] = SCALE[cell_name]
    traffic = registry.traffic(cell["traffic"])
    if traffic["loop"] == "open":
        # enough arrivals per drain that batches hold several requests
        traffic.update(rate=40.0, warm_batch=2, trace_seconds=0.5)
    return cell, cfg, traffic


def run(cell_name, seed, fault=None, trace=False):
    cell, cfg, traffic = setup(cell_name)
    return harness.run_cell(cell, cfg, traffic, seed, SECONDS, trace,
                            time.perf_counter(), require_tpu=False,
                            fault=fault)


def correct(res):
    return all(c.ok for c in res["checks"])


@pytest.mark.parametrize("cell", list(SCALE))
def test_sound_run_is_correct(cell):
    res = run(cell, 2**31 + 7)
    assert correct(res), res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


# at the tests' size no anchored count reaches 2**15, so the 16-bit
# control can only fail the whole-graph count
CONTROLS = [("tri.g500-s14", "unordered"), ("tri.g500-s14", "int16"),
            ("serve-zipf.g500-s17", "unordered")]


@pytest.mark.parametrize("cell,kind", CONTROLS)
def test_control_is_not_correct(cell, kind):
    _, cfg, traffic = setup(cell)
    graph = harness.build_graph(cfg)
    ref = registry.reference(traffic["reference"])
    res = run(cell, 11, fault=controls.control(graph, ref, kind))
    assert not correct(res), res["checks"]


FAULTS = [("tri.g500-s14", "stale"), ("tri.g500-s14", "altered"),
          ("serve-zipf.g500-s17", "stale"),
          ("serve-zipf.g500-s17", "half_batch"),
          ("serve-zipf.g500-s17", "altered")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.core import engine
    # the altered fault patches the engine's result constructor; restore
    monkeypatch.setattr(engine.QueryResult, "__init__",
                        engine.QueryResult.__init__)
    res = run(cell, 13, fault=controls.FAULTS[fault])
    assert not correct(res), res["checks"]
    assert res["failed"] > 0 or any(
        c.name == "unlaunched_queries" and not c.ok for c in res["checks"])
