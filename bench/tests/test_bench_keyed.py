"""A whole-graph program whose answer is a keyed vector (PageRank),
driven through a whole run of the harness on CPU at a small size with
the look for a chip skipped: a sound run is correct with every query of
the window compared, and each control, each planted fault and a fixpoint
that falls back to host rounds come out not correct."""
import time

import numpy as np
import pytest

import benchpath  # noqa: F401
from ehbench import controls, harness, registry

BENCH = registry.load_benchmark()
SCALE = 8
SECONDS = 0.5
CELL = {"name": "pagerank.test", "config": "g500-s14",
        "traffic": "pagerank-repeat", "chips": 1}


def setup():
    from repro.core.workload import pagerank_program

    cfg = dict(registry.config(BENCH, CELL["config"]), scale=SCALE)
    traffic = {"name": CELL["traffic"], "loop": "repeat",
               "query": pagerank_program(5), "reference": "pagerank"}
    return cfg, traffic


def run(seed, fault=None):
    cfg, traffic = setup()
    return harness.run_cell(CELL, cfg, traffic, seed, SECONDS, False,
                            time.perf_counter(), require_tpu=False,
                            fault=fault)


def checks(res):
    return {c.name: c.value for c in res["checks"]}


def correct(res):
    return all(c.ok for c in res["checks"])


def test_sound_run_is_correct():
    res = run(2**31 + 11)
    assert correct(res), res["checks"]
    got = checks(res)
    assert got["entry_gap"] == 0 and got["host_rounds"] == 0
    assert "count_gap" not in got
    assert res["attempted"] >= 2 and res["failed"] == 0


def test_every_query_of_the_window_is_compared():
    """Only the answers after the warm-up and the first window query are
    off: the run is not correct, and each of them counts as failed."""
    def later_queries_off(eng):
        real, calls = eng.query, []

        def query(text):
            out = real(text)
            calls.append(1)
            if len(calls) > 2:
                out.annotation = np.asarray(out.annotation) * (1 + 1e-3)
            return out
        eng.query = query

    res = run(19, fault=later_queries_off)
    assert not correct(res), res["checks"]
    assert checks(res)["wrong_queries"] == res["attempted"] - 1
    assert res["failed"] == res["attempted"] - 1 >= 1


@pytest.mark.parametrize("kind", ["bf16", "iters4"])
def test_control_is_not_correct(kind):
    cfg, traffic = setup()
    graph = harness.build_graph(cfg)
    ref = registry.reference(traffic["reference"])
    res = run(23, fault=controls.control(graph, ref, kind))
    assert not correct(res), res["checks"]
    assert checks(res)["entry_gap"] > 0


@pytest.mark.parametrize("fault", ["stale", "altered"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from repro.core import engine
    # the altered fault patches the engine's result constructor; restore
    monkeypatch.setattr(engine.QueryResult, "__init__",
                        engine.QueryResult.__init__)
    res = run(29, fault=controls.FAULTS[fault])
    assert not correct(res), res["checks"]
    assert res["failed"] > 0


def test_host_fallback_is_not_correct():
    """With the device fixpoint switched off the engine runs PageRank's
    rounds on the host: the answers still agree, and ``host_rounds``
    alone makes the run not correct."""
    def host_rounds(eng):
        eng.device_recursion = False

    res = run(31, fault=host_rounds)
    got = checks(res)
    assert got["entry_gap"] == 0 and got["host_rounds"] > 0
    assert not correct(res)
