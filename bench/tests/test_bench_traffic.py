"""Zipfian draws, arrival schedules, and the registry that finds the
benchmark's parts by name."""
import json

import numpy as np
import pytest

import benchpath  # noqa: F401
from ehbench import registry
from ehbench.traffic import arrival_times, zipf_ranks


def test_zipf_matches_its_distribution():
    n, theta, size = 1000, 0.99, 200_000
    ranks = zipf_ranks(n, theta, size, np.random.default_rng(1))
    assert ranks.min() >= 0 and ranks.max() < n
    w = 1.0 / np.arange(1, n + 1) ** theta
    p = w / w.sum()
    freq = np.bincount(ranks, minlength=n) / size
    for k in (0, 1, 9, 99):
        assert abs(freq[k] - p[k]) < 5 * np.sqrt(p[k] * (1 - p[k]) / size)


def test_zipf_is_seeded():
    a = zipf_ranks(50, 0.99, 100, np.random.default_rng([2**31 + 5, 3]))
    b = zipf_ranks(50, 0.99, 100, np.random.default_rng([2**31 + 5, 3]))
    assert np.array_equal(a, b)


def _rng(*key):
    return np.random.default_rng(list(key))


def test_poisson_arrivals():
    """Given their count, Poisson arrivals are uniform over the window:
    the spacings of many schedules average ``1 / rate``."""
    ts = [arrival_times(50.0, 4.0, _rng(1, k), _rng(2, k)) for k in range(200)]
    assert all(len(t) == 200 for t in ts)
    assert all(np.all(np.diff(t) >= 0) and t.min() >= 0 and t.max() < 4.0
               for t in ts)
    assert abs(np.mean([t.mean() for t in ts]) - 2.0) < 0.02


def test_arrivals_are_seeded():
    a = arrival_times(8.0, 51.0, _rng(2**31 + 9, 4), _rng(2**33 + 1, 4))
    b = arrival_times(8.0, 51.0, _rng(2**31 + 9, 4), _rng(2**33 + 1, 4))
    assert len(a) > 0 and np.array_equal(a, b)


def test_every_order_offers_the_same_requests():
    """Two run seeds get the same gaps between arrivals and the same
    bindings, in different orders."""
    from ehbench import harness, loops

    class Fixed(loops.Server):
        def __init__(self):
            self.population = np.arange(1000)
            self.traffic = {"zipf_theta": 0.99}
            self.fixed, self.rng = 17001, harness.rng

    srv = Fixed()
    (d1, b1), (d2, b2) = (srv.schedule(4.8, 51.0, _rng(s, 4))
                          for s in (2**33 + 3, 5))
    assert len(d1) == len(d2) == round(4.8 * 51)
    assert not np.array_equal(b1, b2) and not np.array_equal(d1, d2)
    assert np.array_equal(np.sort(b1), np.sort(b2))
    g1, g2 = (np.diff(np.concatenate([[0.0], d, [51.0]])) for d in (d1, d2))
    assert np.allclose(np.sort(g1), np.sort(g2))


BENCH = registry.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_registry_finds_every_part(cell):
    w = registry.workload(BENCH, cell)
    cfg = registry.config(BENCH, w["config"])
    assert cfg["name"] == w["config"]
    tr = registry.traffic(w["traffic"])
    assert tr["name"] == w["traffic"]
    assert hasattr(registry.reference(tr["reference"]), "answer")
    for trace in (False, True):
        metrics = registry.metrics_for(BENCH, cell, trace)
        assert metrics
        for m in metrics:
            assert callable(registry.metric_reader(m["name"]))
    names = {m["name"] for m in registry.metrics_for(BENCH, cell, False)}
    assert "setup_s" in names and len(names) >= 2


def test_registry_filters_by_workloads():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                            {"name": "b"}],
             "per_layer": [{"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in registry.metrics_for(bench, "x", False)] \
        == ["a", "b"]
    assert [m["name"] for m in registry.metrics_for(bench, "y", False)] \
        == ["b"]
    assert registry.metrics_for(bench, "x", True) == []


def test_registry_reads_a_new_part(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic" / "t-1.json").write_text(json.dumps({"k": 1}))
    (tmp_path / "metrics" / "m.x.py").write_text(
        "def read(run):\n    return run * 2\n")
    assert registry.traffic("t-1", bench_dir=tmp_path) == {"k": 1}
    assert registry.metric_reader("m.x", bench_dir=tmp_path)(4) == 8
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("absent", bench_dir=tmp_path)


def test_peak_table_is_keyed_by_device_kind():
    v5e = registry.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("cpu")
