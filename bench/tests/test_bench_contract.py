"""``BENCHMARK.json`` is well formed, and ``bench/run.py`` refuses to
measure off a TPU or without the engine under test."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import benchpath  # noqa: F401
from ehbench import registry

ROOT = registry.ROOT
BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert {"setup_s"} <= {m["name"] for m in BENCH["end_to_end"]}


def test_bounds_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell])


def test_every_cell_reports_enough():
    for w in BENCH["workloads"]:
        e2e = registry.metrics_for(BENCH, w["name"], False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert registry.metrics_for(BENCH, w["name"], True)
        assert w["chips"] in (1, 4)


def test_paths_hold_the_parts():
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert os.path.isfile(ROOT / c["file"])
    for w in BENCH["workloads"]:
        assert os.path.isfile(registry.BENCH_DIR / "traffic"
                              / f"{w['traffic']}.json")


def test_run_seconds_fits_a_full_check():
    s = BENCH["run_seconds"]
    runs = 2 + 14 * 24
    assert 1 <= s <= 51
    assert runs * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tri.g500-s14",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_without_result_off_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_without_the_engine(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_config_files_are_json_objects(name):
    cfg = registry.config(BENCH, registry.workload(BENCH, name)["config"])
    assert isinstance(cfg, dict)
    entry = [c for c in BENCH["configs"] if c["name"] == cfg["name"]][0]
    for key in entry["reduced"]:
        assert key in cfg
    json.dumps(cfg)


@pytest.mark.parametrize("trace", [0, 1])
def test_run_prints_its_result_line_last(trace, monkeypatch, capsys):
    """``run.main`` through a whole run at a small scale, with the look
    for a chip skipped: the last line of standard output is the result,
    and the last lines of standard error are the checks."""
    import importlib.util

    from ehbench import harness
    config, run_cell = registry.config, harness.run_cell
    monkeypatch.setattr(registry, "config",
                        lambda *a, **k: dict(config(*a, **k), scale=8))
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: run_cell(
        *a, **dict(k, require_tpu=False)))
    spec = importlib.util.spec_from_file_location(
        "bench_run", registry.BENCH_DIR / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cell = BENCH["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(2**33 + 5),
                   "--seconds", "1", "--trace", str(trace)])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"attempted", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    want = {m["name"] for m in registry.metrics_for(BENCH, cell, bool(trace))}
    assert set(line["metrics"]) <= want and line["metrics"]
    assert ("breakdown" in line) == bool(trace)
    last = err.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") for s in last)
