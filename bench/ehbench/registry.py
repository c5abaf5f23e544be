"""Find the benchmark's parts by the names ``BENCHMARK.json`` gives.

* a cell is an entry of ``workloads``;
* a configuration is the JSON file its ``configs`` entry names;
* a traffic mix is ``bench/traffic/<name>.json``;
* a metric (end-to-end or per-layer) is read by
  ``bench/metrics/<name>.py``, whose ``read(run)`` returns a number, or
  None where the run holds nothing for it to read;
* a plain reference is ``bench/references/<name>.py``.  Its
  ``reference(graph)`` and ``answer(result)`` give a count, compared
  exactly, or a keyed answer ``(keys, values)``; a module of keyed
  answers also defines ``compare(got, want)``, the entries that differ
  under its own ``RTOL`` and ``ATOL``.  Its ``CONTROLS`` names the
  variants of ``reference`` that break a guarantee, by their keywords.

A later cell adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = _named(bench["configs"], name, "configuration")
    with open(root / entry["file"]) as f:
        return json.load(f)


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    with open(bench_dir / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", (cell,))]


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "ehbench_part_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir / "metrics" / f"{name}.py").read


def reference(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _module(bench_dir / "references" / f"{name}.py")


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one chip of ``device_kind``.  A device that is
    not in the table is an error, not a default."""
    with open(bench_dir / "ehbench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]
