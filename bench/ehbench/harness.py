"""One run of one cell: build the deployment, draw its traffic from the
seed, warm up, measure a window, check every answer against the plain
reference, and reduce the window to the cell's metrics.

The engine under test is reached only through its public entry points
(``Engine``, ``QueryServer``) and its counters.  Everything that
decides a metric or ``correct`` lives in this directory.
"""
from __future__ import annotations

import gc
import tempfile
from typing import Callable

import numpy as np

from ehbench import graph500, loops, registry
from ehbench import trace as trace_mod
from ehbench.common import NoChip, RunRecord, span

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations (persistent-cache reads included) through
    ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == BACKEND_COMPILE_EVENT:
            self.n += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found platform {info['platform']!r}")
    if require_tpu and info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{info['count']}")
    return info


def memory_peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent random stream ``stream`` of the run's seed."""
    return np.random.default_rng([int(seed), stream])


def build_graph(cfg: dict) -> graph500.Graph:
    """The configuration's dataset: one fixed graph, drawn from its own
    ``graph_seed``."""
    if cfg["generator"] != "graph500":
        raise ValueError(f"unknown generator {cfg['generator']!r}")
    return graph500.graph500(cfg["scale"], cfg["edge_factor"], cfg["a"],
                             cfg["b"], cfg["c"],
                             edge_seed=[cfg["graph_seed"], 1],
                             label_seed=[cfg["graph_seed"], 2])


def run_cell(cell: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True,
             fault: Callable | None = None) -> dict:
    """Run one cell; returns its ``record``, ``checks``, ``device``,
    ``attempted`` and ``failed``.

    ``fault``, for the benchmark's controls and tests only, is called
    with the engine object once it is built, to put something else in
    the timed path's place underneath the harness."""
    info = device_info(cell["chips"], require_tpu)
    compiles = CompileCounter()
    graph = build_graph(cfg)
    loop = loops.LOOPS[traffic["loop"]]
    ref = registry.reference(traffic["reference"])
    tracer = _Tracer() if trace else None
    out = loop(cfg=cfg, traffic=traffic, graph=graph, seed=seed,
               seconds=seconds, t_start=t_start, compiles=compiles,
               tracer=tracer, require_tpu=require_tpu, fault=fault,
               rng=rng, answer=ref.answer)
    record: RunRecord = out["record"]
    peak = memory_peak_bytes()
    out["release"]()
    gc.collect()
    checks = loops.CHECKS[traffic["loop"]](out, graph, ref)
    device = dict(info, memory_peak_bytes=peak)
    if tracer is not None:
        record.trace, record.trace_window = tracer.result()
        lo, hi = record.trace_window
        device["busy_s"] = trace_mod.busy_ns(record.trace.ops, lo, hi,
                                             record.trace.devices) / 1e9
        device["window_s"] = (hi - lo) / 1e9
    return {"record": record, "checks": checks, "device": device,
            "attempted": out["attempted"], "failed": out["failed"]}


class _Tracer:
    """Profiler window around part of a run; the trace goes to a
    temporary directory and is reduced and deleted at once."""

    def __init__(self):
        self._tmp = tempfile.TemporaryDirectory(prefix="ehbench-trace-")
        self._ctx = None

    def __enter__(self):
        import jax
        self._ctx = jax.profiler.trace(self._tmp.name)
        self._ctx.__enter__()
        self._window = span("bench.window")
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        self._window.__exit__(*exc)
        self._ctx.__exit__(*exc)
        return False

    def result(self):
        try:
            tr = trace_mod.load(self._tmp.name)
        finally:
            self._tmp.cleanup()
        win = tr.span("bench.window")
        if win is None:
            raise RuntimeError("the trace holds no bench.window span")
        return tr, win


def metrics(bench: dict, cell_name: str, record: RunRecord,
            trace: bool) -> dict:
    out = {}
    for m in registry.metrics_for(bench, cell_name, trace):
        value = registry.metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(record: RunRecord) -> dict | None:
    if record.trace is None:
        return None
    tr, (lo, hi) = record.trace, record.trace_window
    idle = trace_mod.gaps(tr.ops, lo, hi)
    by_span = trace_mod.attribute_gaps(idle, tr.spans)
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": trace_mod.top_ops(
                trace_mod.named_by_module(tr.ops, tr.modules), lo, hi),
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
