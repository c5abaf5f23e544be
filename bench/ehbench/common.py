"""Types and small helpers shared by the harness and its loops."""
from __future__ import annotations

import dataclasses
import sys
import time

from ehbench import trace as trace_mod


class NoChip(RuntimeError):
    """The run cannot measure: no accelerator, too few chips, or kernels
    that would run in interpret mode."""


@dataclasses.dataclass
class RunRecord:
    """What one run measured, as the metric readers see it."""

    loop: str
    setup_s: float
    window_s: float
    completed: int
    latencies_s: list[float]
    waits_s: list[float]
    counters: dict[str, int]
    trace: trace_mod.Trace | None = None
    trace_window: tuple[int, int] | None = None
    traced_units: int = 0


@dataclasses.dataclass
class Check:
    """One number compared with its limit; the run is correct when
    every check's value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def counters_delta(before: dict, after: dict) -> dict:
    return {k: int(v - before.get(k, 0)) for k, v in after.items()
            if v != before.get(k, 0)}


def now() -> float:
    return time.perf_counter()

