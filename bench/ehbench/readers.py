"""Shared arithmetic of the metric readers in ``bench/metrics``.  Each
returns None where the run holds nothing to read."""
from __future__ import annotations


import numpy as np

from ehbench import trace as trace_mod

# XLA module (program launch) names on the device, from the jitted
# functions of the engine
BAG_PROGRAM = r"^jit__bag_program\("
BAG_PROGRAM_ANY = r"^jit__bag_program(_batch)?\("
PAIR_KERNELS = r"^jit_(bitset_and_popcount|uint_intersect)_kernel\("


def device_idle(run) -> float | None:
    """1 - (union of device operation time) / (traced window)."""
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    busy = trace_mod.busy_ns(run.trace.ops, lo, hi, run.trace.devices)
    return 1.0 - busy / (hi - lo)


def module_ms_per_unit(run, pattern: str) -> float | None:
    """Summed device time of the program launches whose names match,
    per query or request of the traced window, in milliseconds."""
    if run.trace is None or not run.traced_units:
        return None
    lo, hi = run.trace_window
    ns, n = trace_mod.sum_matching(run.trace.modules, pattern, lo, hi)
    if not n:
        return None
    return ns / 1e6 / run.traced_units


def percentile_ms(values, q: float) -> float | None:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values), q)) * 1e3


def counter(run, name: str) -> float | None:
    return run.counters.get(name)


def ratio(run, num: str, den: str) -> float | None:
    d = run.counters.get(den, 0)
    return run.counters.get(num, 0) / d if d else None


