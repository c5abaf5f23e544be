"""Shared arithmetic of the readers of the engine's own spans and
counters (``repro.trace`` in the engine under test).

A span's totals reach ``RunRecord.counters`` as ``span.<name>.ns`` through
the window's delta of the backend's counters; its intervals reach a
traced run's host spans under its own name.  Counter-based readers give
a number per query or request completed in the window.  Each returns
None where the run holds nothing to read, as for an engine without the
span or counter.
"""
from __future__ import annotations

from ehbench import trace as trace_mod


def per_unit(run, key: str, scale: float = 1.0) -> float | None:
    """Counter ``key`` times ``scale``, per query or request completed
    in the window."""
    value = run.counters.get(key)
    if value is None or not run.completed:
        return None
    return value * scale / run.completed


def span_ms(run, name: str) -> float | None:
    """Wall time inside span ``name`` per completed unit, in ms."""
    return per_unit(run, f"span.{name}.ns", 1e-6)


def span_idle_share(run, name: str) -> float | None:
    """Share of the traced window's time under host spans called
    ``name`` (their intervals merged) in which no operation ran on the
    device."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    spans = trace_mod.merge(trace_mod.clip(
        [(s.start, s.end) for s in run.trace.spans if s.name == name],
        lo, hi))
    total = sum(e - s for s, e in spans)
    if not total:
        return None
    idle = sum(g1 - g0 for s, e in spans
               for g0, g1 in trace_mod.gaps(run.trace.ops, s, e))
    return idle / total
