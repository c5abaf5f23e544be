"""Named spans and counters at the engine's layer boundaries.

``span(name, counter=None, **meta)`` times one layer's work:

* it opens a ``jax.profiler.TraceAnnotation`` called ``name``, with
  ``meta`` as its arguments, so in a profile the span lands on its host
  thread's line, on the clock of the device operations, nested under
  the spans that enclose it;
* it keeps a per-thread stack of the open spans, so each knows its
  parent;
* on exit it adds ``span.<name>.ns`` (wall time), ``span.<name>.self_ns``
  (wall time less that of its child spans) and ``span.<name>.calls`` to
  a counter: the ``counter`` it was given, else its parent's.  The engine
  hands its spans the backend's ``stats``, so span totals ride the
  counter that already holds the dispatch counts
  (``ExecBackend.dispatch_summary`` leaves the ``span.`` keys out).

Spans are always on.  Without a profiler session one costs a microsecond
or two.  One process-wide ``jax.monitoring`` listener adds each XLA
compilation to ``span.<innermost open span>.compiles``.

``add(key, n)`` adds to the counter of the innermost open span, for code
that holds no counter of its own (the pair searches, the kernel
wrappers); ``upload(*arrays)`` counts ``upload.bytes`` that way.

This module imports nothing of the engine, so the kernels can use it.
"""
from __future__ import annotations

import threading
import time

import jax
import jax.monitoring
import numpy as np

# the event the benchmark's compile counter counts, so the totals agree
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()
_KEYS: dict[str, tuple[str, str, str, str]] = {}


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keys(name: str) -> tuple[str, str, str, str]:
    """Counter keys of span ``name``: ns, self_ns, calls, compiles."""
    keys = _KEYS.get(name)
    if keys is None:
        p = "span." + name
        keys = _KEYS[name] = (p + ".ns", p + ".self_ns", p + ".calls",
                              p + ".compiles")
    return keys


class span:
    """Context manager timing one named layer; see the module docstring."""

    __slots__ = ("name", "counter", "_meta", "_ann", "_t0", "_child_ns")

    def __init__(self, name: str, counter=None, **meta):
        self.name = name
        self.counter = counter
        self._meta = meta

    def __enter__(self) -> "span":
        stack = _stack()
        if self.counter is None and stack:
            self.counter = stack[-1].counter
        self._ann = jax.profiler.TraceAnnotation(self.name, **self._meta)
        self._ann.__enter__()
        self._child_ns = 0
        stack.append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter_ns() - self._t0
        stack = _stack()
        stack.pop()
        self._ann.__exit__(*exc)
        if stack:
            stack[-1]._child_ns += dt
        if self.counter is not None:
            ns, self_ns, calls, _ = _keys(self.name)
            self.counter[ns] += dt
            self.counter[self_ns] += dt - self._child_ns
            self.counter[calls] += 1
        return False


def add(key: str, n) -> None:
    """Add ``n`` to ``key`` in the counter of the innermost open span;
    nothing happens outside a span."""
    stack = getattr(_local, "stack", None)
    if n and stack and stack[-1].counter is not None:
        stack[-1].counter[key] += int(n)


def device_nbytes(*arrays) -> int:
    """Bytes the host arrays among ``arrays`` take on the device, where
    JAX narrows 64-bit types unless x64 is on.  Arrays already on the
    device count nothing."""
    return sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
               for a in arrays if isinstance(a, np.ndarray))


def upload(*arrays) -> None:
    """Count the host arrays among ``arrays`` as ``upload.bytes``: call
    where they are about to be handed to the device."""
    add("upload.bytes", device_nbytes(*arrays))


def _on_event(name: str, _secs: float, **_kw) -> None:
    if name != COMPILE_EVENT:
        return
    stack = getattr(_local, "stack", None)
    if stack and stack[-1].counter is not None:
        stack[-1].counter[_keys(stack[-1].name)[3]] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)
