"""Controls and planted faults: ways to break the timed path underneath
the harness, each of which the comparison has to report as not correct.

A hook is called with the engine object the loop built (an ``Engine``
for ``repeat``, a ``QueryServer`` for ``open``) before its warm-up.

* ``control``: the plain reference put in the program's place, with one
  guarantee the configuration states broken.  ``ordered=False`` counts
  each match once instead of every ordered match (what a
  symmetry-breaking count gives); ``acc_dtype`` counts in a narrower
  integer type than the engine's 32 bits.
* ``stale``: a step that returns its state unchanged.  ``repeat``
  answers each query with the previous answer without running it;
  ``open`` returns from ``drain`` without running the queue.
* ``half_batch``: each drain runs only the first half of its requests
  and hands the other half the first half's answers.
* ``altered``: every answer the engine produces is off by one.
"""
from __future__ import annotations

import numpy as np


class _Answer:
    """Stands in for a ``QueryResult`` holding one count."""

    def __init__(self, count):
        self.count = count

    def scalar(self):
        return np.asarray(self.count)


def control(graph, ref, *, ordered: bool = True, acc_dtype=np.int64):
    def hook(target):
        if hasattr(target, "submit"):
            def drain(srv=target):
                queue, srv._queue = srv._queue, []
                want = ref.per_vertex(graph, [p.ticket.params[0]
                                              for p in queue],
                                      acc_dtype=acc_dtype, ordered=ordered)
                for p in queue:
                    p.ticket.result = _Answer(want[p.ticket.params[0]])
                    p.ticket.done = True
                return [p.ticket for p in queue]
            target.drain = drain
        else:
            count = ref.reference(graph, acc_dtype=acc_dtype,
                                  ordered=ordered)

            def query(_text, eng=target):
                eng.backend.stats["pipeline.launches"] += 1
                return _Answer(count)
            target.query = query
    return hook


def stale(target):
    if hasattr(target, "submit"):
        target.drain = lambda: []
        return
    real = target.query
    last = []

    def query(text):
        if not last:
            last.append(real(text))
        return last[-1]
    target.query = query


def half_batch(target):
    real = target.drain

    def drain(srv=target):
        with srv._lock:
            queue = list(srv._queue)
            srv._queue = srv._queue[:(len(queue) + 1) // 2]
        real()
        done = queue[:(len(queue) + 1) // 2]
        for i, p in enumerate(queue[len(done):]):
            p.ticket.result = done[i % len(done)].ticket.result
            p.ticket.done = True
        return [p.ticket for p in queue]
    target.drain = drain


def altered(_target):
    from repro.core import engine

    real = engine.QueryResult.from_gj

    def from_gj(res):
        out = real(res)
        out.annotation = np.asarray(out.annotation) + 1
        return out
    engine.QueryResult.from_gj = staticmethod(from_gj)
    return real


FAULTS = {"stale": stale, "half_batch": half_batch, "altered": altered}
