"""Controls and planted faults: ways to break the timed path underneath
the harness, each of which the comparison has to report as not correct.

A hook is called with the engine object the loop built (an ``Engine``
for ``repeat``, a ``QueryServer`` for ``open``) before its warm-up.

* ``control``: the plain reference put in the program's place, with one
  guarantee the configuration states broken: the reference module's
  ``CONTROLS`` names each such variant by the keywords it passes to the
  module's ``reference`` (or ``per_vertex``).
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
    """Stands in for a ``QueryResult``: one count, or a keyed answer
    ``(keys, values)`` as a key column and its annotation."""

    def __init__(self, value):
        if isinstance(value, tuple):
            keys, self.annotation = value
            self.vars, self.columns = ("x",), {"x": keys}
        else:
            self.vars, self.columns = (), {}
            self.annotation = np.asarray(value)

    def scalar(self):
        return self.annotation


def control(graph, ref, name: str):
    opts = ref.CONTROLS[name]

    def hook(target):
        if hasattr(target, "submit"):
            def drain(srv=target):
                queue, srv._queue = srv._queue, []
                want = ref.per_vertex(graph, [p.ticket.params[0]
                                              for p in queue], **opts)
                for p in queue:
                    p.ticket.result = _Answer(want[p.ticket.params[0]])
                    p.ticket.done = True
                return [p.ticket for p in queue]
            target.drain = drain
        else:
            value = ref.reference(graph, **opts)

            def query(_text, eng=target):
                eng.backend.stats["pipeline.launches"] += 1
                return _Answer(value)
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
    """Patches ``QueryResult``'s constructor, where every path of the
    engine (joins, counts, recursive fixpoints) builds its answer; returns
    the real one."""
    from repro.core import engine

    real = engine.QueryResult.__init__

    def init(self, vars, columns, annotation):
        real(self, vars, columns, annotation)
        if annotation is not None:
            self.annotation = np.asarray(annotation) + 1
    engine.QueryResult.__init__ = init
    return real


FAULTS = {"stale": stale, "half_batch": half_batch, "altered": altered}
