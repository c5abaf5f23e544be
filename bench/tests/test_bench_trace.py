"""The trace reduction: interval arithmetic on synthetic events, and the
whole reduction on a small trace recorded on CPU through
``jax.profiler``."""
import pytest

import benchpath  # noqa: F401
from ehbench import trace as T


def ev(name, start, end, device=0):
    return T.Event(name, start, end, device)


def test_merge_and_busy():
    ops = [ev("a", 0, 10), ev("b", 5, 15), ev("c", 20, 30), ev("d", 25, 26)]
    assert T.merge((e.start, e.end) for e in ops) == [(0, 15), (20, 30)]
    assert T.busy_ns(ops, 0, 40) == 25
    assert T.busy_ns(ops, 10, 25) == 10          # clipped to the window


def test_busy_is_averaged_over_devices():
    ops = [ev("a", 0, 10, 0), ev("a", 0, 30, 1)]
    assert T.busy_ns(ops, 0, 40, devices=2) == 20


def test_gaps():
    ops = [ev("a", 10, 20), ev("b", 30, 40)]
    assert T.gaps(ops, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def test_innermost_and_attribution():
    spans = [ev("bench.window", 0, 100), ev("bench.query", 10, 90),
             ev("$prep", 20, 40), ev("$sort", 25, 30), ev("$land", 60, 80)]
    segs = T.innermost(spans)
    assert (25, 30, "$sort") in segs and (30, 40, "$prep") in segs
    idle = [(0, 15), (22, 35), (70, 95)]
    got = T.attribute_gaps(idle, spans)
    assert got == {"bench.window": 10 + 5, "bench.query": 5 + 10,
                   "$prep": 3 + 5, "$sort": 5, "$land": 10}
    assert T.attribute_gaps([(200, 210)], spans) == {"host": 10}


def test_sum_matching_and_top_ops():
    mods = [ev("jit__bag_program(1)", 0, 10), ev("jit__bag_program_batch(2)",
            20, 25), ev("jit_gather(3)", 30, 60)]
    assert T.sum_matching(mods, r"^jit__bag_program\(", 0, 100) == (10, 1)
    assert T.sum_matching(mods, r"^jit__bag_program(_batch)?\(", 0,
                          100) == (15, 2)
    assert T.sum_matching(mods, r"gather", 0, 40) == (10, 1)
    top = T.top_ops(mods, 0, 100, n=2)
    assert top == [["jit_gather(3)", 30e-9], ["jit__bag_program(1)", 10e-9]]


def test_ops_named_by_module():
    mods = [ev("jit_f(12)", 0, 50), ev("jit_g(34)", 60, 90)]
    ops = [ev("%while.3 = (s32[]) while(...)", 5, 40), ev("%copy", 62, 70),
           ev("%orphan", 95, 99)]
    got = [e.name for e in T.named_by_module(ops, mods)]
    assert got == ["jit_f/while.3", "jit_g/copy", "orphan"]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    out = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(out)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.query"):
                    f(x).block_until_ready()
    return T.load(str(out))


def test_recorded_trace_reduces(cpu_trace):
    tr = cpu_trace
    win = tr.span("bench.window")
    assert win is not None and win[1] > win[0]
    assert sum(s.name == "bench.query" for s in tr.spans) == 3
    assert tr.ops and tr.devices == 1
    lo, hi = win
    busy = T.busy_ns(tr.ops, lo, hi, tr.devices)
    assert 0 < busy < hi - lo
    idle = T.gaps(tr.ops, lo, hi)
    by_span = T.attribute_gaps(idle, tr.spans)
    assert sum(by_span.values()) == pytest.approx(hi - lo - busy)
    assert T.top_ops(tr.ops, lo, hi)
