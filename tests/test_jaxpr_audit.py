"""Trace-level program auditor (repro.analysis.jaxpr_audit) tests.

Locks the tentpole invariants: injected host callbacks, launch-budget
drift, mis-sized frontier buffers, 64-bit dtype leaks and oversized
broadcasts are each rejected with their typed code, and the seven paper
queries (plus the batched serving probe) audit clean against the
committed ``jaxpr_baseline.json`` on every CI leg.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_audit as JA
from repro.analysis.jaxpr_audit import (JaxprAuditError, ProgramSpec,
                                        assert_clean, audit_closed_jaxpr)


def codes(violations):
    return [v.code for v in violations]


def _loop_over_buffer(cap_traced: int):
    """One chunked fill-style while loop carrying a (cap_traced,) buffer
    — the shape the audit must reconcile with the declared capacity."""

    def fn(buf):
        def cond(s):
            return s[0] < 2

        def body(s):
            c, b = s
            return c + 1, b.at[c].set(c)

        return jax.lax.while_loop(cond, body, (jnp.int32(0), buf))

    return jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((cap_traced,), np.int32))


# ------------------------------------------------------------- rejections
def test_injected_pure_callback_rejected():
    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct((4,), np.int32), x)

    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), np.int32))
    vs = audit_closed_jaxpr(closed, ProgramSpec("inj"))
    assert "host-callback" in codes(vs)
    with pytest.raises(JaxprAuditError, match="host-callback"):
        assert_clean(closed, ProgramSpec("inj"))


def test_extra_while_loop_breaks_launch_budget():
    closed = _loop_over_buffer(8)
    # the program declares ZERO fill loops -> the traced while is a
    # launch the budget never accounted for
    vs = audit_closed_jaxpr(closed, ProgramSpec("extra"))
    assert codes(vs) == ["launch-budget"]


def test_missing_while_loop_breaks_launch_budget():
    closed = jax.make_jaxpr(lambda x: x + 1)(
        jax.ShapeDtypeStruct((8,), np.int32))
    vs = audit_closed_jaxpr(
        closed, ProgramSpec("missing", loops=(("extend", "y", 8, 8),)))
    assert codes(vs) == ["launch-budget"]


def test_oversized_frontier_buffer_rejected():
    # the loop carries a 16-wide buffer but the plan lowered cap 8
    closed = _loop_over_buffer(16)
    vs = audit_closed_jaxpr(
        closed, ProgramSpec("wide", loops=(("extend", "y", 8, 8),)))
    assert "frontier-cap" in codes(vs)
    # matching capacity: clean
    ok = _loop_over_buffer(8)
    assert audit_closed_jaxpr(
        ok, ProgramSpec("ok", loops=(("extend", "y", 8, 8),))) == []


def test_non_pow2_declared_capacity_rejected():
    closed = _loop_over_buffer(12)
    vs = audit_closed_jaxpr(
        closed, ProgramSpec("bucket", loops=(("extend", "y", 12, 4),)))
    assert "frontier-bucket" in codes(vs)


def test_f64_leak_rejected_under_x64_trace():
    """A float64 compiled in under enable_x64 must be flagged when the
    program's own inputs never declared a 64-bit width."""
    with jax.enable_x64():
        closed = jax.make_jaxpr(
            lambda x: (x.astype(jnp.float64) * 2.0).sum())(
            jax.ShapeDtypeStruct((4,), np.float32))
    vs = audit_closed_jaxpr(closed,
                            ProgramSpec("leak", allow_64=False))
    assert "dtype-widening" in codes(vs)
    # declared 64-bit INPUTS are not leaks (catalog long annotations)
    with jax.enable_x64():
        ok = jax.make_jaxpr(lambda x: x * 2)(
            jax.ShapeDtypeStruct((4,), np.int64))
    assert audit_closed_jaxpr(ok, ProgramSpec("ok", allow_64=False)) == []


def test_oversized_broadcast_rejected():
    from repro.core import statistics as S

    big = 2 * S.PIPELINE_MAX_BUFFER
    closed = jax.make_jaxpr(
        lambda x: jnp.zeros((big,), np.int32) + x)(
        jax.ShapeDtypeStruct((), np.int32))
    vs = audit_closed_jaxpr(closed, ProgramSpec("bcast"))
    assert "broadcast-materialize" in codes(vs)
    # under the ceiling: clean
    small = jax.make_jaxpr(
        lambda x: jnp.zeros((64,), np.int32) + x)(
        jax.ShapeDtypeStruct((), np.int32))
    assert audit_closed_jaxpr(small, ProgramSpec("s")) == []


# ------------------------------------------------- the real paper programs
@pytest.fixture(scope="module")
def paper_audit():
    return JA.audit_paper_queries(smoke=True)


def test_paper_queries_audit_clean(paper_audit):
    reports, violations = paper_audit
    assert violations == [], [str(v) for v in violations]
    # every program is callback-free — the zero-host-sync claim at the
    # trace level, not just the counter level
    assert all(r.host_callbacks == 0 for r in reports)
    # the inventory covers all seven paper queries + the serving batch
    names = {r.name.split("::")[0] for r in reports}
    assert {"triangle", "triangle_list", "4clique", "lollipop", "barbell",
            "pagerank", "sssp", "serve_batch"} <= names


def test_paper_queries_match_committed_baseline(paper_audit):
    reports, _ = paper_audit
    new, removed = JA.compare(reports, JA.load_baseline())
    assert new == [], f"programs/launches not in baseline: {new}"
    assert removed == [], (f"baselined programs disappeared — shrink "
                           f"jaxpr_baseline.json: {removed}")


def test_fixpoint_programs_have_expected_loops(paper_audit):
    reports, _ = paper_audit
    by_name = {r.name: r for r in reports}
    # seminaive SSSP carries exactly one device while-loop; the naive
    # fixed-iteration PageRank path unrolls through scan (zero whiles)
    assert by_name["sssp::seminaive2"].fill_loops == 1
    assert by_name["pagerank::naive2"].fill_loops == 0


def test_counters_surface_in_dispatch_summary():
    records, eng = JA.collect_paper_programs(smoke=True)
    JA.audit_records(records[:2], counters=eng.backend.stats)
    summary = eng.dispatch_summary()
    assert summary.get("analysis.jaxpr_programs", 0) >= 2
    assert summary.get("analysis.jaxpr_violations", 0) == 0


def test_batched_program_spec_carries_batch_dim():
    """The vmapped serving program audits with base_ndim=1: [B, cap]
    buffers are the declared capacity, not a violation."""
    records, _eng = JA.collect_paper_programs(smoke=True)
    batched = [r for r in records if r[0] == "bag_batch"]
    assert batched, "serving probe recorded no batched program"
    closed, spec = JA.trace_record(batched[0])
    assert spec.batch > 1
    assert audit_closed_jaxpr(closed, spec) == []
