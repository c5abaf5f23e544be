"""Serving-layer benchmark + CI latency gate.

``PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]``

Measures the query-serving path (``repro.serve.QueryServer``) on both
backends:

  * **point-query latency** — N prepared re-binds of the anchored
    triangle query, reported as p50/p99 seconds and QPS.  Every request
    after the first must hit the cached physical plan and traced bag
    program; the gate checks the no-recompile counters EXACTLY
    (``compile.plan_searches == 0``, ``trace_count`` delta 0 during the
    serving phase).
  * **batched vs sequential throughput** — the same B bindings through
    ``PreparedQuery.run_batch`` (one fused vmapped launch per same-shape
    chunk on the device backend) vs the per-binding loop, with EXACT
    result parity and EXACT batch-launch counters
    (``pipeline.batched_launches`` / ``pipeline.batched_queries``).

Writes ``SERVE_results.json`` (next to ``BENCH_results.json``).  The CI
gate mirrors ``benchmarks/run.py``: walls compare against the committed
``benchmarks/serve_baseline.json`` within ``--tolerance`` (default 3x)
plus a fixed absolute slack — shared-runner throughput swings wildly, so
the wall check only catches gross regressions, while the counter and
parity comparisons are exact and machine-independent.

``--write-baseline PATH`` refreshes the baseline from this run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmarks.run import BASELINE_ABS_SLACK_S

# dispatch counters gated EXACTLY per backend: the serving invariants
# (zero recompiles, fused batch launches) stated as machine-independent
# integers rather than timing
GATED_COUNTERS = (
    "compile.plan_searches",
    "compile.logical_compiles",
    "compile.physical_builds",
    "pipeline.batched_launches",
    "pipeline.batched_queries",
)


def _digest(res) -> float:
    if not res.vars:
        return float(np.asarray(res.scalar()))
    ann = res.annotation
    if ann is None:
        return float(res.num_rows)
    return float(np.asarray(ann, dtype=np.float64).sum())


def run_suite(smoke: bool) -> list:
    from repro.data import powerlaw_graph
    from repro.serve import QueryServer

    n, deg, n_point, batch = (150, 6, 32, 16) if smoke \
        else (2000, 12, 128, 64)
    g = powerlaw_graph(n, deg, 2.0, seed=0)
    src = np.repeat(np.arange(g.n), g.degrees)
    query = "C(;w:long) :- R(0,y),S(y,z),T(0,z); w=<<COUNT(*)>>."
    vertices = [int(v) for v in
                np.argsort(g.degrees)[::-1][:max(n_point, batch)]]

    out = []
    for backend in ("numpy", "device"):
        srv = QueryServer(backend=backend)
        srv.load_graph("bench", "R", src, g.neighbors)
        for al in ("S", "T"):
            srv.alias("bench", al, "R")
        from repro.core.executor import BagResultCache

        pq = srv.prepare("bench", query)
        bindings = [vertices[i % len(vertices)] for i in range(n_point)]
        pq.run(vertices[0])   # warm: plan search + codegen + trace
        pq.run_batch(bindings)  # warm the batched trace at serving shape

        eng = srv.engine("bench")
        stats = srv.backend.stats
        before = dict(stats)
        traces_before = srv.backend.trace_count()

        # ---- point-query latency: prepared re-binds, one at a time
        # (fresh bag cache: measure the join work, not warmup reuse)
        eng.bag_cache = BagResultCache()
        lat = []
        seq_results = []
        t_seq0 = time.perf_counter()
        for v in bindings:
            t0 = time.perf_counter()
            seq_results.append(pq.run(v))
            lat.append(time.perf_counter() - t0)
        seq_wall = time.perf_counter() - t_seq0
        lat = np.sort(np.asarray(lat))

        # ---- batched throughput: same bindings, one run_batch call
        # (fresh bag cache again so the host fallback loop cannot ride
        # on the sequential phase's cached per-binding results)
        eng.bag_cache = BagResultCache()
        t0 = time.perf_counter()
        batched = pq.run_batch(bindings)
        batched_wall = time.perf_counter() - t0

        parity = all(
            _digest(a) == _digest(b)    # EXACT, not approximate
            for a, b in zip(batched, seq_results))
        delta = {k: int(stats.get(k, 0) - before.get(k, 0))
                 for k in GATED_COUNTERS}
        retraces = srv.backend.trace_count() - traces_before

        # ---- static-vs-measured HBM: the memory_budget model against
        # the live device caches, per tenant (zeros on the numpy leg —
        # nothing is device-resident there).  Drift past the model
        # tolerance is a gate failure, same as parity.
        from repro.analysis.memory_budget import (MemoryBudgetError,
                                                  check_store)
        try:
            hbm = check_store(srv)
            hbm_ok = True
        except MemoryBudgetError as e:
            hbm = {"error": str(e)}
            hbm_ok = False

        out.append({
            "backend": backend,
            "n_queries": n_point,
            "p50_s": float(lat[len(lat) // 2]),
            "p99_s": float(lat[min(len(lat) - 1,
                                   int(len(lat) * 0.99))]),
            "seq_wall_s": seq_wall,
            "seq_qps": n_point / max(seq_wall, 1e-9),
            "batched_wall_s": batched_wall,
            "batched_qps": n_point / max(batched_wall, 1e-9),
            "batched_speedup": seq_wall / max(batched_wall, 1e-9),
            "parity": bool(parity),
            "retraces": int(retraces),
            "hbm": hbm,
            "hbm_ok": bool(hbm_ok),
            "dispatch": delta,
            "counters": {k: int(v)
                         for k, v in sorted(srv.counters.items())},
        })
    return out


# ------------------------------------------------- baseline gate
def _gate_summary(suite: list) -> dict:
    return {r["backend"]: {
        "p50_s": r["p50_s"],
        "batched_wall_s": r["batched_wall_s"],
        "parity": r["parity"],
        "retraces": r["retraces"],
        "dispatch": r["dispatch"],
    } for r in suite}


def write_baseline(suite: list, path: str, smoke: bool) -> None:
    payload = {
        "meta": {"smoke": bool(smoke), "unix_time": time.time(),
                 "note": "refresh with: python -m benchmarks.serve_bench "
                         "--smoke --write-baseline "
                         "benchmarks/serve_baseline.json"},
        "backends": _gate_summary(suite),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote serve baseline {path}")


def check_baseline(suite: list, path: str, tolerance: float,
                   smoke: bool) -> list:
    with open(path) as f:
        base = json.load(f)
    cur = _gate_summary(suite)
    failures = []
    base_smoke = base.get("meta", {}).get("smoke")
    if base_smoke is not None and bool(base_smoke) != bool(smoke):
        return [f"serve baseline {path} recorded with smoke={base_smoke} "
                f"but this run has smoke={smoke}"]
    for key, b in sorted(base["backends"].items()):
        c = cur.get(key)
        if c is None:
            failures.append(f"{key}: in baseline but not in this run")
            continue
        if not c["parity"]:
            failures.append(f"{key}: batched vs sequential parity FAILED")
        if c["retraces"] != b["retraces"]:
            failures.append(f"{key}: serving-phase retraces "
                            f"{c['retraces']} != baseline {b['retraces']}")
        for wall_key in ("p50_s", "batched_wall_s"):
            limit = b[wall_key] * tolerance + BASELINE_ABS_SLACK_S
            if c[wall_key] > limit:
                failures.append(
                    f"{key}: {wall_key} {c[wall_key]:.4f}s exceeds "
                    f"baseline {b[wall_key]:.4f}s * {tolerance:g} + "
                    f"{BASELINE_ABS_SLACK_S:g}s = {limit:.4f}s")
        if c["dispatch"] != b["dispatch"]:
            diff = sorted(set(c["dispatch"].items())
                          ^ set(b["dispatch"].items()))
            keys = sorted({k for k, _ in diff})
            failures.append(
                f"{key}: serving counters changed ({', '.join(keys)}) — "
                f"if intended, refresh with --write-baseline")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph, few queries (CI lane)")
    ap.add_argument("--json", default="SERVE_results.json")
    ap.add_argument("--check-baseline", default=None, metavar="PATH")
    ap.add_argument("--write-baseline", default=None, metavar="PATH")
    ap.add_argument("--tolerance", type=float, default=3.0)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    suite = run_suite(args.smoke)
    print("backend,p50_ms,p99_ms,seq_qps,batched_qps,speedup,"
          "batched_launches,parity")
    for r in suite:
        print(f"{r['backend']},{r['p50_s'] * 1e3:.2f},"
              f"{r['p99_s'] * 1e3:.2f},{r['seq_qps']:.0f},"
              f"{r['batched_qps']:.0f},{r['batched_speedup']:.2f},"
              f"{r['dispatch']['pipeline.batched_launches']},"
              f"{r['parity']}")

    with open(args.json, "w") as f:
        json.dump({"meta": {"smoke": bool(args.smoke),
                            "argv": sys.argv[1:],
                            "unix_time": time.time()},
                   "suite": suite}, f, indent=2)
    print(f"# wrote {args.json}")

    # exact gates, baseline-independent
    bad = [r for r in suite if not r["parity"]]
    if bad:
        print(f"# SERVE PARITY FAILURES: {[r['backend'] for r in bad]}")
        sys.exit(1)
    drifted = [r for r in suite if not r["hbm_ok"]]
    if drifted:
        print("# HBM MODEL DRIFT (static footprint model vs live device "
              "caches):")
        for r in drifted:
            print(f"#   {r['backend']}: {r['hbm'].get('error')}")
        sys.exit(1)
    for r in suite:
        for tenant, h in sorted(r["hbm"].items()):
            print(f"# hbm[{r['backend']}/{tenant}]: "
                  f"model={h['model_bytes']}B live={h['live_bytes']}B "
                  f"delta={h['delta_bytes']}B")
    recompiles = [r for r in suite
                  if any(r["dispatch"].get(k, 0)
                         for k in ("compile.plan_searches",
                                   "compile.logical_compiles",
                                   "compile.physical_builds"))
                  or r["retraces"]]
    if recompiles:
        print("# NO-RECOMPILE VIOLATIONS (plan searches / builds / "
              "retraces during the serving phase):")
        for r in recompiles:
            print(f"#   {r['backend']}: {r['dispatch']} "
                  f"retraces={r['retraces']}")
        sys.exit(1)

    if args.write_baseline:
        write_baseline(suite, args.write_baseline, args.smoke)
    if args.check_baseline:
        failures = check_baseline(suite, args.check_baseline,
                                  args.tolerance, args.smoke)
        if failures:
            print("# SERVE BASELINE REGRESSIONS:")
            for fail in failures:
                print(f"#   {fail}")
            sys.exit(1)
        print(f"# serve baseline check OK ({args.check_baseline}, "
              f"tolerance {args.tolerance:g}x)")


if __name__ == "__main__":
    main()
