"""Benchmark harness: one module per paper table/figure, plus the
cross-backend suite that tracks the perf trajectory across PRs.

``PYTHONPATH=src python -m benchmarks.run [--only tableN] [--smoke]``

prints ``name,us_per_call,derived`` CSV rows (paper protocol: 7 runs,
trimmed mean) and writes ``BENCH_results.json`` — machine-readable
per-query × per-backend wall times plus the backend's kernel-dispatch
counters, so regressions in *where* intersections execute are visible,
not just regressions in time.  The suite runs with the static
verification layer fully on (``verify_plans`` default + dispatch
``sanitize``), and records the ``analysis.*`` counters — in the
exact-compared dispatch deltas and as per-row engine-lifetime totals —
so the baseline gate also proves verification stayed on.  Queries whose cost-based plan search
(``core.plan_search``) picked a non-appearance-order plan are ALSO timed
with ``REPRO_PLAN_SEARCH=off`` semantics, recording the wall-time win
and result parity against the seed plan in the artifact.

``--smoke`` runs only the backend suite on tiny graphs (one repetition),
for CI's bench-smoke lane. ``--only`` restricts the run to the matching
table/figure module and skips the backend suite (unless the filter
mentions "backend").

Device rows additionally carry the whole-bag-fusion A/B
(``bag_fusion.speedup_vs_unfused`` with exact parity, one launch per
bag vs one per attribute step) and the per-query jit-launch budget
(``pipeline.launches == extend.closing_syncs`` — gated EXACTLY below).

Bench-regression gate (CI): ``--check-baseline benchmarks/baseline.json``
compares the suite against the committed baseline — wall times within a
generous ``--tolerance`` (default 3x plus a fixed absolute slack: smoke
walls are sub-second and shared-runner throughput swings 2-3x, so the
wall check only catches gross regressions; the EXACT dispatch-counter
and parity comparison is the sharp, machine-independent half of the
gate) — and exits nonzero on regression.  ``--write-baseline PATH`` refreshes the file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# absolute wall slack (seconds) under --check-baseline: smoke runs are
# jit-compile dominated and tiny, so a pure ratio would flag noise
BASELINE_ABS_SLACK_S = 0.25


# ----------------------------------------------------- backend suite
def _result_digest(res):
    if not res.vars:
        return float(np.asarray(res.scalar()))
    ann = res.annotation
    if ann is None:
        return int(res.num_rows)
    return float(np.asarray(ann, dtype=np.float64).sum())


def _ab_walls(eng, q, reps, set_mode, capture_counters=False):
    """Warmed, interleaved A/B timing shared by the plan-search and
    device-recursion comparisons: ``set_mode(False|True)`` toggles the
    engine feature, one untimed execution per mode absorbs plan search /
    jit / codegen / store builds, then ``max(reps, 2)`` interleaved
    timed pairs (machine-speed drift hits both modes).  Returns
    ``(walls dict, last off-mode result, last off-mode counter delta)``
    and leaves the feature switched back on."""
    from repro.core.executor import BagResultCache

    def one(mode_on):
        set_mode(mode_on)
        eng.bag_cache = BagResultCache()
        before = dict(eng.backend.stats) if capture_counters else None
        t0 = time.perf_counter()
        res = eng.query(q)
        wall = time.perf_counter() - t0
        delta = ({k: v - before.get(k, 0)
                  for k, v in eng.backend.stats.items()}
                 if capture_counters else None)
        return wall, res, delta

    ws = {False: [], True: []}
    off_res = off_delta = None
    for mode in (False, True):      # untimed warmup
        one(mode)
    for _ in range(max(reps, 2)):   # interleaved timed pairs
        for mode in (False, True):
            w, res, d = one(mode)
            ws[mode].append(w)
            if mode is False:
                off_res, off_delta = res, d
    set_mode(True)
    return ws, off_res, off_delta


def run_backend_suite(smoke: bool) -> list:
    """Every paper query on every backend: wall time + dispatch counters.

    Also asserts cross-backend result parity (the differential-testing
    invariant of the backend layer) — a mismatch is reported in the row
    rather than silently timed.
    """
    from repro.core.engine import Engine
    from repro.core.executor import BagResultCache
    from repro.core.workload import ALIASES, paper_query_set
    from repro.data import powerlaw_graph

    n, deg, reps = (150, 6, 1) if smoke else (2000, 12, 3)
    g = powerlaw_graph(n, deg, 2.0, seed=0)
    src = np.repeat(np.arange(g.n), g.degrees)
    hub = int(np.argmax(g.degrees))

    out = []
    digests = {}
    for backend in ("numpy", "device"):
        # sanitize=True: every suite execution runs the dispatch
        # sanitizer (repro.analysis.kernel_check.check_dispatch), and the
        # analysis.* counters land in each row's exact-compared dispatch
        # delta — the baseline gate thereby proves verification stayed on
        eng = Engine(backend=backend, sanitize=True)
        eng.load_edges("Edge", src, g.neighbors)
        for al in ALIASES:
            eng.alias(al, "Edge")
        # untimed process warmup: one throwaway query absorbs the
        # per-process jax/XLA init so the FIRST suite entry's wall
        # measures the query, not interpreter startup (matters for the
        # --check-baseline gate, which compares absolute walls)
        eng.query("Warm(;w:long) :- Edge(x,y); w=<<COUNT(*)>>.")
        for qname, q in paper_query_set(source=hub):
            walls = []
            res = None
            dispatch = {}
            for _ in range(reps):
                # fresh engine-lifetime bag cache per rep: the suite times
                # the join work (paper protocol), not cross-query reuse —
                # within-query cross-rule hits still occur and are counted
                eng.bag_cache = BagResultCache()
                before = eng.backend.dispatch_summary()
                t0 = time.perf_counter()
                res = eng.query(q)
                walls.append(time.perf_counter() - t0)
                # last rep's delta: per-execution counts, comparable
                # between --smoke (1 rep) and full (3 reps) artifacts
                # (dispatch_summary leaves the spans' wall times out)
                dispatch = {k: v - before.get(k, 0)
                            for k, v in eng.backend.dispatch_summary().items()
                            if v != before.get(k, 0)}
            digest = _result_digest(res)
            digests.setdefault(qname, digest)
            plan_md = eng.plan_metadata()
            row = {
                "query": qname,
                "backend": backend,
                "wall_s": min(walls),
                "result": digest,
                "parity": bool(np.isclose(digest, digests[qname],
                                          rtol=1e-5, atol=1e-6)),
                # the zero-sync invariant, stated per query: the device
                # pipeline must never take a per-extension host round-
                # trip (gated EXACTLY at zero below), and lands once per
                # executed join (closing_syncs)
                "host_syncs": int(dispatch.get("extend.host_syncs", 0)),
                "closing_syncs": int(dispatch.get("extend.closing_syncs",
                                                  0)),
                "pipeline_on": bool(getattr(eng.backend,
                                            "pipeline_enabled", False)),
                # whole-bag fusion launch budget: with fusion on, every
                # executed join bag is ONE jit launch, so launches ==
                # closing_syncs (gated EXACTLY below)
                "launches": int(dispatch.get("pipeline.launches", 0)),
                "fused_on": bool(getattr(eng.backend, "fuse_bags",
                                         False)),
                "dispatch": dispatch,
                # cumulative static-verification counters (plans and
                # search candidates validated, sanitize assertions run):
                # the per-rep delta above can miss plans_verified on
                # warm physical-plan-cache reps, so the artifact also
                # carries the engine-lifetime totals
                "analysis": {k: int(v)
                             for k, v in sorted(eng.backend.stats.items())
                             if k.startswith("analysis.")},
                # optimizer choices per executed rule: fhw, attribute
                # order, per-level layout routing + threshold, estimated
                # vs actual cardinalities — so plan-quality regressions
                # are visible in the artifact, not just wall time.
                "plan": plan_md,
            }
            # Cost-based search changed this query's plan: time BOTH modes
            # warmed (one untimed execution each absorbs plan search,
            # codegen and store builds — with reps=1 in --smoke the main
            # wall is compile-contaminated, which would bias the
            # comparison either way) and record the win + parity.
            changed = any(r.get("plan_search", {}).get("order_changed")
                          for r in plan_md)
            if changed and eng.plan_search:
                ws, off_res, _ = _ab_walls(
                    eng, q, reps,
                    lambda m: setattr(eng, "plan_search", m))
                on_wall, off_wall = min(ws[True]), min(ws[False])
                row["plan_search"] = {
                    "order_changed": True,
                    "wall_s_warm": on_wall,
                    "baseline_wall_s": off_wall,
                    "speedup_vs_off": off_wall / max(on_wall, 1e-9),
                    "parity_vs_off": bool(np.isclose(
                        digest, _result_digest(off_res),
                        rtol=1e-5, atol=1e-6)),
                }
            # Recursion ran as a device-resident fixpoint: ALSO time the
            # pre-PR per-round host loop (device_recursion off) warmed,
            # recording the per-round wall-time win + result parity — the
            # recursion half of the bench gate.
            rec_rounds = int(dispatch.get("recursion.device_rounds", 0))
            if backend == "device" and rec_rounds:
                ws, host_res, host_delta = _ab_walls(
                    eng, q, reps,
                    lambda m: setattr(eng, "device_recursion", m),
                    capture_counters=True)
                host_rounds = int(host_delta.get("recursion.host_rounds",
                                                 rec_rounds))
                dev_w, host_w = min(ws[True]), min(ws[False])
                row["device_recursion"] = {
                    "rounds": rec_rounds,
                    "wall_s_warm": dev_w,
                    "host_loop_wall_s": host_w,
                    "host_loop_rounds": host_rounds,
                    # whole-query walls divided by rounds: approximate
                    # (non-recursive rules amortized in), comparable
                    # between the two modes on the same query
                    "per_round_wall_s": dev_w / max(rec_rounds, 1),
                    "per_round_host_wall_s": host_w / max(host_rounds, 1),
                    "speedup_vs_host_loop": host_w / max(dev_w, 1e-9),
                    "parity_vs_host_loop": bool(np.isclose(
                        digest, _result_digest(host_res),
                        rtol=1e-5, atol=1e-6)),
                }
            # Zero-sync pipeline A/B: time the pinned per-extension-sync
            # path (pipeline off) warmed against the device-resident
            # count-then-fill path on the same query — the perf half of
            # ROADMAP item 3's acceptance (device wall no worse than the
            # sync path), plus an extra differential-parity check.
            if (backend == "device"
                    and row["pipeline_on"]
                    and dispatch.get("extend.pipeline_extends", 0)):
                ws, sync_res, sync_delta = _ab_walls(
                    eng, q, reps,
                    lambda m: setattr(eng.backend, "pipeline_enabled", m),
                    capture_counters=True)
                pipe_w, sync_w = min(ws[True]), min(ws[False])
                row["device_pipeline"] = {
                    "wall_s_warm": pipe_w,
                    "sync_path_wall_s": sync_w,
                    "sync_path_host_syncs": int(
                        sync_delta.get("extend.host_syncs", 0)),
                    "speedup_vs_sync_path": sync_w / max(pipe_w, 1e-9),
                    "parity_vs_sync_path": bool(np.isclose(
                        digest, _result_digest(sync_res),
                        rtol=1e-5, atol=1e-6)),
                }
            # Whole-bag fusion A/B: time the per-attribute-step pipeline
            # (fusion off, one launch per step) warmed against the
            # one-launch-per-bag fused program on the same query — the
            # perf half of the fusion acceptance, plus exact parity.
            if (backend == "device"
                    and row["pipeline_on"] and row["fused_on"]
                    and dispatch.get("pipeline.launches", 0)):
                ws, unf_res, unf_delta = _ab_walls(
                    eng, q, reps,
                    lambda m: setattr(eng.backend, "fuse_bags", m),
                    capture_counters=True)
                fus_w, unf_w = min(ws[True]), min(ws[False])
                row["bag_fusion"] = {
                    "wall_s_warm": fus_w,
                    "unfused_wall_s": unf_w,
                    "unfused_launches": int(
                        unf_delta.get("pipeline.launches", 0)),
                    "speedup_vs_unfused": unf_w / max(fus_w, 1e-9),
                    "parity_vs_unfused": bool(np.isclose(
                        digest, _result_digest(unf_res),
                        rtol=1e-5, atol=1e-6)),
                }
            out.append(row)
    return out


# ------------------------------------------------- bench-regression gate
def _gate_summary(suite: list) -> dict:
    """The comparable slice of a suite run: wall + parity + EXACT dispatch
    counters per query × backend.  Recursion queries on the device
    backend additionally gate on host-loop parity — the dispatch
    counters (``recursion.device_rounds`` / ``recursion.host_trie_
    rebuilds``) are already part of the exact comparison, so a recursion
    round silently falling back to the host loop fails the gate."""
    out = {}
    for r in suite:
        entry = {
            "wall_s": float(r["wall_s"]),
            "parity": bool(r["parity"]),
            "host_syncs": int(r.get("host_syncs", 0)),
            "dispatch": {k: int(v) for k, v in sorted(r["dispatch"].items())},
        }
        rec = r.get("device_recursion")
        if rec is not None:
            entry["recursion_parity"] = bool(rec["parity_vs_host_loop"])
        pipe = r.get("device_pipeline")
        if pipe is not None:
            entry["pipeline_parity"] = bool(pipe["parity_vs_sync_path"])
        fus = r.get("bag_fusion")
        if fus is not None:
            entry["fusion_parity"] = bool(fus["parity_vs_unfused"])
        out[f"{r['query']}/{r['backend']}"] = entry
    return out


def write_baseline(suite: list, path: str, smoke: bool) -> None:
    payload = {
        "meta": {"smoke": bool(smoke), "unix_time": time.time(),
                 "note": "refresh with: python -m benchmarks.run --smoke "
                         "--write-baseline benchmarks/baseline.json"},
        "queries": _gate_summary(suite),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote baseline {path} ({len(payload['queries'])} entries)")


def check_baseline(suite: list, path: str, tolerance: float,
                   smoke: bool) -> list:
    """Compare ``suite`` against the committed baseline; returns the list
    of human-readable violations (empty = gate passes)."""
    with open(path) as f:
        base = json.load(f)
    cur = _gate_summary(suite)
    failures = []
    base_smoke = base.get("meta", {}).get("smoke")
    if base_smoke is not None and bool(base_smoke) != bool(smoke):
        return [f"baseline {path} was recorded with smoke={base_smoke} but "
                f"this run has smoke={smoke} — walls/dispatch are not "
                f"comparable across suite sizes"]
    for key in sorted(set(cur) - set(base["queries"])):
        failures.append(f"{key}: present in this run but not in the "
                        f"baseline — refresh with --write-baseline to gate it")
    for key, b in sorted(base["queries"].items()):
        c = cur.get(key)
        if c is None:
            failures.append(f"{key}: present in baseline but not in this run")
            continue
        if not c["parity"]:
            failures.append(f"{key}: cross-backend parity FAILED")
        if b.get("recursion_parity") and not c.get("recursion_parity", True):
            failures.append(f"{key}: device-recursion vs host-loop parity "
                            f"FAILED")
        if b.get("pipeline_parity") and not c.get("pipeline_parity", True):
            failures.append(f"{key}: pipeline vs pinned-sync-path parity "
                            f"FAILED")
        if b.get("fusion_parity") and not c.get("fusion_parity", True):
            failures.append(f"{key}: fused-bag vs per-step-pipeline parity "
                            f"FAILED")
        limit = b["wall_s"] * tolerance + BASELINE_ABS_SLACK_S
        if c["wall_s"] > limit:
            failures.append(
                f"{key}: wall {c['wall_s']:.3f}s exceeds baseline "
                f"{b['wall_s']:.3f}s * {tolerance:g} + "
                f"{BASELINE_ABS_SLACK_S:g}s = {limit:.3f}s")
        if c["dispatch"] != b["dispatch"]:
            diff = sorted(set(c["dispatch"].items())
                          ^ set(b["dispatch"].items()))
            keys = sorted({k for k, _ in diff})
            failures.append(
                f"{key}: dispatch counters changed ({', '.join(keys)}) — "
                f"if intended, refresh with --write-baseline")
    return failures


# ------------------------------------------------------------- driver
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module name")
    ap.add_argument("--smoke", action="store_true",
                    help="backend suite only, tiny graphs, 1 rep (CI lane)")
    ap.add_argument("--json", default="BENCH_results.json",
                    help="output path for the machine-readable results")
    ap.add_argument("--check-baseline", default=None, metavar="PATH",
                    help="compare the backend suite against a committed "
                         "baseline; exit nonzero on regression")
    ap.add_argument("--write-baseline", default=None, metavar="PATH",
                    help="write/refresh the bench baseline from this run")
    ap.add_argument("--tolerance", type=float, default=3.0,
                    help="wall-time regression tolerance factor for "
                         "--check-baseline (default 3x)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    module_rows = []
    if not args.smoke:
        from benchmarks import (appc_orderings, fig4_intersect_micro,
                                table4_layout_oracle, table5_triangle,
                                table6_pagerank, table7_sssp,
                                table8_ablations)
        modules = [table5_triangle, table6_pagerank, table7_sssp,
                   table8_ablations, table4_layout_oracle,
                   fig4_intersect_micro, appc_orderings]

        print("name,us_per_call,derived")
        for mod in modules:
            name = mod.__name__.split(".")[-1]
            if args.only and args.only not in name:
                continue
            t0 = time.monotonic()
            try:
                for r in mod.run():
                    print(r)
                    module_rows.append(r)
                    sys.stdout.flush()
            except Exception as e:  # report and continue
                print(f"{name},ERROR,{e!r}")
                module_rows.append(f"{name},ERROR,{e!r}")
            print(f"# {name} finished in {time.monotonic() - t0:.1f}s")

    if args.only and not args.smoke and "backend" not in args.only:
        if args.check_baseline or args.write_baseline:
            print("# ERROR: --check-baseline/--write-baseline need the "
                  "backend suite, which --only skips")
            sys.exit(2)
        # a filtered single-module run: skip the cross-backend suite
        payload = {"meta": {"smoke": False, "argv": sys.argv[1:],
                            "unix_time": time.time()},
                   "backend_suite": [], "module_rows": module_rows}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json} (backend suite skipped: --only)")
        return

    suite = run_backend_suite(args.smoke)
    print("query,backend,wall_ms,parity,top_dispatch")
    for row_ in suite:
        top = sorted((k for k in row_["dispatch"]
                      if k.startswith("intersect.")),
                     key=lambda k: -row_["dispatch"][k])
        extra = ""
        ps = row_.get("plan_search")
        if ps:
            extra = (f"  # plan changed: {ps['speedup_vs_off']:.2f}x vs "
                     f"search-off (parity={ps['parity_vs_off']})")
        pipe = row_.get("device_pipeline")
        if pipe:
            extra += (f"  # pipeline: 0 host syncs, "
                      f"{pipe['speedup_vs_sync_path']:.2f}x vs sync path "
                      f"({pipe['sync_path_host_syncs']} syncs, "
                      f"parity={pipe['parity_vs_sync_path']})")
        fus = row_.get("bag_fusion")
        if fus:
            extra += (f"  # fused bags: {row_['launches']} launches "
                      f"(vs {fus['unfused_launches']} unfused), "
                      f"{fus['speedup_vs_unfused']:.2f}x, "
                      f"parity={fus['parity_vs_unfused']}")
        rec = row_.get("device_recursion")
        if rec:
            extra += (f"  # device recursion: {rec['rounds']} rounds, "
                      f"{rec['speedup_vs_host_loop']:.2f}x vs host loop "
                      f"({rec['per_round_host_wall_s'] * 1e3:.1f} -> "
                      f"{rec['per_round_wall_s'] * 1e3:.1f} ms/round, "
                      f"parity={rec['parity_vs_host_loop']})")
        print(f"{row_['query']},{row_['backend']},"
              f"{row_['wall_s'] * 1e3:.1f},{row_['parity']},"
              f"{top[0] if top else '-'}{extra}")

    payload = {
        "meta": {"smoke": bool(args.smoke),
                 "argv": sys.argv[1:],
                 "unix_time": time.time()},
        "backend_suite": suite,
        "module_rows": module_rows,
    }
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {args.json}")

    # parity gates BEFORE the baseline is (re)written: a run with a
    # cross-backend mismatch must never produce a reference file
    bad = [r for r in suite if not r["parity"]]
    if bad:
        print(f"# PARITY FAILURES: {[r['query'] for r in bad]}")
        sys.exit(1)

    # zero-sync gate, EXACT and baseline-independent: with the pipeline
    # on, a device-backend query taking ANY per-extension host sync is a
    # regression (the invariant is ==0, not "few")
    leaky = [r for r in suite
             if r["backend"] == "device" and r.get("pipeline_on")
             and r.get("host_syncs", 0) != 0]
    if leaky:
        print("# ZERO-SYNC VIOLATIONS (extend.host_syncs != 0 with the "
              "device pipeline on):")
        for r in leaky:
            print(f"#   {r['query']}: {r['host_syncs']}")
        sys.exit(1)

    # launch-budget gate, EXACT and baseline-independent: with whole-bag
    # fusion on, every executed join bag is ONE jit launch, so
    # pipeline.launches must equal extend.closing_syncs (one landing per
    # join attempt — overflow retries count one launch per attempt)
    over = [r for r in suite
            if r["backend"] == "device" and r.get("pipeline_on")
            and r.get("fused_on")
            and r.get("launches", 0) != r.get("closing_syncs", 0)]
    if over:
        print("# LAUNCH-BUDGET VIOLATIONS (pipeline.launches != "
              "extend.closing_syncs with whole-bag fusion on):")
        for r in over:
            print(f"#   {r['query']}: {r['launches']} launches, "
                  f"{r['closing_syncs']} landings")
        sys.exit(1)

    if args.write_baseline:
        write_baseline(suite, args.write_baseline, args.smoke)

    if args.check_baseline:
        failures = check_baseline(suite, args.check_baseline,
                                  args.tolerance, args.smoke)
        if failures:
            print("# BENCH BASELINE REGRESSIONS:")
            for fail in failures:
                print(f"#   {fail}")
            sys.exit(1)
        print(f"# baseline check OK ({args.check_baseline}, "
              f"tolerance {args.tolerance:g}x)")


if __name__ == "__main__":
    main()
