"""Find the knee of a serving cell: run its open loop at rising fixed
rates in one process and print, per rate, the latency percentiles and
whether the backlog grew.

    python3 bench/sweep.py --workload <name> --seed <n> --rates 1,2,4,8 --seconds 20

The server is built and warmed once, as in a run of the cell; each rate
then gets a fresh schedule.  A rate is sustained when the loop answers
its last request within a second of the window's close and the wait of
the window's last third stays under twice that of its first third plus
50 ms.  The sweep stops at the first rate that is not sustained; the
knee is the last rate that was.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def sweep(server, rates, seconds, seed, compiles):
    """Rows of the sweep, one per rate tried."""
    from ehbench import harness
    plans = [server.schedule(r, seconds, harness.rng(seed, 100 + k))
             for k, r in enumerate(rates)]
    server.warm_up(len(plans[0][0]), np.concatenate([b for _, b in plans]),
                   compiles)
    rows = []
    for rate, (due, binds) in zip(rates, plans):
        c0 = compiles.n
        w = server.window(due, binds)
        third = max(1, len(due) // 3)
        row = {
            "rate": rate, "requests": len(due),
            "p50_ms": float(np.percentile(w["latency"], 50)) * 1e3,
            "p95_ms": float(np.percentile(w["latency"], 95)) * 1e3,
            "waiting_at_close": int(np.sum(due + w["latency"] > seconds)),
            "drain_tail_s": w["end"] - seconds,
            "wait_first_third_ms": float(np.mean(w["wait"][:third])) * 1e3,
            "wait_last_third_ms": float(np.mean(w["wait"][-third:])) * 1e3,
            "max_batch": max(w["sizes"]), "drains": len(w["sizes"]),
            "compiles": compiles.n - c0}
        row["sustained"] = bool(
            row["drain_tail_s"] < 1.0
            and row["wait_last_third_ms"]
            < 2 * row["wait_first_third_ms"] + 50)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from ehbench import harness, loops, registry
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    harness.device_info(cell["chips"], True)
    compiles = harness.CompileCounter()
    server = loops.Server(cfg=cfg, traffic=traffic,
                          graph=harness.build_graph(cfg), seed=args.seed,
                          require_tpu=True, fault=None, rng=harness.rng)
    rows = sweep(server, [float(r) for r in args.rates.split(",")],
                 args.seconds, args.seed, compiles)
    ok = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"knee": ok[-1] if ok else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
