"""Run a cell with its control in the program's place, on several seeds,
and print the numbers its comparison reads.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

The control is the plain reference with one guarantee of the
configuration broken (``ehbench.controls.control``).  The cell's
reference module names its variants in ``CONTROLS``: for the triangle
counts ``unordered`` counts each match once, ``int16`` counts in 16
bits, ``int32`` in the engine's own 32 bits (the nearest type below the
``long`` the query declares); for PageRank ``bf16`` holds the ranks in
bfloat16 and ``iters4`` stops a round short.  ``--controls`` picks some
of them; by default every one runs.  Every line of output is one JSON
object per seed and control; a sound control reads ``correct: false``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default=None,
                    help="comma-separated names of the reference's "
                         "CONTROLS (default: all)")
    args = ap.parse_args(argv)

    from ehbench import controls, harness, registry
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    graph = harness.build_graph(cfg)
    ref = registry.reference(traffic["reference"])
    names = (args.controls.split(",") if args.controls
             else list(ref.CONTROLS))
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in names:
            hook = controls.control(graph, ref, name)
            res = harness.run_cell(cell, cfg, traffic, seed, args.seconds,
                                   False, time.perf_counter(), fault=hook)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": name,
                "correct": all(c.ok for c in res["checks"]),
                "attempted": res["attempted"], "failed": res["failed"],
                "checks": {c.name: c.value for c in res["checks"]},
                "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
