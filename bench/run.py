"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from ``BENCHMARK.json``.  The run needs a TPU: on any other
platform, with fewer chips than the cell asks for, or where the
engine's kernels would run in interpret mode, it exits with code 3 and
prints no result.  Otherwise the last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared with its limit), and the last lines of standard
error repeat the checks.
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


def process_start() -> float:
    """``time.perf_counter()`` at the start of this process, read from
    the kernel's process table where it has one."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from ehbench import harness, registry
    from ehbench.common import log
    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("error: the engine under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return EXIT_NO_PROGRAM

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # cache every program, however quick its compile, so that only a
    # cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    try:
        res = harness.run_cell(cell, cfg, traffic, args.seed, args.seconds,
                               bool(args.trace), t_start)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    record, checks = res["record"], res["checks"]
    line = {
        "correct": all(c.ok for c in checks),
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": harness.metrics(bench, args.workload, record,
                                   bool(args.trace)),
        "device": res["device"],
    }
    if args.trace:
        line["breakdown"] = harness.breakdown(record)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value} limit {c.limit} "
                    f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
