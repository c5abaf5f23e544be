"""Smoke run of the engine's device path on one TPU chip.

    python3 chip_smoke.py                      # full size, needs a TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --scales 9,7,10   # rehearsal

Drives the system through the entry points a user calls, in this one
process, on seeded Graph500 Kronecker graphs (edge factor 16, Graph500's
a/b/c) made in bulk at start-up:

  kernels    the three pair kernels the engine dispatches, compiled
             (never interpreted), bit for bit against their ref.py
             oracles at real widths;
  pattern    ``Engine(backend="device").load_edges`` + ``Engine.query``
             for the triangle, lollipop and barbell programs of
             ``core.workload.paper_query_set`` on the symmetric edge
             relation at the pattern scale, and 4-clique at the clique
             scale; each equals the ``NumpyBackend`` engine's answer and
             runs with ``pipeline.launches >= 1`` and
             ``extend.host_syncs == 0``;
  serve      ``QueryServer`` over the device backend on the pattern
             graph: one prepared anchored triangle query, answered
             singly and as a ``run_batch`` of 32 Zipf-drawn vertex
             bindings (``pipeline.batched_launches >= 1``), each equal
             to the numpy engine's answer;
  analytics  PageRank and SSSP at the analytics scale, each one device
             fixpoint with no host rounds, against plain numpy
             implementations of the same programs (PageRank to a
             tolerance set from float32 summation, SSSP exactly).

``--scales P,C,A`` sets the pattern, clique and analytics scales
(default 17,10,21).  Earlier lines report, per phase, wall times with
the closing transfer inside the timed span (first call = compile + run,
second = steady where it is run, and the engine's own dispatch-compile
wall), counters, modelled device bytes and the device's peak memory.
Exit code 0 and the last line ``{"ok": true, "device": {...}}`` only
when every phase passed on a TPU with no kernel in interpret mode.  Off a TPU the full-size run stops at
once; a run with ``--scales`` runs every phase and then fails on the
platform check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DEFAULT_SCALES = (17, 10, 21)
SEED = 0            # graphs, kernel inputs and serving bindings
EDGE_FACTOR = 16
PR_ITERS = 5
SERVE_BATCH = 32
ZIPF_A = 1.5
# float32 PageRank against a float64 reference: relative error from
# summing up to max-degree float32 terms (~1e-5 at these degrees), with
# a floor far below 1/N
PR_RTOL, PR_ATOL = 1e-4, 1e-10
ANCHORED_TRIANGLE = "C(;w:long) :- R(0,y),S(y,z),T(0,z); w=<<COUNT(*)>>."


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn):
    """Wall seconds of ``fn`` — every engine entry point returns host
    arrays, so its closing ``host_get`` is inside the span."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def counter_delta(before, after, prefixes=("extend.", "pipeline.",
                                            "recursion.", "intersect.",
                                            "fold.")):
    return {k: int(v - before.get(k, 0)) for k, v in sorted(after.items())
            if v != before.get(k, 0) and k.startswith(prefixes)}


def graph(scale: int, seed: int):
    from repro.data.graphs import kronecker_graph
    (g, dt) = timed(lambda: kronecker_graph(scale, EDGE_FACTOR, seed=seed))
    src = np.repeat(np.arange(g.n, dtype=np.int64), g.degrees)
    log(f"[graph] scale={scale} vertices={g.n} directed_edges={g.m} "
        f"max_degree={int(g.degrees.max())} gen_s={dt:.3f}")
    return g, src


def engines(g, src):
    from repro.core.engine import Engine
    from repro.core.workload import ALIASES
    out = []
    for backend in ("device", "numpy"):
        eng = Engine(backend=backend)
        eng.load_edges("Edge", src, g.neighbors)
        for a in ALIASES:
            eng.alias(a, "Edge")
        out.append(eng)
    return out


# ------------------------------------------------------------------ phases
def phase_kernels(backend, seed: int) -> None:
    """Pair kernels compiled for the chip, bit for bit against ref.py."""
    import jax.numpy as jnp

    from repro.kernels.bitset_intersect.ops import bitset_and_popcount
    from repro.kernels.bitset_intersect.ref import bitset_and_popcount_ref
    from repro.kernels.materialize.kernel import bitset_materialize_kernel
    from repro.kernels.materialize.ops import _tri
    from repro.kernels.materialize.ref import bitset_materialize_ref
    from repro.kernels.uint_intersect.ops import uint_intersect_count
    from repro.kernels.uint_intersect.ref import uint_intersect_count_ref

    rng = np.random.default_rng(seed)
    interp = backend._interpret
    pairs = 1 << 16

    words = rng.integers(0, 1 << 32, size=(4096, 8), dtype=np.uint32)
    pa = rng.integers(0, 4096, pairs)
    pb = rng.integers(0, 4096, pairs)
    wd = jnp.asarray(words)
    want = np.asarray(bitset_and_popcount_ref(wd[pa], wd[pb]))
    check_kernel("bitset_and_popcount", want, lambda: np.asarray(
        bitset_and_popcount(words, pa, pb, interpret=interp)))

    # uint sets up to the engine's 256-element limit, -1 padded
    def sets(p):
        member = rng.random((p, 512)) < 0.25
        vals = np.where(member, np.arange(512), 1 << 30)
        vals = np.sort(vals, axis=1)[:, :256]
        return np.where(vals == 1 << 30, -1, vals).astype(np.int32)
    a, b = sets(8192), sets(8192)
    want = np.asarray(uint_intersect_count_ref(jnp.asarray(a),
                                               jnp.asarray(b)))
    check_kernel("uint_intersect", want, lambda: np.asarray(
        uint_intersect_count(a, b, interpret=interp)))

    ba = rng.integers(0, 2, size=(16384, 256)).astype(np.int32)
    bb = rng.integers(0, 2, size=(16384, 256)).astype(np.int32)
    want = np.concatenate([np.asarray(x).ravel() for x in
                           bitset_materialize_ref(jnp.asarray(ba),
                                                  jnp.asarray(bb))])
    check_kernel("materialize", want, lambda: np.concatenate(
        [np.asarray(x).ravel() for x in bitset_materialize_kernel(
            jnp.asarray(ba), jnp.asarray(bb), _tri(256), block_rows=256,
            interpret=interp)]))


def check_kernel(name, want, run) -> None:
    got, first = timed(run)
    got2, steady = timed(run)
    ok = np.array_equal(got, want) and np.array_equal(got2, want)
    log(f"[kernels] {name} elements={want.size} first_s={first:.6f} "
        f"steady_s={steady:.6f} bit_exact={ok}")
    if not ok:
        raise AssertionError(f"{name}: kernel output differs from ref.py")


def run_pattern(dev, ref, name: str, q: str, scale: int,
                steady: bool) -> None:
    """One pattern query on the device engine against the numpy engine.
    ``steady`` re-runs it (fresh bag cache) for a steady-state wall; at
    the pattern scale that second run is skipped, because the query is
    host-bound and its first call already costs about as much as a
    second one, and the whole run has to stay inside its time limit."""
    from repro.analysis.memory_budget import trie_device_bytes
    from repro.core.executor import BagResultCache

    before = dict(dev.backend.stats)
    res, first = timed(lambda: dev.query(q))
    delta = counter_delta(before, dev.backend.stats)
    key = "span.eh.bag.launch.compiles"
    launch_compiles = dev.backend.stats[key] - before.get(key, 0)
    results = [res]
    steady_s = None
    if steady:
        dev.bag_cache = BagResultCache()   # re-launch, not a cache answer
        res2, steady_s = timed(lambda: dev.query(q))
        results.append(res2)
    want, ref_s = timed(lambda: ref.query(q))
    got = [int(np.asarray(r.scalar())) for r in results]
    exp = int(np.asarray(want.scalar()))
    log(f"[pattern] {name} scale={scale} result={got[0]} reference={exp} "
        f"first_s={first:.6f} steady_s={steady_s} "
        f"bag_launch_compiles={launch_compiles} "
        f"reference_s={ref_s:.6f} "
        f"edge_trie_device_bytes="
        f"{trie_device_bytes(dev.catalog.get('Edge'))} "
        f"peak_bytes={peak_bytes()} counters={json.dumps(delta)}")
    if any(g != exp for g in got):
        raise AssertionError(f"{name}: device {got} != numpy {exp}")
    if delta.get("pipeline.launches", 0) < 1:
        raise AssertionError(f"{name}: no device pipeline launch")
    if delta.get("extend.host_syncs", 0) != 0:
        raise AssertionError(f"{name}: host syncs {delta}")


def phase_pattern(g, src, clique_scale: int, seed: int, scale: int):
    from repro.core.workload import paper_query_set
    queries = dict(paper_query_set())
    dev, ref = engines(g, src)
    for name in ("triangle", "lollipop", "barbell"):
        run_pattern(dev, ref, name, queries[name], scale, steady=False)
    gc, srcc = graph(clique_scale, seed)
    devc, refc = engines(gc, srcc)
    run_pattern(devc, refc, "4clique", queries["4clique"], clique_scale,
                steady=True)


def phase_serve(g, src, seed: int) -> None:
    from repro.core.engine import Engine
    from repro.core.executor import BagResultCache
    from repro.serve import QueryServer

    srv = QueryServer(backend="device")
    srv.load_graph("kron", "R", src, g.neighbors)
    ref = Engine(backend="numpy")
    ref.load_edges("R", src, g.neighbors)
    for al in ("S", "T"):
        srv.alias("kron", al, "R")
        ref.alias(al, "R")
    # popularity: a seeded order over vertices with edges, drawn Zipf
    rng = np.random.default_rng(seed)
    popular = rng.permutation(np.flatnonzero(g.degrees > 0))
    ranks = np.minimum(rng.zipf(ZIPF_A, SERVE_BATCH), len(popular)) - 1
    bindings = [int(v) for v in popular[ranks]]
    pq = srv.prepare("kron", ANCHORED_TRIANGLE)
    rq = ref.prepare(ANCHORED_TRIANGLE)
    want = [int(np.asarray(rq.run(v).scalar())) for v in bindings]

    stats = srv.backend.stats
    single, first = timed(lambda: pq.run(bindings[0]))
    srv.engine("kron").bag_cache = BagResultCache()
    single2, steady = timed(lambda: pq.run(bindings[0]))
    before = dict(stats)
    batch, b_first = timed(lambda: pq.run_batch(bindings))
    delta = counter_delta(before, stats)
    batch2, b_steady = timed(lambda: pq.run_batch(bindings))
    got = [int(np.asarray(r.scalar())) for r in batch]
    got2 = [int(np.asarray(r.scalar())) for r in batch2]
    log(f"[serve] anchored_triangle single result="
        f"{int(np.asarray(single.scalar()))} reference={want[0]} "
        f"first_s={first:.6f} steady_s={steady:.6f}")
    log(f"[serve] anchored_triangle batch={SERVE_BATCH} "
        f"distinct_vertices={len(set(bindings))} first_s={b_first:.6f} "
        f"steady_s={b_steady:.6f} matches={sum(map(int.__eq__, got, want))}"
        f"/{SERVE_BATCH} peak_bytes={peak_bytes()} "
        f"counters={json.dumps(delta)}")
    for r in (single, single2):
        if int(np.asarray(r.scalar())) != want[0]:
            raise AssertionError("single anchored triangle != numpy")
    if got != want or got2 != want:
        raise AssertionError("batched anchored triangles != numpy")
    if delta.get("pipeline.batched_launches", 0) < 1:
        raise AssertionError(f"batch took no batched launch: {delta}")


def pagerank_ref(g, iters: int):
    """The PageRank program in plain numpy: keys are the vertices with
    out-edges, N their number, ``y = 0.15/N + 0.85 * sum_z PR(z)/deg(z)``
    over ``Edge(x,z)``."""
    deg = g.degrees.astype(np.int64)
    keys = np.flatnonzero(deg > 0)
    n_keys = len(keys)
    row = np.repeat(np.arange(g.n), deg)
    inv = np.zeros(g.n)
    inv[keys] = 1.0 / deg[keys]
    pr = np.zeros(g.n)
    pr[keys] = 1.0 / n_keys
    for _ in range(iters):
        s = np.bincount(row, weights=(pr * inv)[g.neighbors], minlength=g.n)
        pr = np.where(deg > 0, 0.15 / n_keys + 0.85 * s, 0.0)
    return keys, pr[keys]


def sssp_ref(g, source: int):
    """The SSSP program in plain numpy: ``Edge(source, x)`` at 1, then
    ``min(SSSP(w)) + 1`` over ``Edge(w, x)`` — breadth-first levels from
    the source's neighbours."""
    offs = g.offsets.astype(np.int64)
    dist = np.full(g.n, -1, np.int64)
    frontier = np.unique(g.neighbors[offs[source]:offs[source + 1]])
    d = 1
    while len(frontier):
        dist[frontier] = d
        lo, cnt = offs[frontier], np.diff(offs)[frontier]
        start = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
        nxt = np.unique(g.neighbors[start + np.arange(cnt.sum())])
        frontier = nxt[dist[nxt] < 0]
        d += 1
    keys = np.flatnonzero(dist >= 0)
    return keys, dist[keys]


def phase_analytics(scale: int, seed: int) -> None:
    from repro.analysis.memory_budget import trie_device_bytes
    from repro.core.engine import Engine
    from repro.core.workload import pagerank_program, sssp_program

    g, src = graph(scale, seed)
    eng = Engine(backend="device")
    _, load_s = timed(lambda: eng.load_edges("Edge", src, g.neighbors))
    hub = int(np.argmax(g.degrees))
    (pr_keys, pr_want), pr_ref_s = timed(lambda: pagerank_ref(g, PR_ITERS))
    (ss_keys, ss_want), ss_ref_s = timed(lambda: sssp_ref(g, hub))
    for name, q, ref_s in (("pagerank", pagerank_program(PR_ITERS),
                            pr_ref_s),
                           ("sssp", sssp_program(hub), ss_ref_s)):
        before = dict(eng.backend.stats)
        res, first = timed(lambda: eng.query(q))
        delta = counter_delta(before, eng.backend.stats)
        keys = np.asarray(res.columns[res.vars[0]], np.int64)
        ann = np.asarray(res.annotation, np.float64)
        if name == "pagerank":
            ok = (np.array_equal(keys, pr_keys)
                  and np.allclose(ann, pr_want, rtol=PR_RTOL, atol=PR_ATOL))
            err = (float(np.max(np.abs(ann - pr_want) / pr_want))
                   if len(keys) == len(pr_keys) else None)
            detail = f"max_rel_err={err}"
        else:
            ok = (np.array_equal(keys, ss_keys)
                  and np.array_equal(ann, ss_want.astype(np.float64)))
            detail = f"max_distance={int(ann.max(initial=0))}"
        log(f"[analytics] {name} scale={scale} keys={len(keys)} "
            f"reference_keys="
            f"{len(pr_keys) if name == 'pagerank' else len(ss_keys)} "
            f"{detail} load_s={load_s:.6f} first_s={first:.6f} "
            f"reference_s={ref_s:.6f} "
            f"edge_trie_device_bytes="
            f"{trie_device_bytes(eng.catalog.get('Edge'))} "
            f"peak_bytes={peak_bytes()} counters={json.dumps(delta)}")
        if not ok:
            raise AssertionError(f"{name}: device result != numpy")
        if delta.get("recursion.device_fixpoints", 0) != 1 \
                or delta.get("recursion.host_rounds", 0) != 0:
            raise AssertionError(f"{name}: not one device fixpoint: {delta}")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scales", default=None, metavar="P,C,A",
                    help="pattern, 4-clique and analytics Kronecker scales "
                         "(default %s)" % ",".join(map(str, DEFAULT_SCALES)))
    args = ap.parse_args(argv)
    scales = (tuple(int(s) for s in args.scales.split(","))
              if args.scales else DEFAULT_SCALES)
    if len(scales) != 3:
        ap.error("--scales takes three comma-separated integers")

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    from repro.core.backend import DeviceBackend, make_backend

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    on_tpu = device["platform"] == "tpu"
    log(f"[setup] device={json.dumps(device)} scales={scales} "
        f"compile_cache={cache_dir}")
    if not on_tpu and args.scales is None:
        log(f"[setup] FAIL: no TPU (platform {device['platform']!r}); the "
            f"full-size run needs one, pass --scales to rehearse")
        return 2

    problems = []
    backend = make_backend(None)
    if not isinstance(backend, DeviceBackend):
        problems.append(f"default backend is {backend.name!r}, not device")
        backend = DeviceBackend()
    if backend._interpret:
        problems.append("Pallas kernels resolve to interpret mode")

    t_all = time.perf_counter()
    failed = []

    def phase(name, fn, *a):
        t0 = time.perf_counter()
        try:
            fn(*a)
            log(f"[{name}] passed in {time.perf_counter() - t0:.3f}s")
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.3f}s")
            failed.append(name)

    p_scale, c_scale, a_scale = scales
    phase("kernels", phase_kernels, backend, SEED)
    g, src = graph(p_scale, SEED)
    phase("pattern", phase_pattern, g, src, c_scale, SEED, p_scale)
    phase("serve", phase_serve, g, src, SEED)
    del g, src
    phase("analytics", phase_analytics, a_scale, SEED)

    log(f"[done] wall_s={time.perf_counter() - t_all:.3f} "
        f"peak_bytes={peak_bytes()} failed_phases={failed}")
    if not on_tpu:
        problems.append(f"not a TPU: platform {device['platform']!r}")
    for p in problems:
        log(f"[done] FAIL: {p}")
    if failed or problems:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
