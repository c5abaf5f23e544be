"""End-to-end driver (the paper's kind: a graph engine serving queries).

Builds a power-law graph, preprocesses it the EmptyHeaded way (dictionary
encoding -> degree ordering -> symmetric pruning -> set-level layout
optimization), then serves a batch of pattern + analytics queries and
reports per-query latency and the layout optimizer's decisions.

    PYTHONPATH=src python examples/graph_analytics.py [--nodes 5000]

With no ``--backend`` the platform decides, as for ``Engine()``: the
device backend on a TPU host, numpy elsewhere (``REPRO_ENGINE_BACKEND``
overrides).  ``--backend device`` runs the whole query batch on the
device-resident set store: trie levels are
uploaded once, each attribute extension is a single fused device call,
and the terminal-fold intersections dispatch to the layout-cohort Pallas
kernels. The kernel-dispatch summary printed at the end shows which
kernel handled each intersection.
"""
import argparse
import time

import numpy as np

from repro.core.engine import Engine
from repro.core.layouts import HybridSetStore
from repro.data import powerlaw_graph
from repro.graph import (apply_ordering, graph_stats, order_nodes,
                         prune_symmetric)
from repro.kernels.bitset_intersect.ops import as_word_kernel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=3000)
    ap.add_argument("--mean-deg", type=float, default=12)
    ap.add_argument("--exponent", type=float, default=1.9)
    ap.add_argument("--backend",
                    default=None, choices=("numpy", "device"),
                    help="execution backend for the query engine "
                         "(default: chosen by the platform)")
    args = ap.parse_args()

    print("== build + preprocess ==")
    g = powerlaw_graph(args.nodes, args.mean_deg, args.exponent, seed=0)
    print("graph:", graph_stats(g))
    g = apply_ordering(g, order_nodes(g, "hybrid"))
    pruned = prune_symmetric(g)

    store = HybridSetStore.build(pruned,
                                 word_kernel=as_word_kernel())
    print("layout optimizer:", store.stats())

    print("\n== serve pattern queries (WCOJ engine) ==")
    eng = Engine(backend=args.backend)
    print(f"== backend: {eng.backend.name} ==")
    src = np.repeat(np.arange(g.n), g.degrees)
    eng.load_edges("Edge", src, g.neighbors)
    psrc = np.repeat(np.arange(pruned.n), pruned.degrees)
    eng_p = Engine(backend=args.backend)
    eng_p.load_edges("Edge", psrc, pruned.neighbors)
    for e in (eng, eng_p):
        for a in ("R", "S", "T", "U", "X", "Y", "R2", "S2", "T2"):
            e.alias(a, "Edge")

    queries = [
        ("triangle count (pruned)", eng_p,
         "C(;w:long) :- R(x,y),S(y,z),T(x,z); w=<<COUNT(*)>>."),
        ("4-clique count (pruned)", eng_p,
         "C(;w:long) :- R(x,y),S(y,z),T(x,z),U(x,a),X(y,a),Y(z,a); "
         "w=<<COUNT(*)>>."),
        ("lollipop count", eng,
         "C(;w:long) :- R(x,y),S(y,z),T(x,z),U(x,a); w=<<COUNT(*)>>."),
        ("barbell count (GHD early-agg)", eng,
         "C(;w:long) :- R(x,y),S(y,z),T(x,z),U(x,a),R2(a,b),S2(b,c),"
         "T2(a,c); w=<<COUNT(*)>>."),
        ("pagerank 5 iters", eng,
         "N(;w:int) :- Edge(x,y); w=<<COUNT(x)>>.\n"
         "InvDeg(x;y:float) :- Edge(x,z); y=1.0/<<COUNT(z)>>.\n"
         "PageRank(x;y:float) :- Edge(x,z); y=1.0/N.\n"
         "PageRank(x;y:float)*[i=5] :- Edge(x,z),PageRank(z),InvDeg(z); "
         "y=0.15/N+0.85*<<SUM(z)>>."),
        ("sssp from hub", eng,
         f"SSSP(x;y:int) :- Edge({int(np.argmax(g.degrees))},x); y=1.\n"
         "SSSP(x;y:int)* :- Edge(w,x),SSSP(w); y=<<MIN(w)>>+1."),
    ]
    for name, engine, q in queries:
        t0 = time.perf_counter()
        res = engine.query(q)
        dt = (time.perf_counter() - t0) * 1e3
        val = (int(res.scalar()) if not res.vars else f"{res.num_rows} rows")
        print(f"  {name:34s} {dt:8.1f} ms   -> {val}")

    print("\n== kernel-dispatch summary (which kernel handled each "
          "intersection) ==")
    merged = dict(eng.dispatch_summary())
    for k, v in eng_p.dispatch_summary().items():
        merged[k] = merged.get(k, 0) + v
    for key in sorted(merged):
        print(f"  {key:28s} {merged[key]}")


if __name__ == "__main__":
    main()
