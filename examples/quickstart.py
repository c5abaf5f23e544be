"""Quickstart: the EmptyHeaded public API in 40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.engine import Engine

# a tiny undirected graph: two triangles sharing edge (1, 2)
edges = [(0, 1), (1, 2), (0, 2), (1, 3), (2, 3)]
src = np.array([u for u, v in edges] + [v for u, v in edges])
dst = np.array([v for u, v in edges] + [u for u, v in edges])

# no backend named: the platform decides — the device backend on a TPU
# host, the numpy backend elsewhere (REPRO_ENGINE_BACKEND overrides)
eng = Engine()
print(f"default backend: {eng.backend.name}")
eng.load_edges("Edge", src, dst)
for alias in ("R", "S", "T"):
    eng.alias(alias, "Edge")

# 1. triangle listing (paper Table 2, row 1)
tri = eng.query("Triangle(x,y,z) :- R(x,y),S(y,z),T(x,z).")
print(f"triangle listing rows: {tri.num_rows} (expect 12 = 2 triangles x 6)")

# 2. counting with an aggregate
cnt = eng.query("CountTriangle(;w:long) :- R(x,y),S(y,z),T(x,z); "
                "w=<<COUNT(*)>>.")
print(f"triangle count: {int(cnt.scalar())}")

# 3. PageRank (recursive datalog; paper Table 2)
pr = eng.query(
    "N(;w:int) :- Edge(x,y); w=<<COUNT(x)>>.\n"
    "InvDeg(x;y:float) :- Edge(x,z); y=1.0/<<COUNT(z)>>.\n"
    "PageRank(x;y:float) :- Edge(x,z); y=1.0/N.\n"
    "PageRank(x;y:float)*[i=5] :- Edge(x,z),PageRank(z),InvDeg(z); "
    "y=0.15/N+0.85*<<SUM(z)>>.")
print("pagerank:", {k: round(v, 4) for k, v in pr.as_dict().items()})

# 4. SSSP (seminaive recursion, MIN semiring)
sssp = eng.query("SSSP(x;y:int) :- Edge(0,x); y=1.\n"
                 "SSSP(x;y:int)* :- Edge(w,x),SSSP(w); y=<<MIN(w)>>+1.")
print("sssp from node 0:", {k: int(v) for k, v in sssp.as_dict().items()})

# 5. inspect the logical plan (GHD)
print("\nGHD plan for the Barbell query:")
eng.alias("U", "Edge")
eng.alias("R2", "Edge")
eng.alias("S2", "Edge")
eng.alias("T2", "Edge")
print(eng.explain("B(;w:long) :- R(x,y),S(y,z),T(x,z),U(x,a),R2(a,b),"
                  "S2(b,c),T2(a,c); w=<<COUNT(*)>>."))

# 6. the device execution backend, named explicitly: trie levels live on
# device, and the hot-path intersections run through the layout-cohort
# Pallas kernels (compiled on a TPU, interpret mode elsewhere).  On a TPU
# host this is also what Engine() above chose.
dev = Engine(backend="device")
dev.load_edges("Edge", src, dst)
for alias in ("R", "S", "T"):
    dev.alias(alias, "Edge")
cnt_dev = dev.query("CountTriangle(;w:long) :- R(x,y),S(y,z),T(x,z); "
                    "w=<<COUNT(*)>>.")
print(f"\ntriangle count on the device backend: {int(cnt_dev.scalar())} "
      f"(matches: {int(cnt_dev.scalar()) == int(cnt.scalar())})")
print("kernel-dispatch summary:")
for key, val in sorted(dev.dispatch_summary().items()):
    print(f"  {key:28s} {val}")
