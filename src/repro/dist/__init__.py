"""Distributed-execution layer: sharding rules, mesh context, fault policy.

Three orthogonal pieces, each consumed by a different layer of the stack:

  sharding      declarative rule tables (LM/GNN/recsys) + a shape-aware
                resolver mapping logical weight axes to mesh axes
                (used by launch.cells to build in/out shardings)
  act_sharding  mesh-context helpers for activation sharding constraints
                inside model code (no-ops on a 1-device mesh, so the same
                model functions run unsharded on the host CPU)
  fault         FaultPolicy + StepRunner: retry-on-transient-failure and
                checkpoint cadence for the training loop
"""
from repro.dist.fault import FaultPolicy, StepRunner, TransientError  # noqa: F401
