"""Declarative sharding rules + shape-aware resolver.

Model code names its weight dimensions with LOGICAL axes ("embed", "heads",
"mlp", "table_rows", ...; see each model's ``param_axes``). This module maps
those names to MESH axes ("pod", "data", "model") per family:

  LM_RULES             TP/EP over 'model' (heads / experts / vocab / mlp),
                       weight FSDP over 'data'
  LM_DENSE_FSDP_RULES  dense-arch training: no TP, weights 2-D-sharded over
                       ('data', 'model') — the pure-FSDP mapping
  GNN_RULES            feature-dim TP; GNN weights are small, so most fall
                       under the replication threshold
  RECSYS_RULES         row-sharded embedding tables over 'model'

Resolution is SHAPE-AWARE: a mesh axis is only assigned to a dim whose size
it divides; on failure the axis falls through the table's priority list to
the next eligible logical axis (e.g. 56 heads on a model=16 mesh fall back
to the embed dim). A mesh axis is never assigned twice in one spec, and
tensors smaller than ``fsdp_min_size`` elements are replicated outright —
collective overhead beats the bytes saved.

All functions take either a concrete ``jax.sharding.Mesh`` or an
``AbstractMesh`` (resolution only reads ``mesh.shape``), so specs can be
computed without touching devices.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Tuple

import jax
from jax.sharding import PartitionSpec as P

AxisEntry = Any  # None | str | tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """One family's axis-assignment policy.

    model_priority  logical axes eligible for the 'model' mesh axis (TP/EP),
                    most-preferred first; first divisible dim wins
    fsdp_priority   logical axes eligible for the fsdp mesh axes
    fsdp_axes       mesh axes bound by weight FSDP, in binding order
    batch_axes      mesh axes composing the data-parallel batch dim
                    (callers prepend 'pod' on 3-D meshes; see launch.cells)
    act_rules       logical activation/cache axis -> candidate mesh axes;
                    'batch' composes left-to-right ('pod','data') and keeps
                    the longest divisible prefix
    fsdp_min_size   element-count floor below which a tensor is replicated
    """
    name: str
    model_priority: Tuple[str, ...]
    fsdp_priority: Tuple[str, ...]
    fsdp_axes: Tuple[str, ...] = ("data",)
    batch_axes: Tuple[str, ...] = ("data",)
    act_rules: Mapping[str, Tuple[str, ...]] = \
        dataclasses.field(default_factory=dict)
    fsdp_min_size: int = 1 << 18


LM_RULES = ShardingRules(
    name="lm",
    model_priority=("expert", "heads", "kv_heads", "vocab", "mlp", "embed",
                    "qk_lora"),
    fsdp_priority=("embed", "mlp", "vocab", "qk_lora", "layer", "expert",
                   "head_dim"),
    act_rules={"batch": ("pod", "data"),
               "cache_seq": ("model",),
               "kv_heads": ("model",),
               "heads": ("model",)},
)

# Dense archs train pure-FSDP: both mesh axes shard weights, activations
# stay data-parallel over the whole mesh (no TP all-reduces on the forward
# pass — the 2-D mapping from the dry-run's worst-fraction analysis).
LM_DENSE_FSDP_RULES = ShardingRules(
    name="lm-dense-fsdp",
    model_priority=(),
    fsdp_priority=("embed", "mlp", "vocab", "qk_lora", "layer", "heads",
                   "head_dim"),
    fsdp_axes=("data", "model"),
    batch_axes=("data", "model"),
    act_rules={"batch": ("pod", "data", "model")},
)

GNN_RULES = ShardingRules(
    name="gnn",
    model_priority=("feat_out", "feat", "bilinear", "vocab", "basis"),
    fsdp_priority=("feat_in", "feat", "basis", "layer"),
    act_rules={"batch": ("pod", "data")},
)

RECSYS_RULES = ShardingRules(
    name="recsys",
    model_priority=("table_rows", "embed"),
    fsdp_priority=("table_rows",),
    act_rules={"batch": ("pod", "data")},
)


# ------------------------------------------------------------------ resolver
def _is_axes_leaf(x) -> bool:
    """Leaves of a param_axes tree: None (replicated) or a tuple of
    logical-axis names (Nones allowed per-dim; () for scalars)."""
    return x is None or (isinstance(x, tuple) and
                         all(e is None or isinstance(e, str) for e in x))


def _axes_used(entries) -> set:
    used = set()
    for e in entries:
        if e is None:
            continue
        used.update(e if isinstance(e, tuple) else (e,))
    return used


def assign_prefix(dim_size: int, candidates, mesh_shape, used: set):
    """Longest prefix of ``candidates`` (present in the mesh, unused so
    far) whose composed size divides ``dim_size``. Returns a spec entry —
    None, a bare axis name, or a tuple — and records taken axes in
    ``used``. Shared by the batch/cache resolver and act_sharding's
    ``constrain`` so the composition semantics live in one place."""
    cand = tuple(a for a in candidates if a in mesh_shape and a not in used)
    while cand and dim_size % math.prod(mesh_shape[a] for a in cand):
        cand = cand[:-1]
    if not cand:
        return None
    used.update(cand)
    return cand[0] if len(cand) == 1 else cand


def _resolve_one(axes, shape, mesh, rules: ShardingRules,
                 fsdp: bool = False) -> P:
    """Resolve one tensor's logical axes to a PartitionSpec.

    Assignment order: (1) 'model' to the highest-priority logical axis
    whose dim size it divides; (2) if ``fsdp``, each fsdp mesh axis to the
    highest-priority still-unassigned divisible dim. Small tensors
    (< fsdp_min_size elements) are replicated outright.
    """
    if axes is None:
        return P(*([None] * len(shape)))
    if len(axes) != len(shape):
        raise ValueError(f"axes {axes} do not match shape {shape}")
    if math.prod(shape) < rules.fsdp_min_size:
        return P(*([None] * len(shape)))
    mesh_shape = dict(mesh.shape)
    entries: list = [None] * len(shape)
    used: set = set()

    def assign(mesh_axis: str, priority: Tuple[str, ...]) -> None:
        if mesh_axis not in mesh_shape or mesh_axis in used:
            return
        size = mesh_shape[mesh_axis]
        for name in priority:
            if name not in axes:
                continue
            i = axes.index(name)
            if entries[i] is None and shape[i] % size == 0:
                entries[i] = mesh_axis
                used.add(mesh_axis)
                return

    assign("model", rules.model_priority)
    if fsdp:
        for ax in rules.fsdp_axes:
            assign(ax, rules.fsdp_priority)
    return P(*entries)


def resolve_param_specs(axes_tree, shapes_tree, mesh, rules: ShardingRules,
                        fsdp: bool = False):
    """Map a param_axes tree + matching ShapeDtypeStruct tree to a tree of
    PartitionSpecs (same structure as the params)."""
    return jax.tree.map(
        lambda a, s: _resolve_one(a, tuple(s.shape), mesh, rules, fsdp=fsdp),
        axes_tree, shapes_tree, is_leaf=_is_axes_leaf)


def _resolve_batch_one(axes, shape, mesh, rules: ShardingRules) -> P:
    """Activation/cache spec: each named dim takes the longest divisible
    prefix of its candidate mesh axes that doesn't reuse an axis."""
    if axes is None:
        return P(*([None] * len(shape)))
    if len(axes) != len(shape):
        raise ValueError(f"axes {axes} do not match shape {shape}")
    mesh_shape = dict(mesh.shape)
    entries: list = [None] * len(shape)
    used: set = set()
    for i, name in enumerate(axes):
        if name is None:
            continue
        entries[i] = assign_prefix(shape[i], rules.act_rules.get(name, ()),
                                   mesh_shape, used)
    return P(*entries)


def resolve_batch_specs(axes_tree, shapes_tree, mesh, rules: ShardingRules):
    """Resolve batch/cache trees (e.g. ``transformer.cache_axes``) where
    dims name activation axes like 'batch' and 'cache_seq'."""
    return jax.tree.map(
        lambda a, s: _resolve_batch_one(a, tuple(s.shape), mesh, rules),
        axes_tree, shapes_tree, is_leaf=_is_axes_leaf)


# -------------------------------------------------------------------- ZeRO-1
def zero1_axes(spec: P, mesh, rules: ShardingRules) -> Tuple[str, ...]:
    """Mesh axes available to further shard optimizer state for ``spec``:
    the ('pod',) + fsdp axes present in the mesh and unused by the spec."""
    used = _axes_used(spec)
    return tuple(a for a in ("pod",) + tuple(rules.fsdp_axes)
                 if a in dict(mesh.shape) and a not in used)


def zero1_specs(pspecs, shapes_tree, mesh, rules: ShardingRules):
    """Optimizer-state specs: params' specs plus a ZeRO-1 data-axis shard.

    For each tensor, bind the available batch-parallel axes (composed, or a
    suffix of them if the full composition doesn't divide any free dim) to
    the first unassigned divisible dim. Tensors below the replication
    threshold, or with no divisible free dim, keep the param spec.
    """
    mesh_shape = dict(mesh.shape)

    def one(spec, sds):
        shape = tuple(sds.shape)
        if spec is None:
            spec = P(*([None] * len(shape)))
        if math.prod(shape) < rules.fsdp_min_size:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        trial = zero1_axes(spec, mesh, rules)
        while trial:
            total = math.prod(mesh_shape[a] for a in trial)
            for i, d in enumerate(shape):
                if entries[i] is None and d % total == 0:
                    entries[i] = trial[0] if len(trial) == 1 \
                        else tuple(trial)
                    return P(*entries)
            trial = trial[1:]
        return spec

    return jax.tree.map(one, pspecs, shapes_tree,
                        is_leaf=lambda x: x is None or isinstance(x, P))
