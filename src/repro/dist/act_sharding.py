"""Mesh-context helpers for activation sharding inside model code.

Model functions call ``constrain(x, "batch", None, "model", ...)`` with
LOGICAL names; the mapping to mesh axes happens here, against the mesh
active at trace time (entered via ``use_mesh``). On the host CPU — no mesh,
or a 1-device mesh — every helper is a no-op, so the exact same model code
runs unsharded in unit tests.

``with_batch_axes(fn, axes)`` rebinds what 'batch' means for the duration
of one step function: MoE cells keep activations on ('pod', 'data') while
dense-FSDP cells spread them over ('pod', 'data', 'model').

Like the resolver in ``sharding``, constraints are shape-aware: 'batch'
composes its axes left-to-right and keeps the longest prefix that divides
the actual dim, so padded/odd batch dims degrade to replication instead of
failing to lower.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
from jax._src import mesh as mesh_lib
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.sharding import assign_prefix

# Non-batch logical activation axes -> candidate mesh axes.
_ACT_RULES = {
    "expert": ("model",),     # EP all-to-all boundary in moe_ffn
    "model": ("model",),      # head/TP-sharded score & accumulator dims
    "heads": ("model",),
    "cache_seq": ("model",),  # context parallelism over the KV cache
}
_DEFAULT_BATCH_AXES: Tuple[str, ...] = ("pod", "data")
_batch_axes_var: contextvars.ContextVar = contextvars.ContextVar(
    "repro_batch_axes", default=_DEFAULT_BATCH_AXES)


def _current_mesh() -> Optional[Mesh]:
    """The mesh active for the current trace (see ``use_mesh``); None on
    the bare host."""
    mesh = mesh_lib.thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        return None
    return mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for tracing/lowering (jax 0.4.x spelling of the
    newer ``jax.set_mesh``): makes it visible to ``_current_mesh`` and to
    GSPMD sharding propagation."""
    with mesh:
        yield mesh


def current_batch_axes() -> Tuple[str, ...]:
    return _batch_axes_var.get()


def with_batch_axes(fn, axes: Tuple[str, ...]):
    """Wrap ``fn`` so that, while it runs (i.e. while it traces), the
    logical 'batch' axis maps to ``axes``."""
    axes = tuple(axes)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        token = _batch_axes_var.set(axes)
        try:
            return fn(*args, **kwargs)
        finally:
            _batch_axes_var.reset(token)

    return wrapped


def model_axis_size() -> int:
    """Size of the 'model' mesh axis for the current trace (1 on host)."""
    mesh = _current_mesh()
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("model", 1))


def constrain(x, *axes):
    """``with_sharding_constraint`` by logical axis names, one per dim
    (None = unconstrained). No-op without a multi-device mesh."""
    if len(axes) != x.ndim:  # checked even without a mesh, so the 1-device
        raise ValueError(    # unit tests catch malformed call sites
            f"constrain: {len(axes)} axis names for rank-{x.ndim} value")
    mesh = _current_mesh()
    if mesh is None or mesh.size <= 1:
        return x
    mesh_shape = dict(mesh.shape)
    entries: list = [None] * x.ndim
    used: set = set()
    for i, name in enumerate(axes):
        if name is None:
            continue
        cand = current_batch_axes() if name == "batch" \
            else _ACT_RULES.get(name, ())
        entries[i] = assign_prefix(x.shape[i], cand, mesh_shape, used)
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))
