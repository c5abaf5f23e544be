"""Shared Pallas kernel utilities.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
compiled there.  On any other JAX backend ``interpret_default()`` flips
them into interpret mode, which executes the kernel body through the
interpreter for correctness validation against the pure-jnp oracles in
each kernel's ``ref.py`` — never for speed.  ``DeviceBackend`` resolves
it once, and ``chip_smoke.py`` fails when it resolves to interpret.

TPU tiling notes (v5e): int32/float32 native VREG tile is (8, 128)
(sublane, lane); bf16 is (16, 128). Block shapes below are multiples of the
native tile so the MXU/VPU see hardware-aligned operands.
"""
from __future__ import annotations

import jax
import numpy as np

from repro import trace

# Native tile geometry for fp32/int32 operands.
SUBLANE = 8
LANE = 128


def interpret_default() -> bool:
    """True when no TPU is attached (kernel body runs in Python)."""
    return jax.default_backend() != "tpu"


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def pad_axis(arr, axis: int, to: int, value=0):
    """Pad ``arr`` along ``axis`` up to length ``to`` with ``value``."""
    import jax.numpy as jnp

    cur = arr.shape[axis]
    if cur == to:
        return arr
    assert cur < to, (cur, to)
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, to - cur)
    return jnp.pad(arr, widths, constant_values=value)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def audit_avals(tree):
    """ShapeDtypeStruct mirror of a pytree of (device) arrays.

    The trace-level auditor (``repro.analysis.jaxpr_audit``) records
    program operands through this instead of keeping live buffers: avals
    are enough to retrace abstractly with ``jax.make_jaxpr``, retain no
    device memory, and — crucially — cause no transfer, so recording is
    invisible to the host-sync budget."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def canonical_dtype(dt) -> np.dtype:
    """The dtype a host array actually has ON DEVICE under the current
    x64 regime: ``jnp.asarray`` silently narrows 64-bit widths when x64
    is off, which is exactly why device-byte accounting
    (``repro.analysis.memory_budget``) must not trust host ``nbytes``."""
    dt = np.dtype(dt)
    if jax.config.jax_enable_x64:
        return dt
    down = {"int64": np.int32, "uint64": np.uint32,
            "float64": np.float32, "complex128": np.complex64}
    return np.dtype(down.get(dt.name, dt))


def host_get(tree):
    """THE device→host transfer of the engine's device-resident paths.

    Every closing sync — the Generic-Join pipeline's landing
    (``core.backend``), the recursion fixpoints (``core.recursion``) and
    the materialize kernel's compacted extraction
    (``kernels.materialize.ops``) — routes through this one call site, so
    the static host-sync ratchet (``repro.analysis.sync_lint`` against
    ``sync_baseline.json``) audits exactly one ``device_get`` for the
    whole device path.  Adding a transfer anywhere else in the budgeted
    modules fails the linter; adding one here fails the baseline count.
    The span ``eh.land`` around it holds the wait for the device as well
    as the transfer.
    """
    with trace.span("eh.land"):
        return jax.device_get(tree)
