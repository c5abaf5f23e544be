"""The recursion's device loop (``core.recursion._naive_device``): its
program name on the device, the bytes its rounds have to move, and its
share of the chip's HBM roofline.

One round of PageRank's naive fixpoint is a pull SpMV over the fixed
key set.  Each edge reads one 4-byte neighbour id and one 4-byte
gathered term; each key reads a row bound and an inverse degree and
writes its rank, 4 bytes each.  The engine counts the edges and keys of
every round of its device fixpoints (``recursion.edge_rounds``,
``recursion.key_rounds``), so ``8 E + 12 K`` is what any pull SpMV must
move for the same rounds.  The engine's own loop moves more (a key
index and a factor per edge as well); neither that nor any padding is
counted.  The work is bound by memory, not by arithmetic: a round does
one multiply and one add per edge.
"""
from __future__ import annotations

from ehbench import registry
from ehbench.readers import module_ms_per_unit

# XLA module name of the engine's jitted naive fixpoint
NAIVE_DEVICE = r"^jit__naive_device\("
EDGE_BYTES = 8
KEY_BYTES = 12


def pull_spmv_bytes(edge_rounds: int, key_rounds: int) -> int:
    """Bytes a pull SpMV moves over ``edge_rounds`` edges and
    ``key_rounds`` keys, summed over its rounds."""
    return EDGE_BYTES * edge_rounds + KEY_BYTES * key_rounds


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def hbm_roofline_pct(run) -> float | None:
    """The pull-SpMV bytes of one query of the window over the device
    time of the naive fixpoint in the traced query, as a percentage of
    the chip's peak HBM bandwidth.  None without a trace, without the
    counters or without the fixpoint in the trace; only then is the
    device looked up."""
    if run.trace is None or not run.completed:
        return None
    edges = run.counters.get("recursion.edge_rounds")
    keys = run.counters.get("recursion.key_rounds")
    if edges is None or keys is None:
        return None
    ms = module_ms_per_unit(run, NAIVE_DEVICE)
    if not ms:
        return None
    per_query = pull_spmv_bytes(edges, keys) / run.completed
    peak = registry.peaks(device_kind())["hbm_bytes_per_s"]
    return 100.0 * per_query / (ms / 1e3) / peak
