"""Compile the engine's device programs for a TPU v5e chip that is
described, not attached.

The TPU compiler is installed with JAX, so these tests lower and compile
for one chip of a ``v5e:2x2`` topology here, on a CPU-only host: what
Mosaic or XLA would refuse on the chip (a block that does not match the
tiling, a gather Mosaic cannot lower, too much VMEM) fails here first.
Nothing runs, so nothing here says anything about results or times.

Covered: the three pair kernels the engine dispatches at real widths,
the triangle and 4-clique fused bag programs with the default fill, and
the naive and seminaive device fixpoints.  Programs are recorded from a
small CPU run through the engine's audit hooks and compiled from their
shapes.  Everything that touches the topology happens inside a fixture,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import random_undirected_graph
from repro.core import recursion
from repro.core import workload as W
from repro.core.backend import _bag_program
from repro.core.engine import Engine
from repro.kernels.bitset_intersect.kernel import bitset_and_popcount_kernel
from repro.kernels.materialize.ops import _gather_expand_rank
from repro.kernels.uint_intersect.kernel import uint_intersect_kernel

# real widths: one launch over 2^16 pairs, uint sets up to the engine's
# UINT_KERNEL_MAX_LEN, bitset blocks of the engine's 256 bits
PAIRS = 1 << 16
UINT_LEN = 256
BLOCK_BITS = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def recorded():
    """Audit records of the device programs four paper queries dispatch
    on a small graph, keyed by query name."""
    src, dst, _ = random_undirected_graph(22, 0.3, 3)
    eng = Engine(backend="device")
    eng.load_edges("Edge", src, dst)
    for a in W.ALIASES:
        eng.alias(a, "Edge")
    out = {}
    prev = recursion.AUDIT_LOG
    try:
        for name, q in (("triangle", W.TRIANGLE_COUNT),
                        ("4clique", W.FOUR_CLIQUE),
                        ("pagerank", W.pagerank_program(iters=5)),
                        ("sssp", W.sssp_program(int(src[0])))):
            eng.backend.audit_log = []
            recursion.AUDIT_LOG = []
            eng.query(q)
            out[name] = eng.backend.audit_log + recursion.AUDIT_LOG
    finally:
        recursion.AUDIT_LOG = prev
        eng.backend.audit_log = None
    return out


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_kernel(name, one_chip):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if name == "bitset_and_popcount":
        w = s((PAIRS, 128), jnp.uint32)
        return bitset_and_popcount_kernel.lower(w, w).compile()
    if name == "uint_intersect":
        t = s((UINT_LEN, PAIRS), jnp.int32)
        return uint_intersect_kernel.lower(t, t).compile()
    # materialize: the whole dispatched program (block gather, bit
    # expansion, the Pallas AND + triangular-matmul ranks)
    words = s((PAIRS, BLOCK_BITS // 32), jnp.uint32)
    pos = s((PAIRS,), jnp.int32)
    tri = s((BLOCK_BITS, BLOCK_BITS), jnp.float32)
    return _gather_expand_rank.lower(words, pos, pos, tri,
                                     block_bits=BLOCK_BITS,
                                     interpret=False).compile()


@pytest.mark.parametrize("name", ["bitset_and_popcount", "uint_intersect",
                                  "materialize"])
def test_pair_kernel_compiles_for_v5e(one_chip, name):
    assert "tpu_custom_call" in _compile_kernel(name, one_chip).as_text()


@pytest.mark.parametrize("query", ["triangle", "4clique"])
def test_fused_bag_program_compiles_for_v5e(one_chip, recorded, query):
    bags = [r for r in recorded[query] if r[0] == "bag"]
    assert bags, f"{query} dispatched no fused bag program"
    for _kind, _name, prog, arrays, cursors, ann, fill_mode, _i in bags:
        assert fill_mode == "jnp"       # the default fill
        compiled = _bag_program.lower(
            _on(one_chip, arrays), _on(one_chip, cursors),
            _on(one_chip, ann), prog=prog, fill_mode=fill_mode,
            fill_interpret=False).compile()
        # the jnp fill is plain XLA: no kernel is expected in the bag
        assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("kind,query", [("naive", "pagerank"),
                                        ("seminaive", "sssp")])
def test_device_fixpoint_compiles_for_v5e(one_chip, recorded, kind, query):
    recs = [r for r in recorded[query] if r[0] == kind]
    assert len(recs) == 1, [r[0] for r in recorded[query]]
    rec = recs[0]
    if kind == "seminaive":
        _, _, sr, expr, max_rounds, n, args = rec
        compiled = recursion._seminaive_device.lower(
            sr, expr, max_rounds, n, *_on(one_chip, args)).compile()
    else:
        _, _, sr, expr, iters, tol, max_rounds, k, kinds, args = rec
        compiled = recursion._naive_device.lower(
            sr, expr, iters, tol, max_rounds, k, kinds,
            *_on(one_chip, args)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
