"""Frontier-fill Pallas package: the morsel-chunked fill stage of the
zero-sync count-then-fill extension pipeline as one kernel launch per
chunk (offset inversion -> seed gather -> branch-free lockstep probes),
bit-identical to the plain-jnp reference in :mod:`.ref`, which is the
engine's default fill.  Interpret mode only: the TPU compiler refuses the
kernel's in-kernel 1-D gathers ("Only 2D gather is supported")."""
from repro.kernels.frontier_fill.ops import CONTRACT, fill_chunk  # noqa: F401
