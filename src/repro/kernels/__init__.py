"""Pallas TPU kernel for the protocol's compute hot spot: the batched
weighted-quorum commit scan. It imports nothing of the LM stack (whose
kernels live in ``repro.models.kernels``).

The kernel ships three layers (see tests/test_kernels.py for the
interpret-mode sweeps):
  * quorum_commit.py — pl.pallas_call with explicit BlockSpec VMEM tiling
  * ops.py    — jit'd wrapper (TPU -> kernel, elsewhere -> oracle)
  * ref.py    — the pure-jnp oracle (repro.core.quorum)
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
