"""Pallas TPU kernels of the LM stack: flash attention and the SSD scan.

Each kernel ships three layers (see tests/test_kernels.py for the
interpret-mode allclose sweeps):
  * <name>.py — pl.pallas_call with explicit BlockSpec VMEM tiling
  * ops.py    — jit'd wrappers (TPU -> kernel, elsewhere -> oracle)
  * ref.py    — pure-jnp oracles (the exact code the models run on CPU)
"""
