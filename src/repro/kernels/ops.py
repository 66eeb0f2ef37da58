"""Jit'd public wrapper: pick the compiled Pallas kernel on TPU, the jnp
reference elsewhere. ``force_pallas`` runs the kernel off the TPU, in
interpret mode unless told otherwise (how the CPU test suite checks it)."""

from __future__ import annotations

import jax

from repro.kernels import quorum_commit as _qc
from repro.kernels import ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def quorum_commit(arrivals, weights, *, force_pallas: bool = False,
                  interpret: bool | None = None):
    if on_tpu() or force_pallas:
        return _qc.quorum_commit_pallas(
            arrivals, weights,
            interpret=(not on_tpu()) if interpret is None else interpret)
    return ref.quorum_commit_ref(arrivals, weights)
