"""Jit'd public wrappers: pick the compiled Pallas kernel on TPU, the jnp
reference elsewhere. ``force_pallas`` runs the kernel off the TPU, in
interpret mode unless told otherwise (how the CPU test suite checks it);
models call the ref path via cfg.use_pallas == False."""

from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import quorum_commit as _qc
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def quorum_commit(arrivals, weights, *, force_pallas: bool = False,
                  interpret: bool | None = None):
    if _on_tpu() or force_pallas:
        return _qc.quorum_commit_pallas(
            arrivals, weights,
            interpret=(not _on_tpu()) if interpret is None else interpret)
    return ref.quorum_commit_ref(arrivals, weights)


def flash_attention(q, k, v, *, causal: bool = True,
                    force_pallas: bool = False,
                    interpret: bool | None = None):
    if _on_tpu() or force_pallas:
        return _fa.flash_attention(
            q, k, v, causal=causal,
            interpret=(not _on_tpu()) if interpret is None else interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal)


def ssd(x, dt, A, Bm, Cm, D, chunk, initial_state=None, *,
        force_pallas: bool = False, interpret: bool | None = None):
    if _on_tpu() or force_pallas:
        return _ssd.ssd_chunked_pallas(
            x, dt, A, Bm, Cm, D, chunk, initial_state=initial_state,
            interpret=(not _on_tpu()) if interpret is None else interpret)
    return ref.ssd_ref(x, dt, A, Bm, Cm, D, chunk,
                       initial_state=initial_state)
