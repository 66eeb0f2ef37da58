"""Jit'd public wrappers: pick the compiled Pallas kernel on TPU, the jnp
reference elsewhere. ``force_pallas`` runs the kernel off the TPU, in
interpret mode unless told otherwise (how the CPU test suite checks it);
models call the ref path via cfg.use_pallas == False."""

from __future__ import annotations

from repro.kernels.ops import on_tpu
from repro.models.kernels import flash_attention as _fa
from repro.models.kernels import ref
from repro.models.kernels import ssd_scan as _ssd


def flash_attention(q, k, v, *, causal: bool = True,
                    force_pallas: bool = False,
                    interpret: bool | None = None):
    if on_tpu() or force_pallas:
        return _fa.flash_attention(
            q, k, v, causal=causal,
            interpret=(not on_tpu()) if interpret is None else interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal)


def ssd(x, dt, A, Bm, Cm, D, chunk, initial_state=None, *,
        force_pallas: bool = False, interpret: bool | None = None):
    if on_tpu() or force_pallas:
        return _ssd.ssd_chunked_pallas(
            x, dt, A, Bm, Cm, D, chunk, initial_state=initial_state,
            interpret=(not on_tpu()) if interpret is None else interpret)
    return ref.ssd_ref(x, dt, A, Bm, Cm, D, chunk,
                       initial_state=initial_state)
