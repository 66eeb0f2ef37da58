"""Pure-jnp oracles for the LM kernels (asserted allclose in tests).

These re-export the canonical implementations from the model code so the
kernels validate against the exact code the models run on CPU.
"""

from __future__ import annotations

from repro.models.layers import attend_chunked, attend_full
from repro.models.mamba2 import ssd_chunked


def flash_attention_ref(q, k, v, *, causal: bool = True):
    if q.shape[1] >= 512:
        return attend_chunked(q, k, v, causal=causal)
    return attend_full(q, k, v, causal=causal)


def ssd_ref(x, dt, A, Bm, Cm, D, chunk, initial_state=None):
    return ssd_chunked(x, dt, A, Bm, Cm, D, chunk,
                       initial_state=initial_state)
