"""HLO static analyzer: FLOP exactness, loop multipliers, collectives."""

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import Roofline


@given(L=st.integers(2, 12), B=st.sampled_from([8, 32]),
       D=st.sampled_from([64, 128]))
@settings(max_examples=12, deadline=None)
def test_scan_dot_flops_exact(L, B, D):
    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()
    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((B, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
    c = analyze_hlo(comp.as_text())
    assert abs(c.flops - 2 * B * D * D * L) / (2 * B * D * D * L) < 1e-6


def test_grad_flops_counts_both_passes():
    L, B, D = 5, 16, 64
    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()
    comp = jax.jit(jax.grad(f)).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32)).compile()
    c = analyze_hlo(comp.as_text())
    np.testing.assert_allclose(c.flops, 6 * B * D * D * L, rtol=1e-6)


def test_nested_scan_multipliers():
    M, L, B, D = 3, 4, 8, 32
    def f(x, ws):
        def outer(x, _):
            def inner(x, w):
                return x @ w, None
            x, _ = jax.lax.scan(inner, x, ws)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=M)
        return x.sum()
    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((B, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
    c = analyze_hlo(comp.as_text())
    np.testing.assert_allclose(c.flops, 2 * B * D * D * L * M, rtol=1e-6)


def test_collectives_and_payloads():
    import os
    # collective payload parsing needs >1 partition: synthesize HLO text
    hlo = """
ENTRY %main (p0: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %ar = f32[128,64]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%sum
  ROOT %ag = f32[128,64]{1,0} all-gather(%ar), dimensions={0}
}
"""
    c = analyze_hlo(hlo)
    assert c.coll["all-reduce"] == 128 * 64 * 4
    assert c.coll["all-gather"] == 128 * 64 * 4


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops=197e12, hbm_bytes=819e9 * 2, coll_bytes=50e9,
                 coll_by_kind={}, chips=4, model_flops=4 * 197e12 * 0.5)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 1.0) < 1e-9
    assert r.bottleneck == "memory"
    assert abs(r.mfu_bound - 0.25) < 1e-9     # useful 0.5 / slowdown 2
    d = r.to_dict()
    assert d["bottleneck"] == "memory"
