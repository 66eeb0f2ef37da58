"""Pallas kernels vs pure-jnp oracles in interpret mode (CPU), with
hypothesis sweeps over shapes/dtypes."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.quorum_commit import quorum_commit_pallas
from repro.models.kernels import ref as lm_ref
from repro.models.kernels.flash_attention import flash_attention
from repro.models.kernels.ssd_scan import ssd_chunked_pallas


# ---------------------------------------------------------------------------
# quorum_commit
# ---------------------------------------------------------------------------

@given(st.data())
@settings(max_examples=25, deadline=None)
def test_quorum_commit_matches_ref(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    ops = data.draw(st.integers(1, 200))
    n = data.draw(st.integers(2, 33))
    arrivals = rng.uniform(0, 10, (ops, n)).astype(np.float32)
    mask = rng.random((ops, n)) < 0.3
    arrivals = np.where(mask, np.inf, arrivals).astype(np.float32)
    weights = rng.uniform(0.1, 9.0, (ops, n)).astype(np.float32)

    ct, qs, cm, ws = quorum_commit_pallas(jnp.asarray(arrivals),
                                          jnp.asarray(weights),
                                          interpret=True)
    rct, rqs, rcm, rws = ref.quorum_commit_ref(arrivals, weights)
    np.testing.assert_array_equal(np.asarray(cm), np.asarray(rcm))
    ok = np.asarray(rcm)
    np.testing.assert_allclose(np.asarray(ct)[ok], np.asarray(rct)[ok],
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(qs)[ok], np.asarray(rqs)[ok])
    np.testing.assert_allclose(np.asarray(ws)[ok], np.asarray(rws)[ok],
                               rtol=1e-4)


def test_quorum_commit_ties_follow_replica_order():
    """Votes arriving at the same time count in replica order, as a
    stable sort orders them: the quorum size follows from that order."""
    arrivals = np.array([[1.0, 1.0, 1.0, 2.0, np.inf],
                         [2.0, 1.0, 1.0, 1.0, 1.0],
                         [3.0, 3.0, 3.0, 3.0, 3.0]], np.float32)
    weights = np.array([[1.0, 1.0, 5.0, 1.0, 1.0],
                        [9.0, 1.0, 1.0, 1.0, 1.0],
                        [1.0, 1.0, 1.0, 1.0, 1.0]], np.float32)
    ct, qs, cm, ws = quorum_commit_pallas(jnp.asarray(arrivals),
                                          jnp.asarray(weights),
                                          interpret=True)
    rct, rqs, rcm, rws = ref.quorum_commit_ref(arrivals, weights)
    np.testing.assert_array_equal(np.asarray(cm), [True, True, True])
    np.testing.assert_array_equal(np.asarray(qs), [3, 5, 3])
    for got, want in ((cm, rcm), (ct, rct), (qs, rqs), (ws, rws)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quorum_commit_geometric_weights_top2():
    from repro.core import weights as W
    w = np.tile(np.asarray(W.geometric_weights(7, 1.9)), (4, 1))
    arr = np.tile(np.arange(1.0, 8.0, dtype=np.float32), (4, 1))
    ct, qs, cm, _ = quorum_commit_pallas(jnp.asarray(arr), jnp.asarray(w),
                                         interpret=True)
    assert bool(cm.all())
    np.testing.assert_array_equal(np.asarray(qs), 2)   # steep: top-2 commit
    np.testing.assert_allclose(np.asarray(ct), 2.0)


def test_quorum_kernel_ops_load_no_model_code():
    """The protocol's one device call must not drag in the LM stack."""
    code = ("import sys, repro.kernels.ops; "
            "print(sorted(m for m in sys.modules "
            "if m.startswith('repro.models')))")
    src = os.path.dirname(os.path.dirname(os.path.dirname(ref.__file__)))
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu",
                              "PYTHONPATH": src})
    assert out.stdout.strip() == "[]", out.stdout


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,hd,bq,bk", [
    (1, 256, 4, 2, 64, 128, 128),
    (2, 256, 4, 4, 32, 64, 128),
    (1, 512, 8, 2, 64, 128, 256),
])
def test_flash_attention_matches_ref(dtype, B, S, H, KV, hd, bq, bk):
    rng = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, H, hd), dtype)
    k = jax.random.normal(kk, (B, S, KV, hd), dtype)
    v = jax.random.normal(kv_, (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    want = lm_ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_non_causal():
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 256, 2, 64))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    want = lm_ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@given(st.data())
@settings(max_examples=8, deadline=None)
def test_flash_attention_shape_sweep(data):
    S = data.draw(st.sampled_from([128, 256, 384]))
    H = data.draw(st.sampled_from([2, 4]))
    KV = data.draw(st.sampled_from([1, 2]))
    hd = data.draw(st.sampled_from([32, 64]))
    bq = data.draw(st.sampled_from([64, 128]))
    seed = data.draw(st.integers(0, 2**31))
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(rng, (1, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, S, KV, hd))
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=128,
                          interpret=True)
    want = lm_ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,nh,hp,N,Q", [
    (2, 256, 2, 64, 16, 128),
    (1, 512, 4, 32, 64, 128),
    (1, 128, 1, 64, 128, 64),
])
def test_ssd_matches_ref(B, S, nh, hp, N, Q):
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    D = jnp.ones((nh,))
    y, st_ = ssd_chunked_pallas(x, dt, A, Bm, Cm, D, Q, interpret=True)
    ry, rst = lm_ref.ssd_ref(x, dt, A, Bm, Cm, D, Q)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(rst),
                               atol=2e-3, rtol=2e-3)


def test_ssd_equals_naive_sequential_recurrence():
    """The chunked algorithm must match the O(S) sequential SSM exactly."""
    B, S, nh, hp, N, Q = 1, 64, 2, 8, 4, 16
    rng = jax.random.PRNGKey(3)
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    D = jnp.zeros((nh,))

    def naive():
        s = np.zeros((B, nh, hp, N), np.float32)
        ys = []
        for t in range(S):
            dec = np.exp(np.asarray(dt[:, t] * A[None, :]))  # (B,nh)
            contrib = np.einsum("bn,bh,bhp->bhpn", np.asarray(Bm[:, t]),
                                np.asarray(dt[:, t]), np.asarray(x[:, t]))
            s = s * dec[..., None, None] + contrib
            ys.append(np.einsum("bn,bhpn->bhp", np.asarray(Cm[:, t]), s))
        return np.stack(ys, 1), s

    ny, ns = naive()
    y, st_ = ssd_chunked_pallas(x, dt, A, Bm, Cm, D, Q, interpret=True)
    np.testing.assert_allclose(np.asarray(y), ny, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st_), ns, atol=2e-3, rtol=2e-3)


def test_ssd_initial_state_threading():
    """Splitting a sequence in two with state carry == one full pass."""
    B, S, nh, hp, N, Q = 1, 128, 1, 16, 8, 32
    rng = jax.random.PRNGKey(7)
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    D = jnp.zeros((nh,))
    y_full, s_full = lm_ref.ssd_ref(x, dt, A, Bm, Cm, D, Q)
    h = S // 2
    y1, s1 = lm_ref.ssd_ref(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], D, Q)
    y2, s2 = lm_ref.ssd_ref(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:], D, Q,
                         initial_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=2e-3, rtol=2e-3)
