"""Coordination layer: grad quorum invariants, membership, checkpoint
consensus, and the shard_map masked reduction on a real multi-device mesh
(subprocess with 8 host devices)."""

import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.coord import CheckpointConsensus, GradQuorum, Membership


# ---------------------------------------------------------------------------
# GradQuorum
# ---------------------------------------------------------------------------

@given(n=st.integers(4, 64), seed=st.integers(0, 2**31))
@settings(max_examples=40, deadline=None)
def test_commit_mask_is_strict_weight_majority(n, seed):
    gq = GradQuorum(n)
    rng = np.random.default_rng(seed)
    gq.observe(rng.uniform(0.5, 3.0, n))
    mask = gq.commit_mask()
    w = gq.state.weights()
    assert w[mask].sum() > w.sum() / 2            # Thm-1 semantics
    assert mask.sum() >= 2                        # never a single worker


def test_quorum_prefers_fast_workers():
    gq = GradQuorum(8)
    lat = np.ones(8)
    lat[7] = 10.0                                 # one hard straggler
    for _ in range(10):
        gq.observe(lat)
    mask = gq.commit_mask()
    assert not mask[7], "straggler must not gate the commit"
    assert mask.sum() < 8


def test_row_weights_renormalize():
    gq = GradQuorum(4)
    mask = np.array([True, True, False, True])
    rw = gq.row_weights(mask)
    np.testing.assert_allclose(rw.sum(), 4.0)     # unbiased mean
    assert rw[2] == 0.0


def test_scale_batch_mask_rows():
    gq = GradQuorum(4)
    batch = {"mask": np.ones((8, 3), np.float32)}
    out = gq.scale_batch_mask(batch, np.array([True, False, True, True]))
    assert out["mask"][0, 0] > 1.0                # renormalized up
    assert out["mask"][2, 0] == 0.0 and out["mask"][3, 0] == 0.0


def test_straggler_speedup_positive():
    gq = GradQuorum(32, t_fail=4)
    lat = np.ones(32)
    lat[-3:] = 4.0
    for _ in range(10):
        gq.observe(lat)
    stats = gq.expected_step_time(lat, trials=400)
    assert stats["speedup"] > 1.5


def test_quorum_allreduce_on_mesh():
    """shard_map masked psum on 8 host devices (subprocess isolates the
    XLA_FLAGS device-count override from the rest of the suite)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P, AxisType
        from repro.coord.grad_quorum import quorum_allreduce
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(AxisType.Auto,))
        g = jnp.arange(8.0)[:, None] * jnp.ones((8, 4))
        mask = jnp.array([1., 1., 1., 1., 1., 1., 0., 0.])
        f = jax.shard_map(
            lambda g: quorum_allreduce({"g": g}, mask, "data"),
            mesh=mesh, in_specs=P("data"), out_specs={"g": P("data")})
        out = f(g)["g"]
        # committed mean over workers 0..5 = 2.5
        print(json.dumps({"val": float(np.asarray(out)[0, 0])}))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-2000:]
    val = json.loads(r.stdout.strip().splitlines()[-1])["val"]
    assert abs(val - 2.5) < 1e-5


# ---------------------------------------------------------------------------
# Membership
# ---------------------------------------------------------------------------

def test_membership_elastic_epochs():
    t = [0.0]
    m = Membership(8, hb_timeout=10.0, clock=lambda: t[0])
    assert m.view().epoch == 0
    assert m.leader() == 0
    t[0] = 5.0
    for h in range(8):
        if h != 3:
            m.heartbeat(h)
    t[0] = 12.0
    v = m.view()              # host 3 expired (last hb at t=0), rest fresh
    assert 3 not in v.alive
    assert v.epoch == 1
    assert v.mesh_proposal["data"] == 7
    t[0] = 13.0
    for h in range(8):
        m.heartbeat(h)        # 3 rejoins
    v = m.view()
    assert 3 in v.alive and v.epoch == 2


def test_membership_leader_failover():
    t = [0.0]
    m = Membership(4, hb_timeout=5.0, clock=lambda: t[0])
    t[0] = 10.0
    for h in (1, 2, 3):
        m.heartbeat(h)
    assert m.leader() == 1                        # host 0 dead -> next rank


# ---------------------------------------------------------------------------
# CheckpointConsensus
# ---------------------------------------------------------------------------

def test_ckpt_commit_requires_weight_majority(tmp_path):
    cc = CheckpointConsensus(5, t_fail=2)
    cc.propose(100, ["a", "b"])
    assert not cc.ack(100, 4)                     # lightest host alone: no
    committed = False
    for h in (0, 1, 2):
        committed = cc.ack(100, h) or committed
    assert committed
    path = cc.write_manifest(tmp_path, 100)
    m = CheckpointConsensus.latest_committed(tmp_path)
    assert m is not None and m["step"] == 100
    assert path.exists()


def test_ckpt_latest_ignores_uncommitted(tmp_path):
    cc = CheckpointConsensus(5)
    cc.propose(1, ["x"])
    for h in range(5):
        cc.ack(1, h)
    cc.write_manifest(tmp_path, 1)
    cc.propose(2, ["y"])
    cc.ack(2, 4)                                  # insufficient weight
    cc.write_manifest(tmp_path, 2)                # committed=False inside
    m = CheckpointConsensus.latest_committed(tmp_path)
    assert m["step"] == 1                         # torn step-2 ignored
