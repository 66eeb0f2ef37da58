"""Vectorized quorum math vs brute-force oracle + Theorem 1 properties."""

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import quorum as Q
from repro.core import weights as W
from repro.core.quorum import quorum_commit, quorums_intersect


def brute_force_commit(arrivals, weights, threshold):
    """O(n^2) reference: walk votes in time order, accumulate weight."""
    order = np.argsort(arrivals)
    acc = 0.0
    for k, i in enumerate(order):
        if not np.isfinite(arrivals[i]):
            break
        acc += weights[i]
        if acc > threshold:                  # strict crossing (Thm 1)
            return True, arrivals[i], k + 1, acc
    return False, np.inf, 0, 0.0


@given(st.data())
@settings(max_examples=100, deadline=None)
def test_quorum_commit_matches_brute_force(data):
    n = data.draw(st.integers(2, 12))
    ops = data.draw(st.integers(1, 6))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    arrivals = rng.uniform(0, 10, size=(ops, n))
    # knock out a random subset of votes
    mask = rng.random((ops, n)) < 0.3
    arrivals = np.where(mask, np.inf, arrivals)
    weights = rng.uniform(0.1, 8.0, size=(ops, n))

    res = quorum_commit(jnp.asarray(arrivals), jnp.asarray(weights))
    thresh = weights.sum(-1) / 2.0
    for i in range(ops):
        ok, t, k, acc = brute_force_commit(arrivals[i], weights[i], thresh[i])
        assert bool(res.committed[i]) == ok
        if ok:
            assert abs(float(res.commit_time[i]) - t) < 1e-5
            assert int(res.quorum_size[i]) == k
            assert abs(float(res.weight_sum[i]) - acc) < 1e-4
            # member mask: exactly the k earliest arrivals
            members = np.asarray(res.members[i])
            assert members.sum() == k
            assert weights[i][members].sum() >= thresh[i] - 1e-5


@given(st.integers(0, 2**31))
@settings(max_examples=60, deadline=None)
def test_theorem1_fast_path_quorums_intersect(seed):
    """Any two committing quorums over the same weight vector intersect."""
    rng = np.random.default_rng(seed)
    n = rng.integers(3, 12)
    r = rng.uniform(1.0, 2.0)
    w = np.asarray(W.geometric_weights(int(n), float(r)))
    # two independent operations with independent vote arrival orders
    a1 = rng.permutation(np.arange(1.0, n + 1))
    a2 = rng.permutation(np.arange(1.0, n + 1))
    res = quorum_commit(jnp.asarray(np.stack([a1, a2])),
                        jnp.asarray(np.stack([w, w])))
    assert bool(res.committed[0]) and bool(res.committed[1])
    assert bool(quorums_intersect(res.members[0], res.members[1]))


def test_no_commit_when_too_many_failures():
    w = jnp.asarray(W.geometric_weights(5, 1.4))
    # only the two lightest replicas vote: weight 1.4+1.0 < T=5.37
    arrivals = jnp.array([jnp.inf, jnp.inf, jnp.inf, 1.0, 2.0])
    res = quorum_commit(arrivals, w)
    assert not bool(res.committed[0])
    assert not np.isfinite(float(res.commit_time[0]))


def test_commit_with_top_heavy_quorum():
    w = jnp.asarray(W.geometric_weights(5, 1.9))   # steep: top-2 suffice
    arrivals = jnp.array([0.5, 1.0, jnp.inf, jnp.inf, jnp.inf])
    res = quorum_commit(arrivals, w)
    assert bool(res.committed[0])
    assert int(res.quorum_size[0]) == 2
    assert float(res.commit_time[0]) == 1.0


# ---------------------------------------------------------------------------
# One rule, every form: the host prefix rule, the jnp oracle, the Pallas
# kernel, cabinet_size, the lease k_max, GradQuorum and CheckpointConsensus
# agree on the quorum size of a geometric base under an arrival order.
# ---------------------------------------------------------------------------

RULE_CASES = [
    # uniform weights, even n: n/2 votes reach sum/2 exactly and must not
    # commit, so the quorum is the count majority
    pytest.param(4, 1.0, None, id="uniform-4"),
    pytest.param(6, 1.0, None, id="uniform-6"),
    pytest.param(8, 1.0, None, id="uniform-8"),
    # paper Table 1: ObjA..ObjD
    pytest.param(7, 1.40, None, id="table1-ObjA"),
    pytest.param(7, 1.38, None, id="table1-ObjB"),
    pytest.param(7, 1.25, None, id="table1-ObjC"),
    pytest.param(7, 1.10, None, id="table1-ObjD"),
    # a 64-replica geometric fleet
    pytest.param(64, W.solve_steepness(64, 2), None, id="fleet64-t2"),
    pytest.param(64, W.solve_steepness(64, 10), None, id="fleet64-t10"),
    # random arrival orders
    pytest.param(6, 1.0, 5, id="random-uniform-6"),
    pytest.param(7, 1.40, 1, id="random-table1-ObjA"),
    pytest.param(7, 1.10, 2, id="random-table1-ObjD"),
    pytest.param(9, W.solve_steepness(9, 2), 3, id="random-9-t2"),
    pytest.param(64, W.solve_steepness(64, 2), 4, id="random-fleet64-t2"),
]


@pytest.mark.parametrize("n,r,seed", RULE_CASES)
def test_every_form_of_the_rule_agrees(n, r, seed):
    from repro.coord import CheckpointConsensus, GradQuorum
    from repro.core.cabinet import CabinetReplica
    from repro.core.leases import LeaseConfig
    from repro.core.simulator import Simulation
    from repro.kernels.quorum_commit import quorum_commit_pallas

    base = W.geometric_weights_np(n, r)
    order = (np.arange(n) if seed is None
             else np.random.default_rng(seed).permutation(n))
    arrivals = np.empty(n, np.float32)
    arrivals[order] = np.arange(1, n + 1)
    want = Q.quorum_size(base, order)
    assert 1 <= want <= n
    if r == 1.0:
        assert want == Q.count_majority(n)

    res = quorum_commit(jnp.asarray(arrivals), jnp.asarray(base))
    assert bool(res.committed[0]) and int(res.quorum_size[0]) == want
    _, qs, cm, _ = quorum_commit_pallas(jnp.asarray(arrivals[None]),
                                        jnp.asarray(base[None]),
                                        interpret=True)
    assert bool(cm[0]) and int(qs[0]) == want
    assert int(W.cabinet_size(jnp.asarray(base[order]))) == want

    # equal latency EMAs rank the workers by id: worker i carries base[i]
    gq = GradQuorum(n)
    gq.state.steepness = r
    assert int(gq.commit_mask(arrivals).sum()) == want

    cc = CheckpointConsensus(n, steepness=r)
    cc.propose(0, [])
    acks = next(j + 1 for j, h in enumerate(order) if cc.ack(0, int(h)))
    assert acks == want

    # the lease's k_max: the most top-ranked replicas that cannot cross
    rep = CabinetReplica(0, Simulation(n), t_fail=1, steepness=r,
                         group_cap=1, leases=LeaseConfig())
    k_max = n - 1 - rep.lease_mgr._ll_need
    assert k_max + 1 == Q.quorum_size(base)


@pytest.mark.parametrize("n,r", [(5, 1.4), (7, 1.10), (9, 1.9),
                                 (64, 1.5), (200, 2.0)])
def test_jax_weight_forms_equal_the_numpy_forms(n, r):
    np.testing.assert_array_equal(np.asarray(W.geometric_weights(n, r)),
                                  W.geometric_weights_np(n, r))
    rng = np.random.default_rng(n)
    emas = rng.permutation(3 * n).reshape(3, n) * 1e-3 + 1e-3
    table = W.ObjectWeightTable(n, r, emas[0].copy())
    for obj in (1, 2):
        table.ema[obj] = emas[obj].tolist()
    tracker = W.WeightTracker(latency_ema=jnp.asarray(emas, jnp.float32))
    got = np.asarray(tracker.weights(r))
    for obj in (1, 2):
        np.testing.assert_array_equal(got[obj], table.weights_for(obj))
    np.testing.assert_array_equal(
        np.asarray(W.node_weights_from_latency(jnp.asarray(emas[0]), r)),
        table.node_weights())
