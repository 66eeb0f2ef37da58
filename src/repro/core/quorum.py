"""The weighted-quorum rule (paper §3.1, Theorem 1), in one place.

A set of votes is a quorum when its weight STRICTLY exceeds ``T = sum(w)/2``
of the whole weight vector, voters or not (the threshold is a property of
the object, not of who answers). Strict: at exactly ``sum/2`` two disjoint
sets could both commit under ``>=`` (uniform weights, even n), and
Theorem 1's intersection argument needs ``>``.

Host forms, which replicas and ``repro.coord`` call:
:func:`consensus_threshold`, :func:`crosses`, :func:`quorum_size` (the
smallest crossing prefix in a given order) and :func:`count_majority`. The
hot paths (``fastpath``, ``slowpath``, the isolation scan in
``protocol_base``) keep their one comparison inline against the threshold
``ObjectWeightTable`` hands them: a function call per vote would cost the
leader's saturated loop.

:func:`quorum_commit` is the jnp form, batched over operations, and the
oracle for the Pallas kernel in ``repro.kernels.quorum_commit``: sort each
operation's votes by arrival, prefix-sum their weights, take the first
strict crossing -> commit time, quorum size. Non-voting replicas (crashed,
timed out, or replying CONFLICT) arrive at ``+inf`` and never enter a quorum.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.inf


def consensus_threshold(weights):
    """``T = sum(w)/2`` over the last axis (paper §3.1). Takes numpy or jnp
    arrays and returns the same kind; halving is exact, so a float32 vector
    gives its float32 sum over two."""
    return weights.sum(axis=-1) / 2.0


def crosses(weight, threshold):
    """Theorem 1's strict crossing: ``weight > threshold``, never ``>=``.
    Elementwise on arrays."""
    return weight > threshold


def quorum_size(weights: np.ndarray,
                order: Optional[Sequence[int]] = None) -> int:
    """Smallest k such that the first k replicas of ``order`` (default: in
    index order) carry weight strictly over ``consensus_threshold(weights)``;
    0 if they never do. ``order`` may leave out replicas that do not vote.
    The prefix sum runs in float64 from the first replica of ``order`` on,
    as a replica accumulates its votes."""
    w = np.asarray(weights)
    csum = np.cumsum(w if order is None else w[np.asarray(order, np.int64)],
                     dtype=np.float64)
    hit = crosses(csum, float(consensus_threshold(w)))
    return int(np.argmax(hit)) + 1 if hit.size and hit[-1] else 0


def count_majority(n: int) -> int:
    """Votes a count quorum of ``n`` needs: the rule over unit weights,
    ``n // 2 + 1``."""
    return n // 2 + 1


class QuorumResult(NamedTuple):
    """Result of quorum formation for a batch of operations.

    All fields have shape ``(ops,)`` except ``members`` (``(ops, n)``).
    """

    committed: jax.Array     # bool  — threshold was crossed by voting replicas
    commit_time: jax.Array   # float — time of the crossing vote (inf if not)
    quorum_size: jax.Array   # int32 — number of votes in the quorum
    weight_sum: jax.Array    # float — accumulated weight at commit
    members: jax.Array       # bool (ops, n) — replicas inside the quorum


def quorum_commit(arrivals: jax.Array, weights: jax.Array,
                  threshold: jax.Array | None = None) -> QuorumResult:
    """Earliest weighted-quorum crossing per operation.

    Args:
      arrivals: (ops, n) vote arrival times; ``inf`` = no vote.
      weights:  (ops, n) per-replica vote weight for this op's object.
      threshold: (ops,) consensus threshold; defaults to
        :func:`consensus_threshold` of ``weights``, non-voters included.

    Returns a :class:`QuorumResult`.
    """
    if arrivals.ndim == 1:
        arrivals = arrivals[None]
        weights = weights[None]
    if threshold is None:
        threshold = consensus_threshold(weights)

    order = jnp.argsort(arrivals, axis=-1)               # earliest vote first
    t_sorted = jnp.take_along_axis(arrivals, order, axis=-1)
    w_sorted = jnp.take_along_axis(weights, order, axis=-1)
    # votes that never arrive contribute no weight
    w_sorted = jnp.where(jnp.isfinite(t_sorted), w_sorted, 0.0)
    csum = jnp.cumsum(w_sorted, axis=-1)

    crossed = crosses(csum, threshold[..., None])        # (ops, n) monotone
    committed = jnp.any(crossed & jnp.isfinite(t_sorted), axis=-1)
    # first crossing index; argmax returns 0 when nothing crossed, so mask
    k = jnp.argmax(crossed, axis=-1)
    commit_time = jnp.where(
        committed, jnp.take_along_axis(t_sorted, k[..., None], axis=-1)[..., 0],
        INF)
    size = jnp.where(committed, k + 1, 0).astype(jnp.int32)
    weight_sum = jnp.where(
        committed, jnp.take_along_axis(csum, k[..., None], axis=-1)[..., 0],
        0.0)

    # membership: replicas whose sorted position <= k and which actually voted
    pos_in_sorted = jnp.argsort(order, axis=-1)          # position of replica i
    members = (pos_in_sorted <= k[..., None]) & committed[..., None]
    members = members & jnp.isfinite(arrivals)
    return QuorumResult(committed, commit_time, size, weight_sum, members)


def quorums_intersect(members_a: jax.Array, members_b: jax.Array) -> jax.Array:
    """Theorem 1 checker: do two quorum membership masks intersect?

    ``members_*``: (..., n) bool. Returns (...,) bool.
    """
    return jnp.any(members_a & members_b, axis=-1)


def min_quorum_latency(latencies: jax.Array, weights: jax.Array) -> jax.Array:
    """Lower bound on fast-path commit latency for an object.

    Given one-way replica latencies (coordinator -> replica -> coordinator
    counted as ``latencies``) and the object weight vector, the best possible
    commit time is reached by waiting for replicas in latency order until the
    threshold is crossed. Shape: latencies/weights (..., n) -> (...,).
    """
    res = quorum_commit(latencies, weights)
    return res.commit_time
