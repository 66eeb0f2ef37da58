"""Geometric weight assignment and weighted-quorum invariants (paper §3.1–3.2).

Everything here is pure and vectorized: weight vectors are computed for
batches of objects at once (shape ``(num_objects, n_replicas)``), because the
Object Manager re-derives weights continuously from latency statistics and a
production deployment tracks millions of objects.

Notation (paper §3.1):
  * object weight vector  W^O = [w_1^O .. w_n^O]
  * consensus threshold   T^O = sum(W^O) / 2
  * quorum                any S with sum_{i in S} w_i^O >= T^O

Geometric assignment (paper §3.2, eq. 1): replicas sorted by decreasing
efficiency get ``w_i = R^(n-1-i)`` for rank i in [0, n).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Steepness bounds from the paper (§3.2): R in [1.0, 2.0].
R_MIN = 1.0
R_MAX = 2.0


def geometric_weights(n: int, r: float, dtype=jnp.float32) -> jax.Array:
    """Weights for ``n`` replicas ordered fastest-first: w_i = r^(n-1-i).

    Returns a descending weight vector; ``w[-1] == 1.0`` always (rank n-1
    gets r^0), matching Table 1/2 of the paper.
    """
    if n < 1:
        raise ValueError(f"need at least one replica, got n={n}")
    if not (R_MIN <= r <= R_MAX):
        raise ValueError(f"steepness r={r} outside paper range [{R_MIN}, {R_MAX}]")
    exponents = jnp.arange(n - 1, -1, -1, dtype=dtype)
    if (n - 1) * np.log(max(r, 1.0 + 1e-12)) > 60.0:
        # large fleets: r^(n-1) overflows float32. Quorum math is scale-
        # invariant (threshold = sum/2), so normalize to w_max = 1
        # (descending from 1 instead of descending to 1).
        exponents = exponents - (n - 1)
    return jnp.power(jnp.asarray(r, dtype=dtype), exponents)


def geometric_weights_np(n: int, r: float,
                         dtype=np.float32) -> np.ndarray:
    """Pure-numpy twin of :func:`geometric_weights` for the event-driven
    simulator's replica constructors: the discrete-event path must stay
    free of jax *execution*, because its worker and served-replica
    processes must never start a jax backend (an accelerator belongs to
    the one process that launched them)."""
    if n < 1:
        raise ValueError(f"need at least one replica, got n={n}")
    if not (R_MIN <= r <= R_MAX):
        raise ValueError(f"steepness r={r} outside paper range [{R_MIN}, {R_MAX}]")
    exponents = np.arange(n - 1, -1, -1, dtype=np.float64)
    if (n - 1) * np.log(max(r, 1.0 + 1e-12)) > 60.0:
        exponents = exponents - (n - 1)
    return np.power(np.float64(r), exponents).astype(dtype)


def consensus_threshold(weights: jax.Array) -> jax.Array:
    """T = sum(w)/2 over the last axis (paper §3.1)."""
    return jnp.sum(weights, axis=-1) / 2.0


def cabinet_size(weights_desc: jax.Array) -> jax.Array:
    """Smallest k such that the k heaviest replicas form a quorum.

    ``weights_desc`` must be sorted descending along the last axis. The
    paper calls these k replicas the *cabinet* (top t+1 weighted replicas).
    Vectorized over leading axes.
    """
    csum = jnp.cumsum(weights_desc, axis=-1)
    thresh = consensus_threshold(weights_desc)[..., None]
    # first index where cumulative weight STRICTLY exceeds T (see
    # repro.core.quorum: >= admits disjoint quorums at exactly sum/2)
    meets = csum > thresh
    return jnp.argmax(meets, axis=-1) + 1


def check_invariant_progress(weights: jax.Array, t: int) -> jax.Array:
    """Invariant I1 (progress): sum of top t+1 weights > T.

    ``weights`` need not be sorted. Vectorized over leading axes; returns a
    boolean array.
    """
    w_sorted = jnp.sort(weights, axis=-1)[..., ::-1]
    top = jnp.sum(w_sorted[..., : t + 1], axis=-1)
    return top > consensus_threshold(weights)


def check_invariant_safety(weights: jax.Array, t: int) -> jax.Array:
    """Invariant I2 (safety): no t-subset can form a quorum.

    Under strict-crossing quorums (sum > T) a t-subset is safe iff its
    weight is <= T; the worst case is the t heaviest replicas.
    """
    if t == 0:
        return jnp.ones(weights.shape[:-1], dtype=bool)
    w_sorted = jnp.sort(weights, axis=-1)[..., ::-1]
    top_t = jnp.sum(w_sorted[..., :t], axis=-1)
    return top_t <= consensus_threshold(weights)


def max_safe_t(weights: jax.Array) -> jax.Array:
    """Largest t for which I2 holds: the heaviest t sum strictly below T.

    Equivalently ``cabinet_size - 1`` when I1 holds with equality semantics;
    computed directly from the sorted prefix sums. Vectorized.
    """
    w_sorted = jnp.sort(weights, axis=-1)[..., ::-1]
    csum = jnp.cumsum(w_sorted, axis=-1)
    thresh = consensus_threshold(weights)[..., None]
    below = csum <= thresh * (1 + 1e-7)  # size-k prefix cannot form a quorum
    return jnp.sum(below.astype(jnp.int32), axis=-1)


def solve_steepness(n: int, t: int, *, tol: float = 1e-9) -> float:
    """Find the largest steepness R such that invariants I1+I2 hold for
    failure threshold ``t`` with n replicas.

    I2 requires sum(top t) <= T = sum(all)/2, i.e.
        sum_{i<t} R^(n-1-i) <= 0.5 * sum_i R^(n-1-i).
    The LHS/total ratio is monotonically increasing in R, so bisection works.
    The paper's Table 1/2 values (e.g. n=7: t=1 -> 1.40, t=2 -> 1.38,
    t=3 -> ~1.19..1.25, t=4 -> ~1.08..1.10) come from this feasibility
    region; we return the supremum minus a safety margin.
    """
    if not (1 <= t <= (n - 1) // 2):
        raise ValueError(f"t={t} outside 1..floor((n-1)/2) for n={n}")

    def top_t_fraction(r: float) -> float:
        # normalized exponents: scale-invariant and overflow-safe
        w = np.power(r, np.arange(0, -n, -1, dtype=np.float64))
        return float(w[:t].sum() / w.sum())

    # margin keeps I2 strictly safe under floating point: without it,
    # e.g. n=55/t=1 admits R=2.0 whose top-1 weight equals the threshold
    # to within 1 ulp and a SINGLE replica can "form a quorum"
    feasible = lambda r: top_t_fraction(r) <= 0.5 - 1e-9
    lo, hi = R_MIN, R_MAX
    if feasible(hi):
        return hi
    if not feasible(lo):
        return lo
    while hi - lo > tol:
        mid = 0.5 * (lo + hi)
        if feasible(mid):
            lo = mid
        else:
            hi = mid
    # small margin below the supremum so I2 holds strictly
    return max(R_MIN, lo * (1.0 - 1e-6))


# ---------------------------------------------------------------------------
# Dynamic weight assignment (paper §3.1 "Dynamic weight assignment")
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WeightTracker:
    """Latency-EMA state for dynamic per-object weights.

    ``latency_ema``: (num_objects, n) observed response-time EMA in ms.
    ``decay``: EMA decay (closer to 1 = slower adaptation).

    The paper: "replicas that respond faster to requests for object O
    receive higher weights for that object ... updated continuously based
    on observed response times." We rank replicas per object by the EMA and
    assign geometric weights by rank.
    """

    latency_ema: jax.Array  # (num_objects, n) float32
    decay: float = 0.9

    @staticmethod
    def init(num_objects: int, n: int, initial_latency_ms: float = 10.0,
             decay: float = 0.9) -> "WeightTracker":
        return WeightTracker(
            latency_ema=jnp.full((num_objects, n), initial_latency_ms,
                                 dtype=jnp.float32),
            decay=decay,
        )

    def observe(self, object_ids: jax.Array, latencies_ms: jax.Array
                ) -> "WeightTracker":
        """Fold a batch of observations into the EMA.

        ``object_ids``: (batch,) int32; ``latencies_ms``: (batch, n).
        Duplicate object ids in a batch fold left-to-right (scatter order).
        """
        d = self.decay
        cur = self.latency_ema[object_ids]
        upd = d * cur + (1.0 - d) * latencies_ms
        return dataclasses.replace(
            self, latency_ema=self.latency_ema.at[object_ids].set(upd))

    def weights(self, r: float) -> jax.Array:
        """Per-object geometric weights, (num_objects, n).

        Fastest (lowest EMA) replica per object gets the highest weight.
        """
        num_objects, n = self.latency_ema.shape
        order = jnp.argsort(self.latency_ema, axis=-1)  # fastest first
        ranks = jnp.argsort(order, axis=-1)             # rank of each replica
        base = geometric_weights(n, r)                  # descending by rank
        return base[ranks]

    def ranks(self) -> jax.Array:
        """Rank (0 = fastest) of each replica per object."""
        order = jnp.argsort(self.latency_ema, axis=-1)
        return jnp.argsort(order, axis=-1)


def node_weights_from_latency(latency_ema: jax.Array, r: float) -> jax.Array:
    """Global node weights for the slow path (paper §3.1, W^N).

    ``latency_ema``: (n,) cross-object replica latency EMA.
    """
    order = jnp.argsort(latency_ema)
    ranks = jnp.argsort(order)
    base = geometric_weights(latency_ema.shape[-1], r)
    return base[ranks]


def paper_table1() -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reproduce the object-weighted distributions of paper Table 1.

    Returns (R values, weight matrix (4, 7), thresholds T^O (4,)).
    Rows: ObjA (t=1, R=1.40), ObjB (t=1, R=1.38), ObjC (t=2, R=1.25),
    ObjD (t=3, R=1.10).
    """
    rs = np.array([1.40, 1.38, 1.25, 1.10])
    w = np.stack([np.asarray(geometric_weights(7, float(r))) for r in rs])
    return rs, w, w.sum(axis=-1) / 2.0


def paper_table2() -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reproduce the node-weighted distributions of paper Table 2.

    Rows: t=1 (R=1.40), t=2 (R=1.38), t=3 (R=1.19), t=4 (R=1.08).
    """
    rs = np.array([1.40, 1.38, 1.19, 1.08])
    w = np.stack([np.asarray(geometric_weights(7, float(r))) for r in rs])
    return rs, w, w.sum(axis=-1) / 2.0
