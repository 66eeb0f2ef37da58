"""The weights (paper §3.1–3.2), in one place.

Everything that turns a deployment and its latency observations into vote
weights lives here, each computed once:

  * :func:`clamp_faults` — the fault threshold a group of n can run,
  * :func:`solve_steepness` — the steepness R for (n, t),
  * :func:`geometric_weights_np` — the geometric base, one arithmetic:
    replicas sorted by decreasing efficiency get ``w_i = R^(n-1-i)`` for
    rank i in [0, n) (paper §3.2, eq. 1),
  * :func:`latency_ranks` — the rank of each replica by latency,
  * :class:`ObjectWeightTable` — a replica's per-object and node-level
    weights (numpy, event-loop fast).

The jax forms (:func:`geometric_weights`, :class:`WeightTracker`,
:func:`node_weights_from_latency`) are built on the same base and rank
rule, batched over objects (shape ``(num_objects, n_replicas)``), and
return the values the numpy forms return. The quorum rule they feed
(``T^O = sum(W^O)/2``, crossed strictly) lives in :mod:`repro.core.quorum`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quorum import consensus_threshold, crosses

# Steepness bounds from the paper (§3.2): R in [1.0, 2.0].
R_MIN = 1.0
R_MAX = 2.0


def max_faults(n: int) -> int:
    """The most faults a group of ``n`` can tolerate: ``floor((n-1)/2)``."""
    return (n - 1) // 2


def clamp_faults(n: int, t: int) -> int:
    """A requested fault threshold clamped to ``1..max_faults(n)``, the
    range :func:`solve_steepness` accepts."""
    return max(1, min(t, max_faults(n)))


def geometric_weights_np(n: int, r: float,
                         dtype=np.float32) -> np.ndarray:
    """The geometric base: weights for ``n`` replicas ordered fastest-first,
    ``w_i = r^(n-1-i)``, descending to ``w[-1] == 1.0`` (Table 1/2 of the
    paper). Computed in float64, then cast to ``dtype``.

    numpy, not jax: the event-driven simulator's worker processes and the
    served replicas build their weights from it and must never start a jax
    backend (an accelerator belongs to the one process that launched
    them)."""
    if n < 1:
        raise ValueError(f"need at least one replica, got n={n}")
    if not (R_MIN <= r <= R_MAX):
        raise ValueError(f"steepness r={r} outside paper range [{R_MIN}, {R_MAX}]")
    exponents = np.arange(n - 1, -1, -1, dtype=np.float64)
    if (n - 1) * np.log(max(r, 1.0 + 1e-12)) > 60.0:
        # large fleets: r^(n-1) overflows float32. Quorum math is scale-
        # invariant (threshold = sum/2), so normalize to w_max = 1
        # (descending from 1 instead of descending to 1).
        exponents = exponents - (n - 1)
    return np.power(np.float64(r), exponents).astype(dtype)


def geometric_weights(n: int, r: float, dtype=jnp.float32) -> jax.Array:
    """:func:`geometric_weights_np` as a jnp array: the same values."""
    return jnp.asarray(geometric_weights_np(n, r, dtype))


def latency_ranks(latency, out=None):
    """Rank of each replica by latency along the last axis, 0 = fastest;
    equal latencies rank in replica order (a stable sort). A jnp array
    (any leading axes) gives jnp ranks; a numpy vector or list gives
    numpy ranks, written into ``out`` when it is given."""
    if isinstance(latency, jax.Array):
        order = jnp.argsort(latency, axis=-1, stable=True)
        return jnp.argsort(order, axis=-1)
    order = np.argsort(latency, kind="stable")
    ranks = np.empty_like(order) if out is None else out
    ranks[order] = np.arange(order.size)
    return ranks


def cabinet_size(weights_desc: jax.Array) -> jax.Array:
    """Smallest k such that the k heaviest replicas form a quorum.

    ``weights_desc`` must be sorted descending along the last axis. The
    paper calls these k replicas the *cabinet* (top t+1 weighted replicas).
    Vectorized over leading axes.
    """
    csum = jnp.cumsum(weights_desc, axis=-1)
    meets = crosses(csum, consensus_threshold(weights_desc)[..., None])
    return jnp.argmax(meets, axis=-1) + 1


def check_invariant_progress(weights: jax.Array, t: int) -> jax.Array:
    """Invariant I1 (progress): sum of top t+1 weights > T.

    ``weights`` need not be sorted. Vectorized over leading axes; returns a
    boolean array.
    """
    w_sorted = jnp.sort(weights, axis=-1)[..., ::-1]
    top = jnp.sum(w_sorted[..., : t + 1], axis=-1)
    return crosses(top, consensus_threshold(weights))


def check_invariant_safety(weights: jax.Array, t: int) -> jax.Array:
    """Invariant I2 (safety): no t-subset can form a quorum.

    Under strict-crossing quorums (sum > T) a t-subset is safe iff its
    weight is <= T; the worst case is the t heaviest replicas.
    """
    if t == 0:
        return jnp.ones(weights.shape[:-1], dtype=bool)
    w_sorted = jnp.sort(weights, axis=-1)[..., ::-1]
    top_t = jnp.sum(w_sorted[..., :t], axis=-1)
    return ~crosses(top_t, consensus_threshold(weights))


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
    if not (1 <= t <= max_faults(n)):
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


class ObjectWeightTable:
    """Per-object latency EMA -> geometric weights (numpy, event-loop fast).

    The returned weight vectors are permutations of ``base`` and treated as
    read-only by callers, so the node-level fallback (the common case: a
    first-touch object has no EMA of its own) is cached and recomputed only
    when the node EMA changes (``node_version`` is bumped by
    ``BaseReplica.observe_node``).
    """

    def __init__(self, n: int, r: float, node_ema: np.ndarray,
                 decay: float = 0.85):
        self.n = n
        self.base = geometric_weights_np(n, r)             # descending by rank
        self.half_sum = float(consensus_threshold(self.base))
        self.decay = decay
        # per-object EMAs are plain float lists: element updates in
        # ``observe`` are ~5x cheaper than numpy scalar writes, and the
        # argsort in ``_weights_of`` converts on the (much rarer) read
        self.ema: Dict[int, list] = {}
        self.node_ema = node_ema  # shared fallback: node-level latency EMA
        self.node_version = 0
        self._nw_version = -1
        self._nw: np.ndarray | None = None
        self._ranks = np.empty(n, dtype=np.int64)   # scratch
        self._arange = np.arange(n)
        # installed weight view (repro.core.reassign): while active, the
        # epoch-stamped ranking overrides BOTH the per-object EMAs and
        # the node-level ranking — the view is the shared truth all
        # replicas quorum under, private telemetry resumes on restore.
        self.rank_of: np.ndarray | None = None
        # flat fallback (graceful degradation): when the view-weighted
        # heartbeat-fresh set cannot strictly cross half_sum, quorums
        # degrade to count-majorities (weights 1, threshold n/2)
        self.flat = False
        self._flat_w = np.ones(n, dtype=np.float64)
        self._flat_threshold = float(consensus_threshold(self._flat_w))

    def observe(self, obj: int, replica: int, latency: float) -> None:
        e = self.ema.get(obj)
        if e is None:
            e = self.ema[obj] = self.node_ema.tolist()
        e[replica] = self.decay * e[replica] + (1 - self.decay) * latency

    def _weights_of(self, e: np.ndarray) -> np.ndarray:
        return self.base[latency_ranks(e, self._ranks)]

    def view_weights(self) -> np.ndarray:
        """Node weights under the current view, ignoring the flat
        fallback (the fallback's own trigger test needs these)."""
        if self._nw_version != self.node_version:
            ro = self.rank_of
            self._nw = self.base[ro] if ro is not None \
                else self._weights_of(self.node_ema)
            self._nw_version = self.node_version
        return self._nw

    def node_weights(self) -> np.ndarray:
        """Node-level weights, cached per node-EMA/view version."""
        if self.flat:
            return self._flat_w
        return self.view_weights()

    def weights_for(self, obj: int) -> np.ndarray:
        if self.flat:
            return self._flat_w
        if self.rank_of is not None:
            return self.view_weights()
        e = self.ema.get(obj)
        if e is None:
            return self.view_weights()
        return self._weights_of(e)

    def current_threshold(self) -> float:
        return self._flat_threshold if self.flat else self.half_sum

    def threshold_for(self, obj: int) -> float:
        return self.current_threshold()            # T^O = sum(W^O)/2

    def shared_weights(self) -> np.ndarray:
        """Weights under the SHARED election ranking: the epoch-stamped
        installed view when present (``view_weights`` is then exactly
        ``base[rank_of]``, cached), else the static deployment ranking
        (replica id == rank). Unlike ``node_weights`` this never consults
        the private latency EMA: every node zeroes its own EMA entry and
        so ranks ITSELF top-weight in its private view, which would let
        two partition sides both believe they hold the weighted
        majority. The leadership lease and the isolation detector must
        evaluate one vector that is identical at every replica."""
        if self.flat:
            return self._flat_w
        if self.rank_of is not None:
            return self.view_weights()
        return self.base

    def set_rank_override(self, ranking) -> None:
        """Install (or with ``None`` clear) an epoch-stamped ranking:
        ``ranking[0]`` gets the top geometric weight. Per-object EMAs
        are dropped either way — telemetry gathered under the previous
        weight regime must not leak into the new one."""
        if ranking is None:
            self.rank_of = None
        else:
            ro = np.empty(self.n, dtype=np.int64)
            ro[np.asarray(ranking, dtype=np.int64)] = self._arange
            self.rank_of = ro
        self.ema.clear()
        self.node_version += 1


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
        n = self.latency_ema.shape[-1]
        return geometric_weights(n, r)[self.ranks()]

    def ranks(self) -> jax.Array:
        """Rank (0 = fastest) of each replica per object."""
        return latency_ranks(self.latency_ema)


def node_weights_from_latency(latency_ema: jax.Array, r: float) -> jax.Array:
    """Global node weights for the slow path (paper §3.1, W^N).

    ``latency_ema``: (n,) cross-object replica latency EMA.
    """
    return geometric_weights(latency_ema.shape[-1], r)[
        latency_ranks(latency_ema)]


def paper_table1() -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reproduce the object-weighted distributions of paper Table 1.

    Returns (R values, weight matrix (4, 7), thresholds T^O (4,)).
    Rows: ObjA (t=1, R=1.40), ObjB (t=1, R=1.38), ObjC (t=2, R=1.25),
    ObjD (t=3, R=1.10).
    """
    rs = np.array([1.40, 1.38, 1.25, 1.10])
    w = np.stack([geometric_weights_np(7, float(r)) for r in rs])
    return rs, w, consensus_threshold(w)


def paper_table2() -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reproduce the node-weighted distributions of paper Table 2.

    Rows: t=1 (R=1.40), t=2 (R=1.38), t=3 (R=1.19), t=4 (R=1.08).
    """
    rs = np.array([1.40, 1.38, 1.19, 1.08])
    w = np.stack([geometric_weights_np(7, float(r)) for r in rs])
    return rs, w, consensus_threshold(w)
