"""WOC-as-a-training-feature: weighted-quorum gradient commit.

The paper's exact problem — heterogeneous responders, mostly-independent
updates, occasional global coordination — reappears inside a 1000-node
data-parallel training job:

  * object  -> parameter BUCKET (per-layer-group gradients are independent
               objects; optimizer hyper-state is a hot object),
  * replica -> data-parallel worker (a mesh sub-slice),
  * weight  -> per-bucket geometric weight from the worker's step-latency
               EMA (paper §3.1's dynamic rule, clocked by training steps),
  * fast path commit -> a bucket's gradient commits once the contributing
               workers' weight strictly exceeds T^O = sum(w)/2; stragglers'
               contributions are dropped and the mean renormalizes over the
               committed set (unbiased under random assignment),
  * slow path -> full-participation barrier (mask of ones) for "hot" state:
               optimizer hyper updates, membership epochs, checkpoints.

Mechanically the commit is pure data-plane: each batch row belongs to one
dp worker (row block r), so scaling the LOSS MASK rows by the bucket's
committed-worker indicator (renormalized) makes the ordinary backward
reduction produce exactly the quorum-committed gradient — no extra
collectives, no graph change; the decision logic lives host-side where the
arrival information exists. ``quorum_allreduce`` additionally provides the
explicit shard_map form (masked psum) used when gradients are reduced
outside the autodiff path (e.g. with int8 compression).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quorum as Q
from repro.core import weights as W


@dataclasses.dataclass
class QuorumState:
    latency_ema: np.ndarray        # (n_workers,) seconds
    steepness: float
    decay: float = 0.9
    committed_frac: float = 1.0

    def weights(self) -> np.ndarray:
        # float64: at fleet sizes (n > ~50) a float32 base loses the light
        # tail entirely and strict majority checks break on precision
        base = W.geometric_weights_np(len(self.latency_ema), self.steepness,
                                      np.float64)
        return base[W.latency_ranks(self.latency_ema)]


class GradQuorum:
    """Host-side controller: tracks worker step latencies, picks the
    committed set per step, and emits (a) scaled loss-mask row weights and
    (b) commit metrics/certificates."""

    def __init__(self, n_workers: int, *, t_fail: int = 1,
                 decay: float = 0.9):
        self.n = n_workers
        r = W.solve_steepness(n_workers, W.clamp_faults(
            n_workers, t_fail)) if n_workers >= 3 else 1.5
        self.state = QuorumState(
            latency_ema=np.full(n_workers, 1.0), steepness=r, decay=decay)

    def observe(self, step_latencies: np.ndarray) -> None:
        d = self.state.decay
        self.state.latency_ema = (d * self.state.latency_ema
                                  + (1 - d) * step_latencies)

    def commit_mask(self, arrivals: Optional[np.ndarray] = None
                    ) -> np.ndarray:
        """Committed-worker mask for this step.

        ``arrivals``: measured per-worker gradient-ready times for the
        step (None -> use the latency EMA as the predictor). Workers join
        the quorum in arrival order until weight strictly exceeds T.
        """
        t = self.state.latency_ema if arrivals is None else arrivals
        order = np.argsort(t, kind="stable")
        k = Q.quorum_size(self.state.weights(), order)
        mask = np.zeros(self.n, bool)
        mask[order[:k]] = True
        self.state.committed_frac = k / self.n
        return mask

    def row_weights(self, mask: np.ndarray) -> np.ndarray:
        """Per-worker loss-row scale: m_r * n / sum(m) (renormalized)."""
        m = mask.astype(np.float64)
        return (m * self.n / max(m.sum(), 1.0)).astype(np.float32)

    def scale_batch_mask(self, batch: dict, mask: np.ndarray) -> dict:
        """Scale the loss mask rows by the committed-worker weights.

        Batch rows are laid out worker-major (row block r belongs to dp
        worker r), matching the dp sharding of the global batch.
        """
        rw = self.row_weights(mask)
        B = batch["mask"].shape[0]
        per = B // self.n
        rows = np.repeat(rw, per)
        out = dict(batch)
        out["mask"] = batch["mask"] * rows[:, None]
        return out

    def certificate(self, step: int, mask: np.ndarray) -> dict:
        w = self.state.weights()
        return {"step": step, "committed": mask.tolist(),
                "weight": float(w[mask].sum()),
                "threshold": float(Q.consensus_threshold(w)),
                "frac": self.state.committed_frac}

    # ---- analytics: expected step-time win (order statistics) --------------

    def expected_step_time(self, latency_dist: np.ndarray,
                           trials: int = 2000, seed: int = 0
                           ) -> Dict[str, float]:
        """Monte-Carlo E[step time] under full barrier vs quorum commit.

        latency_dist: (n,) per-worker mean step latencies; each trial draws
        exponential noise around the means (heavy straggler tail).
        """
        rng = np.random.default_rng(seed)
        w = self.state.weights()
        full, quorum = [], []
        for _ in range(trials):
            t = latency_dist * (0.7 + 0.6 * rng.random(self.n)) \
                + rng.exponential(0.1 * latency_dist)
            full.append(t.max())
            order = np.argsort(t)
            quorum.append(t[order[Q.quorum_size(w, order) - 1]])
        return {"barrier_mean_s": float(np.mean(full)),
                "quorum_mean_s": float(np.mean(quorum)),
                "speedup": float(np.mean(full) / np.mean(quorum))}


# ---------------------------------------------------------------------------
# explicit masked reduction (shard_map form)
# ---------------------------------------------------------------------------

def quorum_allreduce(grads, mask, axis_name: str = "data"):
    """Masked-mean psum inside shard_map: each worker contributes its
    gradient scaled by its commit bit; the sum renormalizes by the
    committed count. mask: (n_workers,) float."""
    idx = jax.lax.axis_index(axis_name)
    m = mask[idx]
    count = jax.lax.psum(m, axis_name)
    scaled = jax.tree.map(lambda g: g * m, grads)
    summed = jax.tree.map(lambda g: jax.lax.psum(g, axis_name), scaled)
    return jax.tree.map(lambda g: g / jnp.maximum(count, 1.0), summed)
