"""The device path of a traced run: the window's quorums, folded on the
chip by the program's quorum kernel.

The served replicas decide quorums on the host (numpy) and never call the
device. So that a traced run still drives the program's device path, and
the trace shows what that path costs on the window's real inputs, the
harness rebuilds the vote-arrival matrix of the window's sampled fast-path
and slow-path instances from the replicas' spans and folds it through
``repro.kernels.ops.quorum_commit`` in fixed-shape chunks, once, after the
window. Each row is one proposed op: the proposer's (fast-path
coordinator's or slow-path leader's) own vote at its propose time, each
other replica's accept time, +inf for a vote that never came. Weights are
the paper's geometric node weights with the proposer first and the others
in id order (the replicas' starting ranking; their latency-driven
re-ranking is not in the spans).
"""

from __future__ import annotations

import numpy as np

CHUNK = 8192          # instances per kernel call: one compiled shape


ACCEPT_OF = {"fast_propose": "fast_accept", "slow_propose": "slow_accept"}


def vote_rows(events, n: int, t_fail: int, t0: float, t1: float):
    """(arrivals, weights), each (rows, n) float32, for the proposals in
    [t0, t1) of a canonical event list. A proposal event is ``(t, kind,
    proposer, instance, op)``; an accept ``(t, kind, proposer, instance,
    voter, ...)``."""
    from repro.core import weights as W
    accepts = {}
    for ev in events:
        if ev[1] in ("fast_accept", "slow_accept"):
            accepts.setdefault((ev[1], ev[3]), {}).setdefault(ev[4], ev[0])
    base = W.geometric_weights_np(n, W.solve_steepness(n, t_fail))
    by_proposer = []
    for c in range(n):
        w = np.empty(n)
        w[[c] + [j for j in range(n) if j != c]] = base
        by_proposer.append(w)
    arrivals, weights = [], []
    for ev in events:
        accept = ACCEPT_OF.get(ev[1])
        if accept is not None and t0 <= ev[0] < t1:
            row = np.full(n, np.inf)
            row[ev[2]] = ev[0]
            for voter, t in accepts.get((accept, ev[3]), {}).items():
                row[voter] = t
            arrivals.append(row)
            weights.append(by_proposer[ev[2]])
    return (np.asarray(arrivals, np.float32).reshape(-1, n),
            np.asarray(weights, np.float32).reshape(-1, n))


def warm(n: int) -> None:
    """Compile the one chunk shape (set-up, not the window)."""
    import jax
    from repro.kernels import ops
    a = np.full((CHUNK, n), np.inf, np.float32)
    jax.block_until_ready(ops.quorum_commit(a, np.ones_like(a)))


def replay(arrivals: np.ndarray, weights: np.ndarray) -> int:
    """Fold every row on the device; returns how many rows committed."""
    import jax
    from repro.kernels import ops
    rows, n = arrivals.shape
    committed = 0
    for i in range(0, rows, CHUNK):
        a = np.full((CHUNK, n), np.inf, np.float32)
        w = np.ones((CHUNK, n), np.float32)
        a[:min(CHUNK, rows - i)] = arrivals[i:i + CHUNK]
        w[:min(CHUNK, rows - i)] = weights[i:i + CHUNK]
        out = jax.block_until_ready(ops.quorum_commit(a, w))
        committed += int(np.asarray(out[2]).sum())
    return committed
