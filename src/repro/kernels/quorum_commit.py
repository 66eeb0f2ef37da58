"""Pallas TPU kernel: weighted-quorum commit scan (WOC's hot spot).

The paper (§5.4) attributes replica CPU saturation to "message processing
and quorum computation". At datacenter scale the Object Manager evaluates
quorum formation for millions of in-flight operations per second; this
kernel evaluates a BATCH of operations at once:

  per operation: the first vote, in arrival order, at which the weight
  of the votes that arrived no later than it STRICTLY exceeds
  T = sum(w)/2 -> commit time / quorum size / committed flag.

TPU layout: operations run along the 128 lanes and replicas along the
sublanes, so a (8 x 128) vreg holds 128 operations of up to 8 replicas.
Instead of sorting each operation's votes (a gather per compare-exchange
stage, which Mosaic does not lower), every vote i sums the weight of the
votes j that precede it in a stable sort by arrival time — t_j < t_i, or
t_j == t_i with j <= i. That is an all-pairs pass over the small replica
axis: n broadcast-compare-select steps on the whole tile, no gather, no
cumsum. The vote's rank in that order is the quorum size it would close.

Non-votes are encoded as +inf arrivals: they precede no vote and carry
zero weight into the sums, but their weight still counts toward T (the
threshold is a property of the object, not of who answers).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128          # operations per vreg row
SUBLANES = 8         # replica axis padding
OPS_BLOCK = 1024     # operations per grid step (lane-dense, 8 vregs wide)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel(t_ref, w_ref, commit_t_ref, qsize_ref, committed_ref, wsum_ref,
            *, n: int):
    t = t_ref[...]                               # (NP, BLK) replicas x ops
    w = w_ref[...]
    valid = jnp.isfinite(t)
    vote_w = jnp.where(valid, w, 0.0)
    thresh = jnp.sum(w, axis=0, keepdims=True) / 2.0
    row = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    w_before = jnp.zeros_like(t)                 # weight arrived by my vote
    rank = jnp.zeros_like(t)                     # my 1-based arrival rank
    for j in range(n):                           # padded rows never vote
        t_j = t[j:j + 1, :]
        before = (t_j < t) | ((t_j == t) & (row >= j))
        w_before = w_before + jnp.where(before, vote_w[j:j + 1, :], 0.0)
        rank = rank + before.astype(jnp.float32)
    crossed = valid & (w_before > thresh)        # strict crossing (Thm 1)
    # rank, time and weight all grow along the arrival order, so the
    # first crossing vote holds the minimum of each over crossed votes
    commit_t = jnp.min(jnp.where(crossed, t, jnp.inf), axis=0, keepdims=True)
    committed = commit_t < jnp.inf
    qsize = jnp.min(jnp.where(crossed, rank, float(n + 1)), axis=0,
                    keepdims=True)
    wsum = jnp.min(jnp.where(crossed, w_before, jnp.inf), axis=0,
                   keepdims=True)
    commit_t_ref[...] = commit_t
    qsize_ref[...] = jnp.where(committed, qsize, 0.0).astype(jnp.int32)
    committed_ref[...] = committed.astype(jnp.int32)
    wsum_ref[...] = jnp.where(committed, wsum, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quorum_commit_pallas(arrivals, weights, *, interpret: bool = False):
    """arrivals/weights: (ops, n) -> (commit_time, quorum_size, committed,
    weight_sum). Transposes to (replicas, ops), pads replicas to a
    multiple of 8 and ops to whole blocks (padding gets +inf arrival and
    zero weight: no effect on T or on any real operation)."""
    ops, n = arrivals.shape
    npad = _round_up(n, SUBLANES)
    blk = min(OPS_BLOCK, _round_up(ops, LANES))
    opad = _round_up(ops, blk)
    t = jnp.full((npad, opad), jnp.inf, jnp.float32)
    w = jnp.zeros((npad, opad), jnp.float32)
    t = t.at[:n, :ops].set(arrivals.astype(jnp.float32).T)
    w = w.at[:n, :ops].set(weights.astype(jnp.float32).T)

    in_spec = pl.BlockSpec((npad, blk), lambda i: (0, i))
    out_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=(opad // blk,),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((1, opad), jnp.float32),
            jax.ShapeDtypeStruct((1, opad), jnp.int32),
            jax.ShapeDtypeStruct((1, opad), jnp.int32),
            jax.ShapeDtypeStruct((1, opad), jnp.float32),
        ],
        interpret=interpret,
    )(t, w)
    commit_t, qsize, committed, wsum = (o[0, :ops] for o in out)
    return commit_t, qsize, committed.astype(bool), wsum
