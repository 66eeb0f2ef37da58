"""Pure-jnp oracle for the quorum kernel (asserted equal in tests): the
library's own :func:`repro.core.quorum.quorum_commit`, so the kernel
validates against the rule the protocol runs."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.quorum import quorum_commit as _quorum_commit


def quorum_commit_ref(arrivals, weights):
    res = _quorum_commit(jnp.asarray(arrivals), jnp.asarray(weights))
    return (res.commit_time, res.quorum_size, res.committed, res.weight_sum)
