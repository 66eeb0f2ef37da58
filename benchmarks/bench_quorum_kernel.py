"""Quorum-commit compute micro-benchmark (§5.4's "quorum computation").

Compares: (a) per-op Python/numpy loop (what a Go implementation does per
message), (b) vectorized jnp batch (the library path), (c) the Pallas
kernel's results against (b): compiled on a TPU, in interpret mode on
any other platform.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Claims, write_csv
from repro.core.quorum import quorum_commit
from repro.kernels import ops as kernel_ops


def _python_loop(arrivals, weights):
    out = []
    for t, w in zip(arrivals, weights):
        order = np.argsort(t)
        acc, hit = 0.0, np.inf
        thresh = w.sum() / 2
        for k, i in enumerate(order):
            if not np.isfinite(t[i]):
                break
            acc += w[i]
            if acc > thresh:
                hit = t[i]
                break
        out.append(hit)
    return np.array(out)


def run(out_dir, quick: bool = False) -> list[str]:
    claims = Claims()
    rng = np.random.default_rng(0)
    rows = []
    shapes = [(1024, 8), (8192, 8), (8192, 32), (65536, 16)]
    if quick:
        shapes = shapes[:2]
    for ops, n in shapes:
        arrivals = rng.uniform(0, 10, (ops, n)).astype(np.float32)
        weights = rng.uniform(0.5, 8.0, (ops, n)).astype(np.float32)

        t0 = time.perf_counter()
        ref = _python_loop(arrivals, weights)
        t_py = time.perf_counter() - t0

        a, w = jnp.asarray(arrivals), jnp.asarray(weights)
        f = jax.jit(lambda a, w: quorum_commit(a, w).commit_time)
        f(a, w).block_until_ready()
        t0 = time.perf_counter()
        got = f(a, w)
        got.block_until_ready()
        t_jnp = time.perf_counter() - t0

        ok = np.allclose(np.asarray(got), ref, rtol=1e-5)
        rows.append({"ops": ops, "n": n,
                     "python_us_per_op": round(t_py / ops * 1e6, 3),
                     "jnp_us_per_op": round(t_jnp / ops * 1e6, 3),
                     "speedup": round(t_py / max(t_jnp, 1e-9), 1),
                     "allclose": ok})
    write_csv(out_dir, "quorum_kernel_microbench", rows)

    # correctness of the Pallas kernel at bench shapes: compiled on a
    # TPU, interpreted anywhere else
    a = rng.uniform(0, 10, (512, 16)).astype(np.float32)
    w = rng.uniform(0.5, 8.0, (512, 16)).astype(np.float32)
    ct, _, cm, _ = kernel_ops.quorum_commit(jnp.asarray(a), jnp.asarray(w),
                                            force_pallas=True)
    res = quorum_commit(jnp.asarray(a), jnp.asarray(w))
    dev = jax.devices()[0]
    mode = "compiled" if dev.platform == "tpu" else "interpret-mode"
    claims.check("Pallas quorum kernel == jnp oracle",
                 bool(jnp.all(res.committed == cm))
                 and np.allclose(np.asarray(ct)[np.asarray(cm)],
                                 np.asarray(res.commit_time)[np.asarray(cm)]),
                 f"{mode} allclose at (512,16) on {dev.platform} "
                 f"({dev.device_kind})")
    claims.check("vectorized quorum math beats per-op loop",
                 all(r["speedup"] > 3 for r in rows),
                 f"speedups {[r['speedup'] for r in rows]}")
    return claims.lines
