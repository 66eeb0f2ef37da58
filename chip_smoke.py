"""Bring-up check of WOC on one TPU chip, through the entry points a user calls.

    python3 chip_smoke.py

Phases, in one process (a chip belongs to the one process that opened it):

  1. device      jax must find a TPU; there is no CPU fallback.
  2. deployment  ``examples/scenarios/paper_default.json`` at full size
                 (5 replicas, 40 000 ops, the §5.1 90/5/5 mix) through
                 ``Scenario.from_json`` -> ``run_scenario``, with history
                 capture, the linearizability check and tracing on. Every
                 op must commit.
  3. device path the run's (fast-path instances x replicas) vote-arrival
                 matrix, rebuilt from its trace, evaluated on the chip by
                 the compiled quorum kernel (``repro.kernels.ops``), as one
                 call and as per-tick batches of 100; the result must equal
                 an independent numpy reference. Then a ``WeightTracker``
                 table of 1 M objects x 5 replicas: ``observe`` then
                 ``weights``, checked against a numpy argsort of its EMA.
  4. served      ``examples/scenarios/served_kv.json`` over localhost
                 sockets while this process holds the chip; its history must
                 be linearizable and no replica or client process may start
                 a jax backend.

Each phase prints its wall time and the counts it compared; a failed phase
raises, so the script exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import collections
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
PAPER_DEFAULT = ROOT / "examples" / "scenarios" / "paper_default.json"
SERVED_KV = ROOT / "examples" / "scenarios" / "served_kv.json"
TICK_BATCH = 100     # paper_default in flight per tick: 2 clients x 5 x 10
TABLE_OBJECTS = 1_000_000


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def device_phase() -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: jax found no TPU (platform "
                         f"{dev.platform!r}); this check runs only on the chip")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def deployment_phase(total_ops: int | None = None):
    """Run ``paper_default`` with history, linearizability check and
    tracing on; ``run_scenario`` raises if the history is not
    linearizable. Returns the run's artifacts."""
    from repro.scenario import Scenario, run_scenario
    spec = json.loads(PAPER_DEFAULT.read_text())
    spec["verify"] = {"capture_history": True, "check_linearizable": True}
    spec["obs"] = {"trace": True, "sample_every": 1}
    if total_ops is not None:
        spec["total_ops"] = total_ops
    t0 = time.perf_counter()
    art = run_scenario(Scenario.from_json(json.dumps(spec)))
    wall = time.perf_counter() - t0
    r = art.result
    _require(r.committed_ops == spec["total_ops"],
             f"committed {r.committed_ops} of {spec['total_ops']} ops")
    _say("deployment", wall_s=wall, committed_ops=r.committed_ops,
         total_ops=spec["total_ops"], history=len(r.history),
         linearizable=True, trace_events=len(r.trace))
    return art


def vote_matrix(trace, n: int, base: np.ndarray):
    """Rebuild each fast-path instance's vote arrivals from a run's trace.

    One row per proposed op: the coordinator's self-vote at its propose
    time, every other replica's ``fast_accept`` stamp for that batch, and
    +inf for a replica whose vote never arrived. The row's weights are
    ``base`` permuted by the coordinator's latency ranking as its last
    ``ema`` span before the proposal gives it (itself first; replica-id
    order before the first span). Also returns each row's host
    ``fast_commit`` stamp (+inf where the op did not commit fast)."""
    accepts = collections.defaultdict(dict)
    fast_commit = {}
    for ev in trace:
        if ev[1] == "fast_accept":
            accepts[ev[3]].setdefault(ev[4], ev[0])
        elif ev[1] == "fast_commit":
            fast_commit.setdefault(ev[4], ev[0])
    ema = [np.arange(1.0, n + 1.0) for _ in range(n)]
    for node in range(n):
        ema[node][node] = 0.0
    weights_of = [None] * n
    arrivals, weights, stamps = [], [], []
    for ev in trace:                        # canonical (t, kind, ...) order
        kind = ev[1]
        if kind == "ema":
            ema[ev[2]][ev[3]] = ev[4]
            weights_of[ev[2]] = None
        elif kind == "fast_propose":
            t, node, batch, op_id = ev[0], ev[2], ev[3], ev[4]
            if weights_of[node] is None:
                ranks = np.empty(n, dtype=np.int64)
                ranks[np.argsort(ema[node], kind="stable")] = np.arange(n)
                weights_of[node] = base[ranks]
            row = np.full(n, np.inf)
            row[node] = t
            for src, ta in accepts[batch].items():
                row[src] = ta
            arrivals.append(row)
            weights.append(weights_of[node])
            stamps.append(fast_commit.get(op_id, np.inf))
    return (np.asarray(arrivals, np.float32), np.asarray(weights, np.float32),
            np.asarray(stamps, np.float32))


def numpy_quorum(arrivals: np.ndarray, weights: np.ndarray):
    """Host reference: stable sort by arrival, f32 prefix sum of the
    votes' weights, first strict crossing of half the total weight.
    Returns (commit_time, quorum_size, committed)."""
    order = np.argsort(arrivals, axis=1, kind="stable")
    t = np.take_along_axis(arrivals, order, axis=1)
    w = np.where(np.isfinite(t), np.take_along_axis(weights, order, axis=1),
                 np.float32(0))
    csum = np.cumsum(w, axis=1, dtype=np.float32)
    half = weights.sum(axis=1, dtype=np.float32) / np.float32(2)
    crossed = csum > half[:, None]
    committed = crossed.any(axis=1)
    k = crossed.argmax(axis=1)
    commit_t = np.where(committed, t[np.arange(len(t)), k], np.float32(np.inf))
    qsize = np.where(committed, k + 1, 0).astype(np.int32)
    return commit_t.astype(np.float32), qsize, committed


def _compare(name: str, got, ref) -> None:
    commit_t, qsize, committed, _ = (np.asarray(x) for x in got)
    r_t, r_q, r_c = ref
    _require(np.array_equal(committed, r_c), f"{name}: committed differs "
             f"on {int((committed != r_c).sum())} instances")
    _require(np.array_equal(qsize, r_q), f"{name}: quorum size differs "
             f"on {int((qsize != r_q).sum())} instances")
    _require(np.array_equal(commit_t, r_t), f"{name}: commit time differs "
             f"on {int((commit_t != r_t).sum())} instances")


def quorum_phase(art) -> dict:
    """Evaluate the run's vote-arrival matrix through
    ``repro.kernels.ops.quorum_commit``: one call, then per-tick
    batches. Raises unless both equal the numpy reference."""
    import jax
    from repro.kernels import ops

    n = len(art.replicas)
    arrivals, weights, stamps = vote_matrix(art.result.trace, n,
                                            art.replicas[0].obj_weights.base)
    rows = len(arrivals)
    _require(rows > 0, "the run has no fast-path instance")
    ref = numpy_quorum(arrivals, weights)
    kernel = jax.jit(ops.quorum_commit)

    def compile_for(a, w):
        t0 = time.perf_counter()
        lowered = kernel.lower(a, w)
        compiled = lowered.compile()
        return (compiled, time.perf_counter() - t0,
                "tpu_custom_call" in lowered.as_text())

    t0 = time.perf_counter()
    a_dev, w_dev = jax.device_put(arrivals), jax.device_put(weights)
    one, one_compile_s, one_custom = compile_for(a_dev, w_dev)
    t1 = time.perf_counter()
    got = jax.block_until_ready(one(a_dev, w_dev))
    one_call_s = time.perf_counter() - t1
    _compare("one call", got, ref)
    reproduced = int((np.asarray(got[0]) == stamps).sum())
    one_wall = time.perf_counter() - t0

    # per-tick batches; the tail is padded with non-votes and dropped
    t0 = time.perf_counter()
    ticks = -(-rows // TICK_BATCH)
    pad = ticks * TICK_BATCH - rows
    a_t = np.concatenate([arrivals, np.full((pad, n), np.inf, np.float32)])
    w_t = np.concatenate([weights, np.ones((pad, n), np.float32)])
    a_t = a_t.reshape(ticks, TICK_BATCH, n)
    w_t = w_t.reshape(ticks, TICK_BATCH, n)
    tick, tick_compile_s, tick_custom = compile_for(a_t[0], w_t[0])
    t1 = time.perf_counter()
    outs = [tick(a_t[i], w_t[i]) for i in range(ticks)]
    outs = jax.block_until_ready(outs)
    ticks_s = time.perf_counter() - t1
    _compare("per-tick", [np.concatenate([np.asarray(o[j]) for o in outs])[:rows]
                          for j in range(4)], ref)
    tick_wall = time.perf_counter() - t0

    info = {"instances": rows, "replicas": n,
            "committed": int(ref[2].sum()),
            "tpu_custom_call": one_custom and tick_custom,
            "one_call_wall_s": one_wall, "one_call_compile_s": one_compile_s,
            "one_call_run_s": one_call_s,
            "tick_batches": ticks, "tick_wall_s": tick_wall,
            "tick_compile_s": tick_compile_s, "tick_run_s": ticks_s,
            "host_fast_commits": int(np.isfinite(stamps).sum()),
            "host_fast_commits_reproduced": reproduced}
    _say("device path: quorum kernel", **info)
    return info


def weights_phase(r: float, num_objects: int = TABLE_OBJECTS,
                  n: int = 5) -> dict:
    """``WeightTracker`` on the device: observe every object once (in a
    shuffled order), derive weights, and check both against numpy."""
    import jax
    import jax.numpy as jnp
    from repro.core import weights as W

    rng = np.random.default_rng(0)
    ids = rng.permutation(num_objects).astype(np.int32)
    lat = rng.uniform(0.1, 20.0, (num_objects, n)).astype(np.float32)
    t0 = time.perf_counter()
    tracker = W.WeightTracker.init(num_objects, n)
    tracker = tracker.observe(jnp.asarray(ids), jnp.asarray(lat))
    got = jax.block_until_ready(tracker.weights(r))
    device_s = time.perf_counter() - t0

    ema = np.asarray(tracker.latency_ema)
    d = np.float32(tracker.decay)
    want_ema = np.full((num_objects, n), 10.0, np.float32)
    want_ema[ids] = d * want_ema[ids] + (np.float32(1) - d) * lat
    _require(np.allclose(ema, want_ema, rtol=1e-6, atol=0),
             "WeightTracker EMA differs from numpy")
    ranks = np.empty_like(ema, dtype=np.int64)
    np.put_along_axis(ranks, np.argsort(ema, axis=1, kind="stable"),
                      np.arange(n)[None, :], axis=1)
    want = np.asarray(W.geometric_weights(n, r))[ranks]
    mismatched = int((np.asarray(got) != want).any(axis=1).sum())
    _require(mismatched == 0, f"weights differ on {mismatched} objects")
    info = {"objects": num_objects, "replicas": n, "device_s": device_s,
            "objects_checked": num_objects}
    _say("device path: weight table", **info)
    return info


def served_phase(total_ops: int | None = None) -> dict:
    """Serve ``served_kv`` over localhost; its history must linearize and
    no child process may start a jax backend."""
    from repro.transport import ClusterConfig, run_served
    from repro.verify import check_history_linearizable

    cfg = ClusterConfig.from_json(SERVED_KV)
    if total_ops is not None:
        cfg.total_ops = total_ops
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="woc-served-") as run_dir:
        cfg.run_dir = run_dir
        r = run_served(cfg).result
    wall = time.perf_counter() - t0
    _require(r.clients_done == cfg.n_clients,
             f"{r.clients_done} of {cfg.n_clients} clients drained")
    ok, why = check_history_linearizable(r.history)
    _require(ok, f"served history not linearizable: {why}")
    stats = r.node_stats + r.client_stats
    _require(len(stats) == cfg.n_replicas + cfg.n_clients,
             f"{len(stats)} process reports")
    _require(not any(s["jax_backend"] for s in stats),
             "a replica or client process started a jax backend")
    info = {"wall_s": wall, "history": len(r.history),
            "total_ops": cfg.total_ops, "linearizable": True,
            "processes_without_jax_backend": len(stats)}
    _say("served", **info)
    return info


def main() -> int:
    t_start = time.perf_counter()
    device = device_phase()
    _say("device", platform=device["platform"], kind=repr(device["kind"]),
         count=device["count"])

    sys.path.insert(0, str(ROOT / "src"))
    import jax.monitoring
    from repro import compile_cache
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event]))
    cache_dir = compile_cache.enable()

    art = deployment_phase()
    quorum = quorum_phase(art)
    _require(quorum["tpu_custom_call"],
             "the quorum kernel did not compile to a TPU custom call")
    weights_phase(art.replicas[0].r)
    served_phase()
    _say("compile cache", dir=cache_dir,
         hits=cache_events["/jax/compilation_cache/cache_hits"],
         misses=cache_events["/jax/compilation_cache/cache_misses"])
    _say("total", wall_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
