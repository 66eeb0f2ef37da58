"""WOC on the chip's host, served, measured from the client's side.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``:

1. finds the cell's chips (a TPU, no CPU fallback) and loads its
   configuration (``bench/configs/<config>.json``) and traffic mix
   (``bench/traffic/<traffic>.json``);
2. boots the replicas through the program's served entry
   (``repro.transport.ClusterLauncher``, one process per replica) and
   drives them with the benchmark's own clients (``loadgen``): a warm-up,
   then ``--seconds`` of measured window;
3. waits for every op sent to be acknowledged (a minute at most), reads
   the acknowledged writes back through every live replica, and checks the
   whole history against a sequential register per object (``regmodel``);
4. with ``--trace 1``, records the replicas' spans and a profiler trace of
   this process (which holds the chip), folds the window's sampled
   quorums on the chip after the window (``fold``), and reports the
   per-layer metrics instead of the end-to-end ones.

Each metric is computed by ``bench/metrics/<name>.py`` from the run's
record. The last line of standard output is one JSON object; the numbers
compared for ``correct`` are printed beside their limits as the last lines
of standard error and under the result's last key, ``checks``.
"""

from __future__ import annotations

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import asyncio              # noqa: E402
import dataclasses          # noqa: E402
import gc                   # noqa: E402
import importlib.util       # noqa: E402
import json                 # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402
from pathlib import Path    # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np          # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

import loadgen              # noqa: E402
import regmodel             # noqa: E402

DRAIN_S = 60.0              # how long past the window an ack may come
BOOT_S = 60.0               # how long the replicas may take to listen
PROFILE_OPTIONS = dict(python_tracer_level=0, host_tracer_level=1)


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


# -- the cell, by name ---------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, its configuration and traffic files, and the
    metrics it reports, all found by name from ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    traffic_file = root / "bench" / "traffic" / f"{cell['traffic']}.json"

    e2e = [m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    # a per-layer metric without a cell list goes wherever the end-to-end
    # metric it moves is reported
    layer = [m["name"] for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e)]
    return {"name": name, "chips": cell["chips"],
            "config": json.loads((root / cfg_entry["file"]).read_text()),
            "traffic": json.loads(traffic_file.read_text()),
            "end_to_end": e2e, "per_layer": layer,
            "units": {m["name"]: m["unit"]
                      for m in spec["end_to_end"] + spec["per_layer"]}}


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_device(chips: int, require_tpu: bool = True) -> dict:
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"jax found no device: {e}") from e
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); jax found "
                     f"{len(devices)} {dev.platform!r} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "_device": dev}


# -- one run -------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers see of one run. Times are seconds on the
    cluster clock (``NetContext.now``)."""

    t0: float                      # window start
    t1: float                      # window end
    setup_s: float
    due: np.ndarray                # per op due in the window: due time
    ack: np.ndarray                # ... ack receipt, +inf if none came
    path: np.ndarray               # ... commit path the replica reported
    acks: np.ndarray               # every ack receipt of the load clients
    acked_total: int               # ops acknowledged in the whole run
    node_stats: list = dataclasses.field(default_factory=list)
    report: object = None          # obs.critical_path report of the window
    device: Optional[dict] = None  # devtrace.reduce_planes of the trace


def _workload(config: dict, traffic: dict):
    from repro.core.simulator import Workload
    p_common, p_hot = traffic["p_common"], traffic["p_hot"]
    return Workload(p_independent=1.0 - p_common - p_hot, p_common=p_common,
                    p_hot=p_hot, n_common_objects=config["n_common_objects"],
                    n_hot_objects=config["n_hot_objects"],
                    reads_fraction=traffic["reads_fraction"])


def _readback_objects(records, config: dict, traffic: dict, seed: int):
    """Every common and hot object, and a seeded sample of the independent
    objects that acknowledged writes touched."""
    written = np.unique(np.concatenate([
        r["obj"][(r["kind"] == "w") & np.isfinite(r["ack"])]
        for r in records]))
    indep = written[[loadgen.object_class(int(o)) == 0 for o in written]]
    rng = np.random.default_rng([seed, 0xBAC])
    k = min(traffic["readback_independent"], len(indep))
    sample = rng.choice(indep, size=k, replace=False) if k else indep[:0]
    return ([int(o) for o in sample]
            + [(1 << 60) | i for i in range(config["n_common_objects"])]
            + [(1 << 61) | i for i in range(config["n_hot_objects"])])


async def _drive(launcher, cell: dict, seed: int, seconds: float,
                 annotate) -> dict:
    config, traffic = cell["config"], cell["traffic"]
    n = config["n_replicas"]
    workload = _workload(config, traffic)
    capped = traffic["arrival"] == "cap"
    load = loadgen.ClientSet(
        n=n, first_gid=n, count=traffic["clients"], epoch=launcher.epoch,
        seed=seed, run_dir=launcher.run_dir, batch_size=config["batch_size"],
        workload=workload,
        inflight=traffic["inflight_batches"] if capped else None)
    t_warm = load.now + traffic["connect_s"]
    t0 = t_warm + traffic["warmup_s"]
    t1 = t0 + seconds
    with annotate("bench.connect"):
        await loadgen.sleep_until(load, t_warm)
    # what the harness itself costs while it offers the load: its CPU time
    # (all threads) per second of wall time, and how late its loop wakes
    lag = asyncio.ensure_future(loadgen.loop_lag(load, t1))
    cpu0, wall0 = time.process_time(), load.now
    with annotate("bench.load"):
        if capped:
            await loadgen.run_capped(load, t1)
        else:
            rate_b = traffic["rate_ops_s"] / config["batch_size"] \
                / traffic["clients"]
            sched = [np.concatenate([
                loadgen.batch_schedule(seed, c, rate_b, t_warm, t0),
                loadgen.batch_schedule(seed, c + 1000, rate_b, t0, t1)])
                for c in range(traffic["clients"])]
            await loadgen.run_open(load, sched, t1)
    harness = {"cpu_share": (time.process_time() - cpu0)
               / (load.now - wall0), "loop_lag": await lag}
    with annotate("bench.drain"):
        await load.drain(t1 + DRAIN_S)
    records = [c.records() for c in load.clients]
    lateness = np.concatenate([np.asarray(c.lateness) for c in load.clients]
                              + [np.zeros(0)])
    await load.close()

    with annotate("bench.readback"):
        objs = _readback_objects(records, config, traffic, seed)
        rb = loadgen.ClientSet(
            n=n, first_gid=n + traffic["clients"], count=1,
            epoch=launcher.epoch, seed=seed, run_dir=launcher.run_dir,
            batch_size=config["batch_size"], workload=workload)
        for j in sorted(launcher.nodes):
            rb.clients[0].send_reads(objs, j)
        await rb.drain(rb.now + DRAIN_S)
        readback = rb.clients[0].records()
        await rb.close()
    return {"records": records, "readback": readback, "t0": t0, "t1": t1,
            "lateness": lateness, "harness": harness,
            "live": sorted(launcher.nodes)}


def _check(records, readback) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    rows = []
    unacked = unanswered = 0
    for r in records + [readback]:
        acked = np.isfinite(r["ack"])
        unacked += int(np.count_nonzero(~acked))
        for i in range(len(r["obj"])):
            if r["kind"][i] == "w":
                rows.append((int(r["obj"][i]), "w", r["value"][i],
                             r["sent"][i], r["ack"][i]))
            elif acked[i] and r["answered"][i]:
                rows.append((int(r["obj"][i]), "r", r["result"][i],
                             r["sent"][i], r["ack"][i]))
            elif acked[i]:
                unanswered += 1
    read_objs = {row[0] for row in rows if row[1] == "r"}
    bad = regmodel.check_history(row for row in rows if row[0] in read_objs)
    return {"checks": {"unacked_ops": [unacked, 0],
                       "unanswered_reads": [unanswered, 0],
                       "stale_objects": [len(bad), 0]},
            "why": sorted(bad.items())[:3],
            "objects_checked": len(read_objs),
            "writes_checked": sum(1 for row in rows
                                  if row[1] == "w" and row[0] in read_objs),
            "reads_checked": sum(1 for row in rows if row[1] == "r")}


def _window(records, t0: float, t1: float):
    due = np.concatenate([r["due"] for r in records])
    inside = (due >= t0) & (due < t1)
    return (due[inside], np.concatenate([r["ack"] for r in records])[inside],
            np.concatenate([r["path"] for r in records])[inside])


def _annotate_with(trace: bool):
    if trace:
        import jax
        return jax.profiler.TraceAnnotation
    import contextlib
    return lambda name: contextlib.nullcontext()


def run_once(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, reorder: bool = False,
             root: Path = ROOT, t_start: Optional[float] = None, log=print):
    """One run: (the result object, the :class:`Run` its metrics read).
    ``reorder`` runs the program's frame-reorder mutation twin (the
    control)."""
    from repro.transport import ClusterConfig, ClusterLauncher

    cell = load_cell(workload, root)
    config, traffic = cell["config"], cell["traffic"]
    annotate = _annotate_with(trace)
    work = Path(tempfile.mkdtemp(prefix="woc-bench-"))
    cfg = ClusterConfig(
        protocol="woc", n_replicas=config["n_replicas"], n_clients=0,
        t_fail=config["t_fail"], seed=seed, batch_size=config["batch_size"],
        max_queue=config["max_queue"], hb_scale=config["hb_scale"],
        trace=trace, sample_every=traffic["trace_sample_every"],
        reorder=reorder, run_dir=str(work / "cluster"))
    launcher = ClusterLauncher(cfg)
    profiling = False
    try:
        device = find_device(cell["chips"], require_tpu)
        if trace:
            import fold
            from repro import compile_cache
            compile_cache.enable()
            fold.warm(config["n_replicas"])
        # ClusterLauncher.start() with a longer wait: nine replicas, each
        # importing jax, can take more than its 15 s to boot on a busy host
        launcher.epoch = time.time()
        for i in range(config["n_replicas"]):
            launcher.start_node(i)
        launcher.wait_for_ports(range(config["n_replicas"]), timeout=BOOT_S)
        if trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            for k, v in PROFILE_OPTIONS.items():
                setattr(opts, k, v)
            jax.profiler.start_trace(str(work / "profile"),
                                     profiler_options=opts)
            profiling = True
        # the load generator must not stall on its own garbage collector:
        # a full collection over the window's ops pauses it for tens of ms
        gc.collect()
        gc.disable()
        try:
            out = asyncio.run(_drive(launcher, cell, seed, seconds,
                                     annotate))
        finally:
            gc.enable()
        with annotate("bench.stop"):
            launcher.stop()
        stats = device["_device"].memory_stats() or {}
        node_stats = [json.loads(f.read_text()) for f in
                      sorted(launcher.run_dir.glob("node-*.stats.json"))]
        events = []
        if trace:
            with annotate("bench.reduce"):
                from repro.obs.critical_path import analyze_events
                from repro.obs.spans import canonical_events
                for i in range(config["n_replicas"]):
                    tf = launcher.run_dir / f"node-{i}.trace.jsonl"
                    if tf.exists():
                        with open(tf) as f:
                            events.extend(tuple(json.loads(line))
                                          for line in f)
                events = canonical_events(events)
                report = analyze_events(events, window=(out["t0"], out["t1"]))
                arrivals, weights = fold.vote_rows(
                    events, config["n_replicas"], config["t_fail"],
                    out["t0"], out["t1"])
            with annotate("bench.fold"):
                folded = fold.replay(arrivals, weights)
            jax.profiler.stop_trace()
            profiling = False
            import devtrace
            reduced = devtrace.reduce_trace_dir(work / "profile")
            log(f"[fold] instances={len(arrivals)} committed={folded}",
                file=sys.stderr)
    finally:
        if profiling:
            import jax
            jax.profiler.stop_trace()
        launcher.stop()
        shutil.rmtree(work, ignore_errors=True)

    records = out["records"]
    t0, t1 = out["t0"], out["t1"]
    due, ack, path = _window(records, t0, t1)
    started = T_START if t_start is None else t_start
    run = Run(t0=t0, t1=t1, setup_s=launcher.epoch + t0 - started,
              due=due, ack=ack, path=path,
              acks=np.concatenate([r["ack"] for r in records]),
              acked_total=sum(int(np.isfinite(r["ack"]).sum())
                              for r in records + [out["readback"]]),
              node_stats=node_stats)
    if trace:
        run.report = report
        run.device = reduced

    check = _check(records, out["readback"])
    lat = out["lateness"]
    if lat.size:
        log(f"[generator] batches={lat.size} late_p50_ms="
            f"{np.percentile(lat, 50) * 1e3} late_p99_ms="
            f"{np.percentile(lat, 99) * 1e3} late_max_ms={lat.max() * 1e3}",
            file=sys.stderr)
    lags = out["harness"]["loop_lag"]
    log(f"[harness] cpu_share={out['harness']['cpu_share']} loop_lag_p50_ms="
        f"{np.percentile(lags, 50) * 1e3} loop_lag_p99_ms="
        f"{np.percentile(lags, 99) * 1e3} loop_lag_max_ms={lags.max() * 1e3}",
        file=sys.stderr)
    log(f"[window] ops={len(due)} acked={int(np.isfinite(ack).sum())} "
        f"replicas_live={out['live']} objects_checked="
        f"{check['objects_checked']} writes_checked={check['writes_checked']}"
        f" reads_checked={check['reads_checked']}", file=sys.stderr)
    log("[replicas] " + " ".join(
        f"{s['node']}:applied={s['applied']},isolated={s['isolated']},"
        f"recovering={s['recovering']},dropped="
        f"{sum(c['dropped'] for c in s['channels'])}" for s in node_stats),
        file=sys.stderr)
    for obj, why in check["why"]:
        log(f"[stale] object {obj:#x}: {why}", file=sys.stderr)

    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for name in names:
        value = metric_reader(name, root)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell["units"][name]}
    checks = check["checks"]
    correct = len(due) > 0 and all(v <= lim for v, lim in checks.values())
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": correct,
              "attempted": len(due) + len(out["readback"]["obj"]),
              "failed": int(np.count_nonzero(~np.isfinite(ack)))
              + checks["unanswered_reads"][0],
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    for name, (value, limit) in checks.items():
        log(f"[check] {name} = {value} (limit {limit})", file=sys.stderr)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result, _ = run_once(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
