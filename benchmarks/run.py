"""Benchmark driver: one module per paper table/figure + beyond-paper
benches. Writes CSVs to experiments/bench/ and prints a paper-claim
validation summary.
``python -m benchmarks.run [--quick] [--only NAME] [--jobs N]``

``--quick`` threads a reduced-size mode through every suite (smaller
sweeps, fewer ops/batches/trials) so CI smoke steps and laptops can run
the full driver in minutes instead of hours. Quick mode trades
claim-validation fidelity for speed: the reduced runs sit in noisier
queueing regimes, so treat quick-mode [MISS] lines as a prompt to re-run
the full suite, not as a regression verdict. The ``engine`` suite is the
exception — its claims are sized to hold in quick mode (CI runs
``--quick --only engine``).
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time

from benchmarks import (bench_batch_size, bench_client_scaling,
                        bench_conflict_rate, bench_engine,
                        bench_fault_recovery, bench_grad_quorum,
                        bench_parallel_shard, bench_payload,
                        bench_quorum_kernel, bench_server_scaling,
                        bench_shard_scaling, bench_weights,
                        bench_workloads)
from repro import compile_cache

SUITES = [
    ("engine", bench_engine),
    ("weights_tables", bench_weights),
    ("quorum_kernel", bench_quorum_kernel),
    ("grad_quorum", bench_grad_quorum),
    ("conflict_rate", bench_conflict_rate),
    ("batch_size", bench_batch_size),
    ("client_scaling", bench_client_scaling),
    ("server_scaling", bench_server_scaling),
    ("workloads", bench_workloads),
    ("shard_scaling", bench_shard_scaling),
    ("parallel", bench_parallel_shard),
    ("payload", bench_payload),
    ("faults", bench_fault_recovery),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/bench")
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="reduced batches/clients/sweeps in every suite "
                         "(CI smoke / laptop mode)")
    ap.add_argument("--jobs", type=int, default=0,
                    help="worker processes for parallel-simulation suites "
                         "(0 = auto: min(groups, cores)); suites that do "
                         "not take a jobs parameter ignore it")
    ap.add_argument("--trace", action="store_true",
                    help="export Perfetto-loadable TRACE_*.json span "
                         "artifacts from trace-aware suites (see "
                         "repro.obs); suites that do not take a trace "
                         "parameter ignore it")
    args = ap.parse_args()
    compile_cache.enable()

    all_lines = []
    t00 = time.time()
    for name, mod in SUITES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        kwargs = {"quick": args.quick}
        params = inspect.signature(mod.run).parameters
        if "jobs" in params:
            kwargs["jobs"] = args.jobs
        if "trace" in params:
            kwargs["trace"] = args.trace
        lines = mod.run(args.out, **kwargs)
        for ln in lines:
            print("  " + ln, flush=True)
        print(f"  ({time.time()-t0:.0f}s)", flush=True)
        all_lines += lines

    misses = [l for l in all_lines if l.startswith("[MISS]")]
    print(f"\n=== paper-claim validation: "
          f"{len(all_lines) - len(misses)}/{len(all_lines)} PASS "
          f"({time.time()-t00:.0f}s total) ===")
    for m in misses:
        print("  " + m)
    return 1 if misses else 0


if __name__ == "__main__":
    sys.exit(main())
