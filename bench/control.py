"""The control of ``correct``: a cell run with the program's frame-reorder
mutation twin (``ClusterConfig.reorder``: every 4th frame on a replica link
displaced past the next 12, breaking per-link FIFO). Run on the chip's host
at the cell's own size; each seed must come out ``correct: false``:

    python3 bench/control.py --workload w9.mix90.cap --seconds 20 \\
        --seeds 101 102 103

Prints one line per seed with the numbers compared and ``correct``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    for seed in args.seeds:
        result, _ = run.run_once(args.workload, seed, args.seconds, False,
                                 reorder=True, t_start=time.time())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "reorder",
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
