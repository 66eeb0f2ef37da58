"""Peaks of each device kind, and the work one call of a kernel needs.

``peaks.json`` holds the published peaks keyed by ``device_kind``; a kind
that is not there is an error, never a default. A kernel's roofline share
is the least time the chip could take for the work (operations over
``flops_s``, or bytes over ``hbm_bytes_s``, whichever is larger) divided by
the kernel's device time from the trace; the metric that reports it comes
with the first program path that runs the kernel.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]


def quorum_commit_cost(instances: int, replicas: int) -> Tuple[int, int]:
    """Float operations and HBM bytes of one ``quorum_commit`` call over
    ``instances`` quorums of ``replicas`` votes each.

    The kernel (``repro.kernels.quorum_commit``) compares every vote with
    every other vote of its instance: per pair a less-than, an equality,
    a select of the weight and two adds (weight before it and its rank),
    5 n^2; per vote the threshold add, the crossing compare and the three
    masked minimums with their selects, 8 n. It reads the f32 arrival and
    weight of each vote and writes four 4-byte results per instance.
    Padding to whole tiles is not counted: it is not work the decision
    needs.
    """
    n = replicas
    flops = instances * (5 * n * n + 8 * n)
    nbytes = instances * (2 * 4 * n + 4 * 4)
    return flops, nbytes
