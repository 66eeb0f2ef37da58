"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy time, time
per device operation, and the idle gaps named by what the host was doing.

* Chips are the planes named ``/device:TPU:<i>``; other ``/device:``
  planes, such as ``/device:CUSTOM:Megascale Trace``, are not. On each
  chip the operations are the events of the ``XLA Ops`` line (every line
  where a plane has no such line). Busy time is the union of their
  intervals inside the window, averaged over the chips.
* The window is the span of the host's ``bench.*`` annotations, which the
  harness opens around each phase of a traced run. An idle stretch of the
  device is split where those phases change and each piece is named after
  the phase that covers it. Each phase also gets its own busy time and
  span, so that a metric can read one phase (the load) alone.
"""

from __future__ import annotations

import collections
import re
from pathlib import Path
from typing import Dict, List, Tuple

CHIP_PLANE = re.compile(r"/device:(TPU|GPU):\d+")
PHASE_PREFIX = "bench."
OPS_LINE = "XLA Ops"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def reduce_planes(planes) -> dict:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` that carry ``name``, ``start_ns`` and
    ``duration_ns`` (``jax.profiler.ProfileData.planes``)."""
    phases: List[Tuple[float, float, str]] = []
    device_ops: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in planes:
        if CHIP_PLANE.fullmatch(plane.name):
            lines = list(plane.lines)
            chosen = [ln for ln in lines if ln.name == OPS_LINE] or lines
            device_ops[plane.name] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ln in chosen for ev in ln.events if ev.duration_ns > 0]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(PHASE_PREFIX):
                        phases.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, ev.name))
    if not phases:
        raise ValueError("the trace holds no bench.* phase annotation")
    if not device_ops:
        raise ValueError("the trace holds no device plane")
    lo = min(p[0] for p in phases)
    hi = max(p[1] for p in phases)
    window_ns = hi - lo

    busy_ns = []
    by_name: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[str, float]] = []
    by_phase: Dict[str, List[float]] = {}
    for a, b, name in phases:
        by_phase.setdefault(name, [0.0, 0.0])[1] += (b - a) / 1e9
    for events in device_ops.values():
        inside = _clip([(a, b) for a, b, _ in events], lo, hi)
        busy = _union(inside)
        busy_ns.append(sum(b - a for a, b in busy))
        for p0, p1, name in phases:
            by_phase[name][0] += sum(
                b - a for a, b in _clip(busy, p0, p1)) / 1e9
        for a, b, name in events:
            if b > lo and a < hi:
                by_name[name] += (min(b, hi) - max(a, lo)) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            gaps.extend(_name_gap(g0, g1, phases))
    n_dev = len(device_ops)
    busy_s = sum(busy_ns) / n_dev / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_ns / 1e9,
        "device_ops": sorted(([k, v / n_dev] for k, v in by_name.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps),
                            key=lambda kv: -kv[1]),
        # phase -> [device busy seconds in it, averaged over chips; span]
        "phases": {k: [busy / n_dev, span]
                   for k, (busy, span) in by_phase.items()},
    }


def _name_gap(g0: float, g1: float, phases) -> List[Tuple[str, float]]:
    """Split the idle stretch [g0, g1) by the innermost phase covering it."""
    if g1 <= g0:
        return []
    cuts = sorted({g0, g1} | {x for a, b, _ in phases for x in (a, b)
                              if g0 < x < g1})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        covering = [p for p in phases if p[0] <= mid < p[1]]
        name = (min(covering, key=lambda p: p[1] - p[0])[2] if covering
                else "between phases")
        if pieces and pieces[-1][0] == name:
            pieces[-1] = (name, pieces[-1][1] + (b - a) / 1e9)
        else:
            pieces.append((name, (b - a) / 1e9))
    return pieces


def reduce_trace_dir(log_dir) -> dict:
    """Reduce the one ``*.xplane.pb`` a profiler session wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one xplane file under {log_dir}, "
                         f"found {len(found)}")
    return reduce_planes(ProfileData.from_file(str(found[0])).planes)
