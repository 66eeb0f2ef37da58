"""Share of the load phase (``bench.load``, the window and its warm-up) in
which no operation ran on the device, from the profiler trace of the
process that holds the chip. Only the program's own device work can fall
in it: the harness's fold of the window's quorums runs after it."""


def read(run):
    if run.device is None or "bench.load" not in run.device["phases"]:
        return None
    busy, span = run.device["phases"]["bench.load"]
    return 1.0 - busy / span if span > 0 else None
