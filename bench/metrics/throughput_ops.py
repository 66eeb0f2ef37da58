"""Ops acknowledged inside the window, per second of the window."""

from winstats import window_rate


def read(run):
    return window_rate(run.acks, run.t0, run.t1)
