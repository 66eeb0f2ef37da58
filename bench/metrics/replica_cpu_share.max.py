"""CPU time of the busiest replica process (all its threads) per second
of the window: each replica's growth of ``time.process_time`` between its
first and last ``host`` row in the window, over the time between them."""

from hostrows import window_rows


def read(run):
    shares = [(r[-1, 1] - r[0, 1]) / (r[-1, 0] - r[0, 0])
              for r in window_rows(run.node_stats, "host", run.t0, run.t1)
              if r[-1, 0] > r[0, 0]]
    return max(shares) if shares else None
