"""User MB/s acknowledged inside the window, over the window's length;
the pipeline is full at both edges (harness/closed_loop.py)."""
from perfbench.harness.window import rate_mb_s


def read(r):
    return rate_mb_s(r.log.acks, r.log.t_open, r.log.seconds,
                     int(r.cell.traffic["object_size"]))
