"""Encode-engine submissions by the OSDs per client op acknowledged in
the window (1 = one encode call per object)."""
from perfbench.harness.readers import window_ops


def read(r):
    ops = window_ops(r)
    return r.delta("osd.ec_dispatch_submits") / ops if ops else None
