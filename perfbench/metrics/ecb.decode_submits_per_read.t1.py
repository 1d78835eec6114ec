"""Decode-engine submissions by the OSDs per read acknowledged in the
window: how much of the traffic the rebuild carries (with 2 of 12 OSDs
down an object has lost a data shard with probability 1 - C(4,2)/C(12,2),
0.91)."""
from perfbench.harness.readers import window_ops


def read(r):
    ops = window_ops(r)
    return r.delta("osd.ec_decode_submits") / ops if ops else None
