"""Median submit-to-ack latency of the ops acknowledged in the window."""
from perfbench.harness.readers import op_latency_ms


def read(r):
    return op_latency_ms(r, 0.50)
