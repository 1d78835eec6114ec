"""90th percentile of the window's op latencies (Little's law ties
its mean to depth / rate)."""
from perfbench.harness.readers import op_latency_ms


def read(r):
    return op_latency_ms(r, 0.90)
