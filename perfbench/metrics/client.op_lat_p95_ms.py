"""95th percentile of the same latencies as op_lat_p50_ms."""
from perfbench.harness.readers import op_latency_ms


def read(r):
    return op_latency_ms(r, 0.95)
