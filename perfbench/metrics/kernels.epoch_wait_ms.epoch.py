from perfbench.harness.span_readers import kernels_wait_ms as read  # noqa: F401
