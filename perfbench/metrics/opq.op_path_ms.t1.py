from perfbench.harness.span_readers import opq_path_ms as read  # noqa: F401
