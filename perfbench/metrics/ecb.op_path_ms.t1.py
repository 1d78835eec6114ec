from perfbench.harness.span_readers import ecb_path_ms as read  # noqa: F401
