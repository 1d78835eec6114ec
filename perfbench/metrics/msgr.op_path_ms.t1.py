from perfbench.harness.span_readers import msgr_path_ms as read  # noqa: F401
