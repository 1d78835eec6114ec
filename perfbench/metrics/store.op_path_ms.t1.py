from perfbench.harness.span_readers import store_path_ms as read  # noqa: F401
