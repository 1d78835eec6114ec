from perfbench.harness.span_readers import engine_path_ms as read  # noqa: F401
