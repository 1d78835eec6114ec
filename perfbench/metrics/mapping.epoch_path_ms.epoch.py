from perfbench.harness.span_readers import mapping_path_ms as read  # noqa: F401
