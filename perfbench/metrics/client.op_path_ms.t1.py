from perfbench.harness.span_readers import client_path_ms as read  # noqa: F401
