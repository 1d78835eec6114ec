from perfbench.harness.offcpu_readers import hop_send_ms as read  # noqa: F401
