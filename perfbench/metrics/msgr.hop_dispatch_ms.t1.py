from perfbench.harness.offcpu_readers import hop_dispatch_ms as read  # noqa: F401
