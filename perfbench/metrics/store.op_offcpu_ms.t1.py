from perfbench.harness.offcpu_readers import store_offcpu_ms as read  # noqa: F401
