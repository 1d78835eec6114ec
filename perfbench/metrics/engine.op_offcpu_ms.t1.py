from perfbench.harness.offcpu_readers import engine_offcpu_ms as read  # noqa: F401
