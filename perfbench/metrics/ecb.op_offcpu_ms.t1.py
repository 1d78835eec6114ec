from perfbench.harness.offcpu_readers import ecb_offcpu_ms as read  # noqa: F401
