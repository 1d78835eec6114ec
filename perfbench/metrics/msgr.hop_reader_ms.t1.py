from perfbench.harness.offcpu_readers import hop_reader_ms as read  # noqa: F401
