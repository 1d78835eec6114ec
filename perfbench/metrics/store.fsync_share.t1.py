from perfbench.harness.span_readers import fsync_share as read  # noqa: F401
