from perfbench.harness.span_readers import named_share as read  # noqa: F401
