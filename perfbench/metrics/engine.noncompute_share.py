from perfbench.harness.readers import noncompute_share as read  # noqa: F401
