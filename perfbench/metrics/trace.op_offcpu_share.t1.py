from perfbench.harness.offcpu_readers import offcpu_share as read  # noqa: F401
