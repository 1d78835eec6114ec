from perfbench.harness.readers import hbm_peak_gb as read  # noqa: F401
