from perfbench.harness.readers import compiles_in_window as read  # noqa: F401
