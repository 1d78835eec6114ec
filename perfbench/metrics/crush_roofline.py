from perfbench.harness.readers import crush_roofline as read  # noqa: F401
