from perfbench.harness.readers import epoch_apply_ms


def read(r):
    return epoch_apply_ms(r, 0.95)
