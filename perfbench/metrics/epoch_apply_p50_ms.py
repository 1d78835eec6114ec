"""Median time of update_to(new, from_epoch) over the epochs applied in
the window."""
from perfbench.harness.readers import epoch_apply_ms


def read(r):
    return epoch_apply_ms(r, 0.50)
