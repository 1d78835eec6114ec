"""Growth of the stores' files over the window per user byte
acknowledged in it."""
from perfbench.harness.readers import window_ops


def read(r):
    user = window_ops(r) * int(r.cell.traffic["object_size"])
    return r.delta("store.file_bytes") / user if user else None
