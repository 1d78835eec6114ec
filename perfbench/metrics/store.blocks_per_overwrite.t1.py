"""Blocks the twelve OSDs' BlueStores wrote to their block files per
overwrite acknowledged in the window: one a shard for a 4 KiB write
inside one chunk, 12 (a design that rewrites whole 512 KiB shards
writes 1,536)."""
from perfbench.harness.readers import window_ops


def read(r):
    if "store.write_run_blocks" not in r.after:
        return None
    ops = window_ops(r)
    return r.delta("store.write_run_blocks") / ops if ops else None
