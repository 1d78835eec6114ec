"""Blocks checksummed per batched digest call over the window."""


def read(r):
    batches = r.delta("store.csum_batches")
    return (r.delta("store.batched_csum_blocks") / batches
            if batches else None)
