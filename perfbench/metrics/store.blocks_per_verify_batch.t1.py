"""Blocks whose stored checksum one batched digest call verified, over
the window's shard reads."""


def read(r):
    batches = r.delta("store.read_verify_batches")
    return (r.delta("store.read_verify_blocks") / batches
            if batches else None)
