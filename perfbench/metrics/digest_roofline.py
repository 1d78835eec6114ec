"""Digest program device time in the traced slice against the time the
memory bandwidth allows for the blocks its calls checksummed: the calls
seen in the slice, each with the window's mean blocks per batch."""
from perfbench.harness import work


def read(r):
    if r.trace is None or not r.delta("store.csum_batches"):
        return None
    programs = r.cell.config["programs"]["digest"]
    per_call = (r.delta("store.batched_csum_blocks")
                / r.delta("store.csum_batches"))
    w = work.digest_work(r.trace.calls_of(*programs) * per_call,
                         int(r.cell.config["deployment"]["store_block"]))
    return work.roofline_share(w, r.peaks, r.trace.seconds_of(*programs))
