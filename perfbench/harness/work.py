"""What a kernel has to do, counted from shapes and traffic alone, and
the least time a chip of given peaks could take for it.

None of these functions knows which kernel, or whether the device or the
host, does the work: a later change that replaces a kernel is measured
against the same count.
"""

from __future__ import annotations

#: integer operations of one straw2 draw, counted from the algorithm
#: (src/crush/hash.c, mapper.c:334-359), not from any kernel:
#: crush_hash32_3 is 5 mixes of 9 steps, each step two subtractions, a
#: shift and an xor (180), plus 3 xors to seed it and a mask; crush_ln is
#: two table look-ups, a 64-bit product, five shifts and three adds (11);
#: then one subtraction, one 64-bit division and one compare-and-select.
STRAW2_OPS_PER_DRAW = 180 + 4 + 11 + 3


def gf_encode_work(stripes: int, k: int, m: int, stripe_unit: int) -> dict:
    """Reed-Solomon encode of `stripes` stripes of k data chunks of
    `stripe_unit` bytes into m parity chunks: every parity byte is a sum
    of k products in GF(2^8), one multiply and one add each."""
    col = stripes * stripe_unit             # byte columns
    return {"ops": 2 * k * m * col,
            "bytes": (k + m) * col}          # k read, m written


def digest_work(blocks: int, block_size: int) -> dict:
    """A crc32 per block of `block_size` bytes: each byte is read once;
    4 bytes of digest are written per block."""
    return {"ops": 0, "bytes": blocks * (block_size + 4)}


def crush_work(pgs: int, numrep: int, bucket_sizes) -> dict:
    """One straw2 draw per item of every bucket on the path from the
    root to a leaf, for each replica of each PG — the least a placement
    takes, with no retry counted.  Per PG a 4-byte input and numrep
    4-byte results."""
    draws = pgs * numrep * sum(bucket_sizes)
    return {"ops": draws * STRAW2_OPS_PER_DRAW, "draws": draws,
            "bytes": pgs * 4 * (1 + numrep)}


def least_seconds(work: dict, peaks: dict, chips: int = 1) -> tuple[float, str]:
    """The roofline: the larger of operations over the peak rate (the
    chip's integer peak, the only published rate for integer work) and
    bytes over the memory bandwidth, and which of the two it is."""
    by_ops = work["ops"] / (peaks["int8_op_s"] * chips)
    by_bytes = work["bytes"] / (peaks["hbm_bytes_s"] * chips)
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")


def roofline_share(work: dict, peaks: dict, device_seconds: float,
                   chips: int = 1) -> float | None:
    """Percent of the roofline reached; nothing where no device time
    was seen or no work was due."""
    least, _bound = least_seconds(work, peaks, chips)
    if device_seconds <= 0 or least <= 0:
        return None
    return 100.0 * least / device_seconds
