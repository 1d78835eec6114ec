"""What a Clay (k, m, d = k + m - 1) rebuild has to do, counted from
shapes alone (`work.py`'s rules: no kernel, device or host is known
here)."""

from __future__ import annotations

#: GF(2^8) operations of one 2 x 2 pair transform of two bytes: four
#: products and two sums
PAIR_OPS = 6


def clay_decode_work(stripes: int, k: int, m: int, targets: float,
                     su: int, alpha: int) -> dict:
    """The layered decode of `stripes` stripes from k chunks of `su`
    bytes (alpha sub-chunks each) to `targets` rebuilt ones.

    bytes = stripes * (k + targets) * su: the k chunks read once, the
    targets written once.

    ops = stripes * su * (2 * k * m + PAIR_OPS * (k + targets) * (q - 1) / q),
    q = m: on every plane the plane code's product of the k nodes in hand
    onto the n - k = m others, a multiply and an add for each of k * m
    coefficients and each of the su / alpha byte columns of the plane's
    sub-chunk (alpha planes: su columns in all); and one pair transform
    for each coupled byte uncoupled (the k chunks read) or coupled back
    (the targets), a fraction (q - 1) / q of a chunk's bytes (a
    sub-chunk whose digit z_y equals its node's x is its own pair)."""
    q = m
    del alpha           # the count does not depend on how a chunk is cut
    per_stripe = su * (2 * k * m + PAIR_OPS * (k + targets) * (q - 1) / q)
    return {"ops": stripes * per_stripe,
            "bytes": stripes * (k + targets) * su}
