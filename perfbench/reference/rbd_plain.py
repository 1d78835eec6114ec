"""Plain reference for the rbd cells: what an image holds, and what the
shards of one stripe of its data objects hold, after a run's writes.

The image is a byte array of the seeded prefill with every acknowledged
write laid over it in the order of the acknowledgements (one writer at
depth 1: the order of submission).  Its data objects are librbd's
striping at stripe_count 1: object n holds the image's bytes
[n * object_size, (n + 1) * object_size).  A stripe's shards are
perfbench/reference/rs_plain.py's.  Imports nothing of the program.
"""

from __future__ import annotations

from perfbench.reference import rs_plain


class PlainImage:
    def __init__(self, prefill: bytes, object_size: int):
        self.data = bytearray(prefill)
        self.object_size = object_size

    def write(self, offset: int, data: bytes) -> None:
        if offset + len(data) > len(self.data):
            raise ValueError("write past the end of the image")
        self.data[offset:offset + len(data)] = data

    def read(self, offset: int, length: int) -> bytes:
        return bytes(self.data[offset:offset + length])

    def stripe_of(self, offset: int, width: int) -> tuple[int, int]:
        """(data object, stripe of that object) that image byte
        `offset` lies in, stripes `width` bytes wide."""
        return offset // self.object_size, \
            offset % self.object_size // width

    def stripe_shards(self, objno: int, stripe: int, k: int, m: int,
                      stripe_unit: int) -> list[bytes]:
        """The k + m chunks of one stripe of data object `objno`."""
        width = k * stripe_unit
        lo = objno * self.object_size + stripe * width
        return rs_plain.shards_of(self.read(lo, width), k, m, stripe_unit)
