"""Striper — RAID-0 of a byte stream over RADOS objects
(src/osdc/Striper.cc + src/libradosstriper/ analog; the framework's
"long-context" scaling primitive: one large logical stream spread over
many independently-placed objects so reads/writes parallelize across
PGs and OSDs).

Layout follows file_layout_t: stripe_unit bytes per strip, stripe_count
objects per stripe row, object_size bytes per object.  Logical offset →
(object number, object offset) exactly as Striper::file_to_extents.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class StripeLayout:
    """file_layout_t subset."""

    stripe_unit: int = 1 << 16
    stripe_count: int = 4
    object_size: int = 1 << 22

    def __post_init__(self):
        if self.object_size % self.stripe_unit:
            raise ValueError("object_size must be a multiple of "
                             "stripe_unit")

    def num_objects(self, size: int) -> int:
        """Backing objects covering a logical size (object-map width).
        Within a partial object set the first ceil(rem/su) stripe units
        land on min(sc, that) distinct objects."""
        if size <= 0:
            return 0
        su, sc = self.stripe_unit, self.stripe_count
        set_bytes = self.object_size * sc
        full_sets, rem = divmod(size, set_bytes)
        n = full_sets * sc
        if rem:
            blocks = -(-rem // su)
            n += min(sc, blocks)
        return n

    def object_logical_extents(self, objno: int, size: int):
        """[(logical_off, len)] of the bytes objno backs, clamped to the
        image size — the inverse of extents() at stripe-unit granularity
        (Striper::extent_to_file).  Adjacent units are coalesced."""
        su, sc = self.stripe_unit, self.stripe_count
        per_obj = self.object_size // su
        objectsetno, stripepos = divmod(objno, sc)
        out: list[tuple[int, int]] = []
        for u in range(per_obj):
            stripeno = objectsetno * per_obj + u
            logical = (stripeno * sc + stripepos) * su
            if logical >= size:
                break
            n = min(su, size - logical)
            if out and out[-1][0] + out[-1][1] == logical:
                out[-1] = (out[-1][0], out[-1][1] + n)
            else:
                out.append((logical, n))
        return out

    def extents(self, offset: int, length: int):
        """[(objno, obj_off, len)] covering [offset, offset+length)
        (Striper::file_to_extents)."""
        su, sc = self.stripe_unit, self.stripe_count
        per_obj = self.object_size // su    # stripe units per object
        out = []
        pos = offset
        end = offset + length
        while pos < end:
            blockno = pos // su
            stripeno = blockno // sc
            stripepos = blockno % sc
            objectsetno = stripeno // per_obj
            objectno = objectsetno * sc + stripepos
            block_off = pos % su
            obj_off = (stripeno % per_obj) * su + block_off
            n = min(su - block_off, end - pos)
            out.append((objectno, obj_off, n))
            pos += n
        return out


class Striper:
    """Pure layout math, shared by StripedObject / rbd."""

    def __init__(self, layout: StripeLayout):
        self.layout = layout

    def object_name(self, prefix: str, objno: int) -> str:
        return f"{prefix}.{objno:016x}"


class StripedObject:
    """A large logical object striped over an IoCtx
    (libradosstriper surface: write/read/truncate-ish + size)."""

    SIZE_KEY = "striper.size"

    def __init__(self, ioctx, name: str,
                 layout: StripeLayout | None = None, meta_io=None):
        self.io = ioctx
        #: the pool of the size object (an omap object): an erasure-
        #: coded data pool holds no omap, so rbd keeps it beside the
        #: image's header
        self.meta_io = meta_io or ioctx
        self.name = name
        self.layout = layout or StripeLayout()
        self.striper = Striper(self.layout)

    def _size_obj(self) -> str:
        return f"{self.name}.meta"

    def size(self) -> int:
        try:
            omap = self.meta_io.get_omap(self._size_obj())
        except OSError:
            return 0
        blob = omap.get(self.SIZE_KEY)
        return int(blob.decode()) if blob else 0

    def _set_size(self, size: int) -> None:
        self.meta_io.set_omap(self._size_obj(),
                              {self.SIZE_KEY: str(size).encode()})

    def write(self, data: bytes, offset: int = 0) -> None:
        pos = 0
        for objno, obj_off, n in self.layout.extents(offset, len(data)):
            self.io.write(self.striper.object_name(self.name, objno),
                          data[pos:pos + n], offset=obj_off)
            pos += n
        if offset + len(data) > self.size():
            self._set_size(offset + len(data))

    def read(self, offset: int = 0, length: int = 0,
             snapid: int = 0) -> bytes:
        """snapid reads each backing object as of that pool snapshot
        (librados snap_set analog); pass an explicit length then — the
        size object reflects the CURRENT size, not the snap's."""
        total = self.size()
        if length <= 0 or offset + length > total and not snapid:
            length = max(0, total - offset)
        parts = []
        for objno, obj_off, n in self.layout.extents(offset, length):
            try:
                chunk = self.io.read(
                    self.striper.object_name(self.name, objno),
                    length=n, offset=obj_off, snapid=snapid)
            except OSError:
                chunk = b""
            if len(chunk) < n:          # sparse hole: zero-fill
                chunk = chunk + bytes(n - len(chunk))
            parts.append(chunk)
        return b"".join(parts)

    def truncate(self, new_size: int) -> None:
        """Zero the bytes beyond new_size and shrink the logical size
        (discarded data must not resurface on a later grow)."""
        total = self.size()
        if new_size < total:
            self.write(bytes(total - new_size), offset=new_size)
        self._set_size(new_size)

    def remove(self) -> None:
        total = self.size()
        seen = set()
        for objno, _off, _n in self.layout.extents(0, max(total, 1)):
            seen.add(objno)
        for objno in seen:
            try:
                self.io.remove(self.striper.object_name(self.name,
                                                        objno))
            except OSError:
                pass
        try:
            self.meta_io.remove(self._size_obj())
        except OSError:
            pass
