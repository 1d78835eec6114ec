"""An RBD image whose data objects live in an erasure-coded pool with
allow_ec_overwrites, written by one fio-style writer — fio's rbd engine
doing `randwrite`, `bs=4k`, `iodepth=1`, as CBT's librbdfio runs it.

The deployment is ec_pool.py's cluster and k=8 m=4 pool, set up as the
Ceph documentation's "Erasure Coding with Overwrites" says: `osd pool
set <ecpool> allow_ec_overwrites true`, a replicated pool for the
image, `rbd create --data-pool <ecpool>`.  The image is prefilled with
whole-object writes, then overwritten 4 KiB at a time at offsets drawn
from the seed, through ``Image.aio_write`` (librbd's rbd_aio_write).
The check reads the whole image back through librbd against
perfbench/reference/rbd_plain.py, and looks into the OSDs' stores: every
touched stripe's parity against perfbench/reference/rs_plain.py of its
stored data chunks, those against the plain image, and the touched
blocks' stored checksums against zlib's crc32.
"""

from __future__ import annotations

import inspect
import zlib

import numpy as np

from perfbench.harness import closed_loop
from perfbench.harness.cell import Check
from perfbench.reference import payloads, rbd_plain, rs_plain
from perfbench.systems import ec_pool

SPANS = ec_pool.SPANS + ("ec rmw gather",)
TRAFFIC_KIND = "closed_loop_rbd_write"
#: the OSD's counters of the overwrite path; a program that lacks one
#: reads 0
OSD_COUNTERS = ("ec_rmw_writes", "ec_rmw_read_bytes", "ec_rmw_decodes",
                "ec_rmw_gather")
IMAGE = "perfbench_image"


class System(ec_pool.System):
    def __init__(self, cell, seed: int, span=None):
        super().__init__(cell, seed, span)
        dep, tr = cell.config["deployment"], cell.traffic
        self.image_size = int(dep["image_size"])
        #: the image's data objects (rbd's `order`); `obj_size` is the
        #: traffic's, the bytes of one write (fio's bs)
        self.rbd_obj = 1 << int(dep["image_order"])
        self.n_objects = self.image_size // self.rbd_obj
        self.bs = self.obj_size
        if (int(dep["image_stripe_unit"]) != self.rbd_obj
                or int(dep["image_stripe_count"]) != 1
                or self.image_size % self.rbd_obj or self.bs % 4096):
            raise SystemExit(
                f"perfbench: {cell.config_name}: the image has to be "
                f"whole objects striped one by one, and {cell.traffic_name}"
                f" has to write whole 4 KiB blocks")
        self.img = None
        self.model = None
        self.prefill_log = None
        #: overwrite index -> (image offset, bytes)
        self.writes: dict[int, tuple[int, bytes]] = {}
        self._at_prefill: dict = {}
        self.read_back = 0

    # -- the deployment -----------------------------------------------------

    def setup(self) -> None:
        _check_program()
        super().setup()
        dep, tr = self.cell.config["deployment"], self.cell.traffic
        client = self.io.client
        # first thing: the documented steps, stopping at a refusal
        if dep["allow_ec_overwrites"]:
            rc, out = client.mon_command({
                "prefix": "osd pool set", "pool": str(self.io.pool_id),
                "var": "allow_ec_overwrites", "val": "true"})
            if rc != 0:
                raise SystemExit(f"perfbench: allow_ec_overwrites refused: "
                                 f"{out}")
        meta = self.cluster.create_pool(
            client, pool_type="replicated",
            size=int(dep["metadata_pool_size"]),
            pg_num=int(dep["metadata_pool_pg_num"]), epoch_timeout=120.0)
        from ceph_tpu.rbd import Image
        self.img = Image.create(
            client.open_ioctx(meta), IMAGE, size=self.image_size,
            order=int(dep["image_order"]),
            stripe_unit=int(dep["image_stripe_unit"]),
            stripe_count=int(dep["image_stripe_count"]),
            features=list(dep["image_features"]), data_pool=self.io)
        # librbd takes the exclusive lock on the first write
        self.img.lock_acquire("perfbench-fio")
        self.model = rbd_plain.PlainImage(
            b"".join(payloads.payload(self.seed, i, self.rbd_obj)
                     for i in range(self.n_objects)), self.rbd_obj)
        self.prefill_log = closed_loop.run_all(
            self._prefill, self.n_objects, depth=int(tr["prefill_depth"]),
            op_timeout=float(tr.get("op_timeout_s", 300.0)))
        if self.prefill_log.failed or len(
                self.prefill_log.acks) != self.n_objects:
            raise RuntimeError(f"{self.prefill_log.failed} of "
                               f"{self.n_objects} prefill writes failed")
        self._at_prefill = self.counters()

    def _prefill(self, index: int):
        lo = index * self.rbd_obj
        return self.img.aio_write(self.model.read(lo, self.rbd_obj), lo)

    def draw(self, index: int) -> tuple[int, bytes]:
        """Overwrite `index`: a block-aligned offset uniform over the
        image (fio's randwrite, blockalign = bs, norandommap) and its
        bytes, from the seed."""
        rng = np.random.default_rng((self.seed, 0x0b1d, index))
        off = int(rng.integers(self.image_size // self.bs)) * self.bs
        return off, rng.bytes(self.bs)

    def _overwrite(self, index: int):
        off, data = self.writes[index] = self.draw(index)
        return self.img.aio_write(data, off)

    def run_window(self, seconds: float, on_open, on_close) -> None:
        tr = self.cell.traffic
        self.log = closed_loop.run(
            self._overwrite, depth=self.depth,
            precondition_acks=int(tr["precondition_acks"]),
            seconds=seconds, on_open=on_open, on_close=on_close,
            op_timeout=float(tr.get("op_timeout_s", 300.0)),
            span=self.span)

    # -- counters -------------------------------------------------------------

    def counters(self) -> dict:
        c = super().counters()
        for key in OSD_COUNTERS:
            c[f"osd.{key}"] = sum(_perf(d, key) for d in self._daemons)
        c["store.write_run_blocks"] = sum(
            _perf(d.store, "write_run_blocks") for d in self._daemons)
        return c

    def notes(self, before: dict, after: dict) -> dict:
        facts = super().notes(before, after)
        for key in OSD_COUNTERS + ("write_run_blocks",):
            full = f"osd.{key}" if key in OSD_COUNTERS else f"store.{key}"
            facts[key] = after[full] - before[full]
        return facts

    # -- the check ------------------------------------------------------------

    def verify(self) -> list[Check]:
        """The plain image is the prefill with every acknowledged
        overwrite laid over it in the order acknowledged.  Compared:
        the whole image read back through librbd, block by block; the
        chunks of every stripe an overwrite touched (a seeded 2,048 of
        them where there are more) and of a seeded set of untouched
        ones in the OSDs' stores; the touched blocks' stored checksums;
        and that every acknowledged overwrite went the stripe-ranged
        read-modify-write way."""
        tr = self.cell.traffic
        acked = sorted((a for a in self.log.acks if a.ok),
                       key=lambda a: a.t_ack)
        for a in acked:
            self.model.write(*self.writes[a.index])
        blocks_differ = self._read_back()
        width = self.k * self.stripe_unit
        touched = sorted({self.model.stripe_of(self.writes[a.index][0],
                                               width) for a in acked})
        per_obj = self.rbd_obj // width
        quiet = sorted(set(divmod(s, per_obj)
                           for s in range(self.n_objects * per_obj))
                       - set(touched))
        rng = np.random.default_rng((self.seed, 0x7e51))
        touched = _sample(rng, touched, int(tr["verify_stripes"]))
        untouched = _sample(rng, quiet, int(tr["verify_untouched_stripes"]))
        chunks_differ, csums_differ = self._verify_stripes(touched,
                                                           untouched)
        now = self.counters()
        rmw = now["osd.ec_rmw_writes"] - self._at_prefill["osd.ec_rmw_writes"]
        host_stood_in = (now["encode.faults"] + now["decode.faults"]
                         + now["store.csum_fallbacks"])
        return [Check("image_blocks_differ", blocks_differ, 0),
                Check("parity_shards_differ", chunks_differ, 0),
                Check("stored_block_csums_differ", csums_differ, 0),
                Check("overwrites_not_rmw", len(acked) - rmw, 0),
                Check("host_stood_in_for_device", host_stood_in, 0)]

    def _read_back(self) -> int:
        """Blocks of the image that librbd reads back otherwise than
        the plain image holds them."""
        differ = 0
        for objno in range(self.n_objects):
            lo = objno * self.rbd_obj
            want = self.model.read(lo, self.rbd_obj)
            try:
                got = self.img.read(lo, self.rbd_obj)
            except OSError:
                got = b""
            self.read_back += 1
            if got != want:
                differ += sum(
                    got[i:i + self.bs] != want[i:i + self.bs]
                    for i in range(0, self.rbd_obj, self.bs))
        return differ

    def _verify_stripes(self, touched, untouched) -> tuple[int, int]:
        """(chunks of the stripes that differ — each stored parity chunk
        against rs_plain of the stored data chunks, each stored data
        chunk against the plain image; a chunk that cannot be read
        differs — and stored checksums of the touched stripes' blocks
        that are not the crc32 of the block: of the plain image's bytes
        for a data chunk, of the stored bytes for a parity chunk)."""
        k, m, su = self.k, self.m, self.stripe_unit
        block = int(self.cell.config["deployment"]["store_block"])
        where: dict[str, tuple] = {}
        for d in self._daemons:
            for cid in d.store.list_collections():
                for soid in d.store.list_objects(cid):
                    where.setdefault(soid, (d.store, cid))
        chunks_differ = csums_differ = 0
        checked = set(touched)
        for (objno, stripe) in touched + untouched:
            name = f"rbd_data.{IMAGE}.{objno:016x}"
            stored: list[bytes | None] = []
            for s in range(k + m):
                store, cid = where.get(f"{name}:{s}", (None, None))
                try:
                    stored.append(store.read(cid, f"{name}:{s}",
                                             stripe * su, su))
                except (AttributeError, KeyError, OSError):
                    stored.append(None)
            want = self.model.stripe_shards(objno, stripe, k, m, su)
            if all(c is not None for c in stored[:k]):
                plain = rs_plain.shards_of(b"".join(stored[:k]), k, m, su)
            else:
                plain = [None] * (k + m)
            chunks_differ += sum(stored[s] != want[s] for s in range(k))
            chunks_differ += sum(stored[s] is None or stored[s] != plain[s]
                                 for s in range(k, k + m))
            if (objno, stripe) not in checked:
                continue
            for s in range(k + m):
                store, cid = where.get(f"{name}:{s}", (None, None))
                body = want[s] if s < k else stored[s]
                bi = stripe * su // block
                try:
                    with store._lock:
                        got = store._meta(cid, f"{name}:{s}")["csum"]
                except (AttributeError, KeyError, TypeError):
                    got = []
                csums_differ += (body is None or bi >= len(got)
                                 or got[bi] != zlib.crc32(body))
        return chunks_differ, csums_differ

    @property
    def attempted(self) -> int:
        return (self.log.submitted + self.read_back
                + (self.prefill_log.submitted if self.prefill_log else 0))

    @property
    def failed(self) -> int:
        return self.log.failed + (self.prefill_log.failed
                                  if self.prefill_log else 0)


def _sample(rng, items: list, n: int) -> list:
    """A seeded `n` of `items` (all of them where there are fewer)."""
    if len(items) <= n:
        return items
    return sorted(items[i] for i in rng.choice(len(items), n,
                                               replace=False))


def _check_program() -> None:
    """Refuse at once a program that has no erasure-coded data pool
    with overwrites, before any daemon starts."""
    from ceph_tpu.osd.osdmap import PGPool
    from ceph_tpu.rbd import Image
    if not (hasattr(PGPool, "allows_ecoverwrites")
            and hasattr(Image, "aio_write")
            and "data_pool" in inspect.signature(Image.create).parameters):
        raise SystemExit(
            "perfbench: this program has no RBD data pool on an erasure-"
            "coded pool with allow_ec_overwrites (PGPool.allows_"
            "ecoverwrites, Image.create(data_pool=), Image.aio_write)")


def _perf(holder, key: str) -> int:
    try:
        return holder.perf.value(key)
    except KeyError:
        return 0
