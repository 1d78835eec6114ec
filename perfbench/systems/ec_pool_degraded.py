"""The erasure-coded pool of ec_pool.py with some of its OSDs down and
still in, read by a closed-loop client — `rados bench seq` over the
objects a `rados bench write --no-cleanup` left, inside
`mon_osd_down_out_interval`: no recovery starts, every PG is a shard or
two short, and a read that meets a hole among its data shards is rebuilt
by the decode engine.

Set-up is the pool (ec_pool.System.setup), the objects written, the
seeded OSDs killed and marked down, and one whole pass of reads (the
loop's precondition), which meets every PG's erasure pattern.  Reads go
through ``ioctx.aio_read`` as any client's do.  The check compares every
read of every phase with the payload the seed gives
(perfbench/reference/payloads.py), holds the cell to having decoded, and
looks into the surviving OSDs' stores: parity and block checksums
against perfbench/reference/rs_plain.py, and the object rebuilt from the
stored shards by the plain decoder (reference/rs_plain_decode.py).
"""

from __future__ import annotations

import numpy as np

from perfbench.harness import closed_loop
from perfbench.harness.cell import Check
from perfbench.reference import payloads, rs_plain, rs_plain_decode
from perfbench.systems import ec_pool

SPANS = ec_pool.SPANS
TRAFFIC_KIND = "closed_loop_seq_read"
#: the OSD's counters of the read path; a program that lacks one reads 0
OSD_COUNTERS = ("ec_decode_submits", "ec_degraded_reads",
                "ec_decode_targets")
DECODE_KERNEL = "ec_decode"


class System(ec_pool.System):
    def __init__(self, cell, seed: int, span=None):
        super().__init__(cell, seed, span)
        dep, tr = cell.config["deployment"], cell.traffic
        self.n_objects = int(tr["preload_objects"])
        self.n_down = int(dep["down_osds"])
        if int(tr["down_osds"]) != self.n_down or dep["down_out"]:
            raise SystemExit(
                f"perfbench: {cell.traffic_name} takes {tr['down_osds']} "
                f"OSDs down, {cell.config_name} states {self.n_down} "
                f"(down_out {dep['down_out']!r})")
        self.pool = -1
        self.down: list[int] = []
        self.preload_log = None
        self._payloads: dict[int, bytes] = {}
        #: store object name -> OSDs that held it before the kill
        self.where_before: dict[str, list[int]] = {}
        self.lost_data: set[int] = set()
        self.not_exact: list[int] = []
        self._at_kill: dict = {}
        self._undegraded_at_open = 0

    # -- the deployment -----------------------------------------------------

    def setup(self) -> None:
        super().setup()
        tr = self.cell.traffic
        self.pool = self.io.pool_id
        self.preload_log = closed_loop.run_all(
            self._write, self.n_objects, depth=int(tr["preload_depth"]),
            op_timeout=float(tr.get("op_timeout_s", 300.0)))
        if self.preload_log.failed or len(
                self.preload_log.acks) != self.n_objects:
            raise RuntimeError(
                f"{self.preload_log.failed} of {self.n_objects} preload "
                f"writes failed")
        self.where_before = {
            soid: [d.osd_id for d, _cid in held]
            for soid, held in self._holders().items()}
        rng = np.random.default_rng((self.seed, 0xd0e4))
        self.down = sorted(rng.choice(self.n_osds, self.n_down,
                                      replace=False).tolist())
        self.lost_data = {
            i for i in range(self.n_objects) for s in range(self.k)
            if set(self.where_before.get(self._soid(i, s), [])) & set(
                self.down)}
        self.take_down()
        self._at_kill = self.counters()

    def take_down(self) -> None:
        """Kill the seeded OSDs, mark them down (they stay in) and wait
        until the survivors and the client have the map that says so."""
        client = self.io.client
        for osd in self.down:
            self.cluster.kill_osd(osd)
            rc, out = client.mon_command({"prefix": "osd down",
                                          "id": str(osd)})
            if rc != 0:
                raise RuntimeError(f"osd down {osd}: {out}")
        self._daemons = list(self.cluster.osds.values())
        epoch = self.cluster.mon.osdmap.epoch
        self.cluster.wait_for_epoch(epoch, timeout=60.0)
        client.wait_for_epoch(epoch)

    def _holders(self) -> dict[str, list]:
        """Store object name -> [(daemon, collection), ...] over the
        OSDs that are up."""
        held: dict[str, list] = {}
        for d in self._daemons:
            for cid in d.store.list_collections():
                for soid in d.store.list_objects(cid):
                    held.setdefault(soid, []).append((d, cid))
        return held

    def _soid(self, index: int, shard: int) -> str:
        return f"{payloads.object_name(self.seed, index)}:{shard}"

    def _payload(self, index: int) -> bytes:
        got = self._payloads.get(index)
        if got is None:
            got = self._payloads[index] = super()._payload(index)
        return got

    def _read(self, index: int):
        """Object `index` of the loop: the written objects in the order
        written, and again from the first."""
        completion = self.io.aio_read(
            payloads.object_name(self.seed, index % self.n_objects))
        completion.loop_index = index
        return completion

    def _exact(self, completion) -> bool:
        """Whether a read gave the bytes that were acknowledged; runs on
        the loop's thread after the acknowledgement was stamped."""
        index = completion.loop_index
        good = (completion.get_return_value() >= 0 and completion.data
                == self._payload(index % self.n_objects))
        if not good:
            self.not_exact.append(index)
        return good

    def run_window(self, seconds: float, on_open, on_close) -> None:
        tr = self.cell.traffic

        def opened() -> None:
            self._undegraded_at_open = self.pgs_not_degraded()
            on_open()

        self.log = closed_loop.run(
            self._read, depth=self.depth,
            precondition_acks=int(tr["precondition_acks"]),
            seconds=seconds, on_open=opened, on_close=on_close,
            op_timeout=float(tr.get("op_timeout_s", 300.0)),
            ok=self._exact, span=self.span)

    # -- counters -------------------------------------------------------------

    def counters(self) -> dict:
        from ceph_tpu.ops import telemetry
        c = super().counters()
        blue = telemetry.bluestore_summary()
        for key in ("read_verify_batches", "read_verify_blocks"):
            c[f"store.{key}"] = blue[key]
        for key in OSD_COUNTERS:
            c[f"osd.{key}"] = sum(_perf(d, key) for d in self._daemons)
        # the decode engine carries BlueStore's digest batches too: the
        # decode kernel's own device calls are its family's batches
        kernels = telemetry.pipeline_profile_digest()["decode"]["kernels"]
        c["decode.ec_batches"] = kernels.get(DECODE_KERNEL, {}).get(
            "batches", 0)
        return c

    def slice_gate(self):
        """Reads that decode come a few a second: the traced slice has
        to hold `trace_min_decode_calls` submissions to the decode
        engine for the decode program's roofline to be read."""
        need = int(self.cell.traffic.get("trace_min_decode_calls", 0))

        def submits() -> int:
            return sum(_perf(d, "ec_decode_submits") for d in self._daemons)

        n0 = submits()
        return lambda: submits() - n0 >= need

    def notes(self, before: dict, after: dict) -> dict:
        facts = super().notes(before, after)
        for key in ("osd.ec_decode_submits", "osd.ec_degraded_reads",
                    "osd.ec_decode_targets", "decode.ec_batches",
                    "store.read_verify_batches"):
            facts[key.split(".", 1)[1]] = after[key] - before[key]
        facts["down"] = self.down
        facts["objects_that_lost_data"] = len(self.lost_data)
        return facts

    # -- the check ------------------------------------------------------------

    def pgs_not_degraded(self) -> int:
        """PGs of the pool whose acting set, by the monitor's map, is
        not the k + m positions less the OSDs taken down."""
        from ceph_tpu.osd.osdmap import CEPH_NOSD
        osdmap = self.cluster.mon.osdmap
        want = self.k + self.m - self.n_down
        return sum(
            1 for ps in range(osdmap.pools[self.pool].pg_num)
            if sum(o != CEPH_NOSD for o in
                   osdmap.pg_to_up_acting_osds(self.pool, ps)[2]) != want)

    def verify(self) -> list[Check]:
        """Every read of every phase was compared with the seed's
        payload as it was acknowledged (`_exact`).  Here: that the reads
        of objects which had lost a data shard went through the decode
        engine; that the PGs were, and are, degraded; and of a seeded
        sample of the objects, with the first and the last in it, what
        the surviving OSDs' stores hold, against the plain references."""
        tr = self.cell.traffic
        n = min(int(tr["verify_objects"]) or self.n_objects, self.n_objects)
        rng = np.random.default_rng((self.seed, 0x7e51))
        pick = {0, self.n_objects - 1}
        rest = [i for i in range(self.n_objects) if i not in pick]
        pick |= set(rng.choice(rest, max(0, n - len(pick)),
                               replace=False).tolist()) if rest else set()
        parity_wrong, csums_wrong, rebuild_wrong = self._verify_shards(
            sorted(pick))

        now = self.counters()
        since = {key: now[key] - self._at_kill[key] for key in now}
        reads = [a.index % self.n_objects for a in self.log.acks if a.ok]
        rebuilt_short = max(0, sum(i in self.lost_data for i in reads)
                            - since["osd.ec_decode_submits"])
        host_stood_in = (now["encode.faults"] + now["decode.faults"]
                         + now["store.csum_fallbacks"]
                         + max(0, self._verify_batches_due(len(reads))
                               - since["store.read_verify_batches"]))
        return [Check("degraded_reads_not_exact", len(self.not_exact), 0),
                Check("reads_rebuilt_short", rebuilt_short, 0),
                Check("pgs_not_degraded", self._undegraded_at_open
                      + self.pgs_not_degraded(), 0),
                Check("parity_shards_differ", parity_wrong, 0),
                Check("stored_block_csums_differ", csums_wrong, 0),
                Check("plain_rebuild_differs", rebuild_wrong, 0),
                Check("host_stood_in_for_device", host_stood_in, 0)]

    def _verify_batches_due(self, reads: int) -> int:
        """Digest batches the good reads owe: one for each of the k
        shards a read gathers, where a shard has the blocks BlueStore
        batches a read's verification from (a shorter shard is verified
        on the host by design; the cells' shards are 128 blocks)."""
        store = self._daemons[0].store
        block = int(self.cell.config["deployment"]["store_block"])
        width = self.k * self.stripe_unit
        shard_blocks = -(-self.obj_size // width) * self.stripe_unit // block
        if shard_blocks < int(store._conf("bluestore_batched_read_min", 8)):
            return 0
        return reads * self.k

    def _verify_shards(self, sample: list[int]) -> tuple[int, int, int]:
        """What the surviving OSDs' stores hold of the sampled objects
        (shard s of an object is the store object ``<name>:<s>``; one
        that only a down OSD held is not looked for).  Returns (parity
        shards that are missing, unreadable or not the reference's
        bytes; blocks of any shard whose stored checksum is missing or
        not the crc32 of the block, as ec_pool._verify_shards counts
        them; objects that the plain decoder does not rebuild to the
        payload from the stored shards)."""
        block = int(self.cell.config["deployment"]["store_block"])
        alive = {d.osd_id for d in self._daemons}
        where = {soid: [(d.store, cid) for d, cid in held]
                 for soid, held in self._holders().items()}
        parity_wrong = csums_wrong = rebuild_wrong = 0
        for index in sample:
            payload = self._payload(index)
            shards = rs_plain.shards_of(payload, self.k, self.m,
                                        self.stripe_unit)
            have: dict[int, bytes] = {}
            for s, want in enumerate(shards):
                soid = self._soid(index, s)
                if not set(self.where_before.get(soid, [])) & alive:
                    continue            # it went down with its OSD
                holders = where.get(soid, [])
                stored = []
                for store, cid in holders:
                    try:
                        stored.append(store.read(cid, soid))
                    except (OSError, KeyError):
                        stored.append(None)
                if stored and stored[0] is not None:
                    have[s] = stored[0]
                if s >= self.k:
                    parity_wrong += (not stored
                                     or any(b != want for b in stored))
                    # the digest is of what the store holds
                    want = next((b for b in stored if b is not None), want)
                sums = rs_plain.block_csums(want, block)
                if not holders:
                    csums_wrong += len(sums)
                for store, cid in holders:
                    with store._lock:
                        got = store._meta(cid, soid)["csum"]
                    csums_wrong += sum(
                        1 for i, c in enumerate(sums)
                        if i >= len(got) or got[i] != c)
            try:
                rebuilt = rs_plain_decode.object_of(
                    have, self.k, self.m, self.stripe_unit, len(payload))
            except ValueError:
                rebuilt = None
            rebuild_wrong += rebuilt != payload
        return parity_wrong, csums_wrong, rebuild_wrong

    @property
    def attempted(self) -> int:
        return self.log.submitted + (self.preload_log.submitted
                                     if self.preload_log else 0)

    @property
    def failed(self) -> int:
        return self.log.failed + (self.preload_log.failed
                                  if self.preload_log else 0)


def _perf(daemon, key: str) -> int:
    try:
        return daemon.perf.value(key)
    except KeyError:
        return 0
