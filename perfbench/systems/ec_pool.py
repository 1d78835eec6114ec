"""An erasure-coded pool on an in-process cluster, written to by a
closed-loop client — `rados bench write` as the configuration states it.

The deployment is stood up through the program's own entries
(``tools/vstart.MiniCluster``, the mon's ``osd pool create``), driven
through ``ioctx.aio_write_full`` and checked against the payloads the
seed gives (perfbench/reference/payloads.py): through ``ioctx.aio_read``
and, for the parity shards and the block checksums, in the OSDs' stores
against perfbench/reference/rs_plain.py.
"""

from __future__ import annotations

import os
import shutil
import tempfile

import numpy as np

from perfbench.harness import closed_loop
from perfbench.harness.cell import Check
from perfbench.harness.window import in_window, quantile
from perfbench.reference import payloads, rs_plain
from perfbench.systems import engine_faults

SPANS = ("client_op", "generator")
TRAFFIC_KIND = "closed_loop_write"


class System:
    def __init__(self, cell, seed: int, span=None):
        self.cell = cell
        self.seed = seed
        self.span = span
        dep, tr = cell.config["deployment"], cell.traffic
        self.k, self.m = int(dep["k"]), int(dep["m"])
        self.n_osds = int(dep["osds"])
        self.stripe_unit = int(dep["stripe_unit"])
        self.obj_size = int(tr["object_size"])
        self.depth = int(tr["depth"])
        self.base_path = ""
        self.cluster = None
        self.io = None
        self.log = None
        self.read_log = None
        self.mismatched: list[int] = []
        self._daemons: list = []

    # -- the deployment -----------------------------------------------------

    def setup(self) -> None:
        from ceph_tpu.tools.vstart import MiniCluster
        dep = self.cell.config["deployment"]
        store_root = dep.get("store_root") or tempfile.gettempdir()
        self.base_path = tempfile.mkdtemp(prefix="perfbench-store-",
                                          dir=store_root)
        self.cluster = MiniCluster(
            n_osds=self.n_osds, store_type=dep["objectstore"],
            ms_type=dep["ms_type"], base_path=self.base_path).start()
        self._daemons = list(self.cluster.osds.values())
        self.cluster.wait_for_osd_count(self.n_osds, timeout=60.0)
        client = self.cluster.client(timeout=120.0)
        pool = self.cluster.create_pool(
            client, pool_type="erasure", plugin=dep["plugin"],
            k=self.k, m=self.m, epoch_timeout=120.0)
        pg_pool = self.cluster.mon.osdmap.pools[pool]
        osd = self._daemons[0]
        got = {"pg_num": pg_pool.pg_num,
               "stripe_unit": osd._ec_stripe_info(osd._codec(pg_pool),
                                                  pg_pool).su,
               "runtime": pg_pool.ec_profile.get("runtime", "tpu")}
        want = {"pg_num": int(dep["pg_num"]),
                "stripe_unit": self.stripe_unit, "runtime": "tpu"}
        if got != want:
            raise RuntimeError(f"the pool is not the configuration's: "
                               f"{got} != {want}")
        self.io = client.open_ioctx(pool)

    def _payload(self, index: int) -> bytes:
        return payloads.payload(self.seed, index, self.obj_size)

    def _write(self, index: int):
        return self.io.aio_write_full(
            payloads.object_name(self.seed, index), self._payload(index))

    def run_window(self, seconds: float, on_open, on_close) -> None:
        tr = self.cell.traffic
        self.log = closed_loop.run(
            self._write, depth=self.depth,
            precondition_acks=int(tr["precondition_acks"]),
            seconds=seconds, on_open=on_open, on_close=on_close,
            op_timeout=float(tr.get("op_timeout_s", 300.0)),
            span=self.span)

    # -- counters -------------------------------------------------------------

    def counters(self) -> dict:
        from ceph_tpu.ops import telemetry
        c: dict = {}
        c["osd.ec_dispatch_submits"] = sum(
            d.perf.value("ec_dispatch_submits") for d in self._daemons)
        commits = [d.store.perf.value("commit_lat") for d in self._daemons]
        c["store.commits"] = sum(n for n, _s in commits)
        c["store.commit_seconds"] = sum(s for _n, s in commits)
        for side, stats in (("encode", telemetry.dispatch_stats()),
                            ("decode", telemetry.decode_dispatch_stats())):
            d = stats.dump()
            for key in ("submits", "batches", "stripes_out"):
                c[f"{side}.{key}"] = d[key]
            faults = stats.fault_dump()
            c[f"{side}.faults"] = engine_faults(faults)
            c[f"{side}.retries"] = faults["retries"]
        digest = telemetry.pipeline_profile_digest()
        for side in ("encode", "decode"):
            for phase in telemetry.PHASES:
                c[f"phase.{side}.{phase}"] = sum(
                    per["seconds"].get(phase, 0.0)
                    for per in digest[side]["kernels"].values())
        blue = telemetry.bluestore_summary()
        for key in ("csum_batches", "batched_csum_blocks",
                    "scalar_csum_blocks", "csum_fallbacks"):
            c[f"store.{key}"] = blue[key]
        c["store.file_bytes"] = _tree_bytes(self.base_path)
        return c

    def slice_gate(self):
        """The encode program runs a few times a second where objects
        are large: the traced slice has to hold `trace_min_encode_calls`
        device calls of the encode engine for its roofline to be read."""
        from ceph_tpu.ops import telemetry
        stats = telemetry.dispatch_stats()
        need = int(self.cell.traffic.get("trace_min_encode_calls", 0))
        n0 = stats.dump()["batches"]
        return lambda: stats.dump()["batches"] - n0 >= need

    def notes(self, before: dict, after: dict) -> dict:
        """What the window held, on one line of standard error."""
        log = self.log
        acks = in_window(log.acks, log.t_open, log.seconds)
        lat = [(a.t_ack - a.t_submit) * 1e3 for a in acks]
        facts = {"window_acks": len(acks), "submitted": log.submitted,
                 "depth_at_open": log.depth_at(log.t_open),
                 "depth_at_close": log.depth_at(log.t_open + log.seconds)}
        if lat:
            facts["lat_ms"] = {
                "min": round(min(lat), 1), "p50": round(quantile(lat, .5), 1),
                "p90": round(quantile(lat, .9), 1), "max": round(max(lat), 1)}
            # acks per fifth of the window: a stall shows as a low fifth
            fifth = log.seconds / 5
            facts["acks_by_fifth"] = [
                sum(1 for a in acks
                    if i * fifth <= a.t_ack - log.t_open < (i + 1) * fifth)
                for i in range(5)]
        d = {key: after[key] - before[key] for key in after}
        facts["commits"] = d["store.commits"]
        facts["commit_s"] = round(d["store.commit_seconds"], 3)
        return facts

    # -- the check ------------------------------------------------------------

    def verify(self) -> list[Check]:
        """Of the acknowledged objects of every phase — all of them, or
        where the traffic says so a seeded sample with the first and the
        last in it — compare three things with the plain references:
        what a client reads back, with the seed's payload; the m parity
        shards the OSDs committed, with reed_sol_van's
        (reference/rs_plain.py); and the checksum BlueStore keeps of
        each 4 KiB block of the k + m shards, with zlib's crc32."""
        tr = self.cell.traffic
        written = sorted(a.index for a in self.log.acks if a.ok)
        n = min(int(tr["verify_objects"]) or len(written), len(written))
        rng = np.random.default_rng((self.seed, 0x7e51))
        pick = set(written[:1] + written[-1:])
        rest = [i for i in written if i not in pick]
        pick |= set(rng.choice(rest, max(0, n - len(pick)),
                               replace=False).tolist()) if rest else set()
        sample = sorted(pick)

        data: dict[int, bytes] = {}

        def read(j: int):
            return self.io.aio_read(
                payloads.object_name(self.seed, sample[j]))

        def collect(j: int, completion) -> None:
            data[j] = completion.data

        self.read_log = closed_loop.run_all(
            read, len(sample), depth=int(tr.get("verify_depth", 16)),
            collect=collect)
        self.mismatched = [
            sample[j] for j in range(len(sample))
            if data.get(j) != self._payload(sample[j])]
        parity_wrong, csums_wrong = self._verify_shards(sample)
        after = self.counters()
        host_stood_in = (after["encode.faults"] + after["decode.faults"]
                         + after["store.csum_fallbacks"])
        return [Check("acked_objects_not_read_back", len(self.mismatched), 0),
                Check("parity_shards_differ", parity_wrong, 0),
                Check("stored_block_csums_differ", csums_wrong, 0),
                Check("host_stood_in_for_device", host_stood_in, 0)]

    def _verify_shards(self, sample: list[int]) -> tuple[int, int]:
        """What the OSDs' stores hold of the sampled objects.  Shard s
        of an object is the store object ``<name>:<s>`` on whichever OSD
        has it.  Returns (parity shards that are missing, unreadable or
        not the reference's bytes; blocks of any shard whose stored
        checksum is missing or not the crc32 of the block — of the
        reference's bytes for a data shard, of the stored bytes for a
        parity shard, which `parity_shards_differ` judges)."""
        block = int(self.cell.config["deployment"]["store_block"])
        where: dict[str, list] = {}
        for d in self._daemons:
            for cid in d.store.list_collections():
                for soid in d.store.list_objects(cid):
                    where.setdefault(soid, []).append((d.store, cid))
        parity_wrong = csums_wrong = 0
        for index in sample:
            name = payloads.object_name(self.seed, index)
            shards = rs_plain.shards_of(self._payload(index), self.k,
                                        self.m, self.stripe_unit)
            for s, want in enumerate(shards):
                holders = where.get(f"{name}:{s}", [])
                if s >= self.k:
                    # (a client's read has covered the data shards)
                    stored = []
                    for store, cid in holders:
                        try:
                            stored.append(store.read(cid, f"{name}:{s}"))
                        except (OSError, KeyError):
                            stored.append(None)
                    parity_wrong += (not stored
                                     or any(b != want for b in stored))
                    # the digest is of what the store holds
                    want = next((b for b in stored if b is not None), want)
                sums = rs_plain.block_csums(want, block)
                if not holders:
                    csums_wrong += len(sums)
                for store, cid in holders:
                    with store._lock:
                        got = store._meta(cid, f"{name}:{s}")["csum"]
                    csums_wrong += sum(
                        1 for i, c in enumerate(sums)
                        if i >= len(got) or got[i] != c)
        return parity_wrong, csums_wrong

    @property
    def attempted(self) -> int:
        return self.log.submitted + (self.read_log.submitted
                                     if self.read_log else 0)

    @property
    def failed(self) -> int:
        # a read that failed is an object that did not read back
        return self.log.failed + len(self.mismatched)

    def close(self) -> None:
        try:
            if self.cluster is not None:
                self.cluster.stop()
        finally:
            if self.base_path:
                shutil.rmtree(self.base_path, ignore_errors=True)


def _tree_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, name))
            except OSError:
                pass
    return total
