"""ec_pool_degraded.py's deployment and reads on a Clay pool: the plugin
is clay (k, m, d = k + m - 1, alpha = m^((k+m)/m) sub-chunks a chunk),
everything else — OSDs, PGs, stripe unit, the OSDs taken down, the
closed-loop reader, the checks — is that system's.

What differs: the shards are checked against the plain Clay reference
(perfbench/reference/clay_plain.py: parity, block checksums, and the
object rebuilt from the stored shards by the layered decoder), the
decode kernel is the Clay one, and set-up refuses a program whose Clay
pool has no per-stripe layout (no ``StripeInfo``: such a program rebuilds
on the op's thread, outside the decode engine) before it stands
anything up.
"""

from __future__ import annotations

from perfbench.reference import clay_plain, rs_plain
from perfbench.systems import ec_pool_degraded

SPANS = ec_pool_degraded.SPANS
TRAFFIC_KIND = ec_pool_degraded.TRAFFIC_KIND
DECODE_KERNEL = "ec_decode_clay"
_perf = ec_pool_degraded._perf


class System(ec_pool_degraded.System):
    def __init__(self, cell, seed: int, span=None):
        super().__init__(cell, seed, span)
        dep = cell.config["deployment"]
        n = self.k + self.m
        q, t, alpha = clay_plain.geometry(self.k, self.m)
        self.d = int(dep["d"])
        self.alpha = int(dep["sub_chunks"])
        if (dep["plugin"] != "clay" or self.d != n - 1
                or self.alpha != alpha or self.stripe_unit % alpha):
            raise SystemExit(
                f"perfbench: {cell.config_name}: a clay pool with d = k + "
                f"m - 1 = {n - 1}, {alpha} sub-chunks a chunk (q = {q}, "
                f"t = {t}) and a stripe unit that is a whole number of "
                f"them; the configuration states d = {self.d}, "
                f"{self.alpha} sub-chunks, stripe unit {self.stripe_unit}")

    def setup(self) -> None:
        self._check_program()
        super().setup()
        pg_pool = self.cluster.mon.osdmap.pools[self.pool]
        codec = self._daemons[0]._codec(pg_pool)
        got = (codec.d, codec.get_sub_chunk_count())
        if got != (self.d, self.alpha):
            raise RuntimeError(f"the pool's codec has d, sub-chunks {got}, "
                               f"the configuration {self.d, self.alpha}")

    def _check_program(self) -> None:
        """A Clay pool of this profile has to be laid out per stripe and
        decode through the engine (``submit_decode_chunks``)."""
        from ceph_tpu.ec import registry_instance
        from ceph_tpu.ec.base import ErasureCode
        try:
            codec = registry_instance().factory(
                "clay", {"k": str(self.k), "m": str(self.m),
                         "d": str(self.d)})
        except ValueError as e:
            raise SystemExit(f"perfbench: this program has no Clay profile "
                             f"with d: {e}")
        if not (getattr(codec, "supports_rmw_striping", False)
                and type(codec).submit_decode_chunks
                is not ErasureCode.submit_decode_chunks):
            raise SystemExit(
                "perfbench: this program's Clay pool has no stripe info "
                "(supports_rmw_striping) or no engine decode of its own "
                "(submit_decode_chunks): it lays objects out whole and "
                "rebuilds on the op's thread")

    def counters(self) -> dict:
        from ceph_tpu.ops import telemetry
        c = super().counters()
        c["osd.ec_decode_subchunks"] = sum(
            _perf(d, "ec_decode_subchunks") for d in self._daemons)
        kernels = telemetry.pipeline_profile_digest()["decode"]["kernels"]
        c["decode.clay_batches"] = kernels.get(DECODE_KERNEL, {}).get(
            "batches", 0)
        return c

    def notes(self, before: dict, after: dict) -> dict:
        facts = super().notes(before, after)
        for key in ("osd.ec_decode_subchunks", "decode.clay_batches"):
            facts[key.split(".", 1)[1]] = after[key] - before[key]
        return facts

    def _verify_shards(self, sample: list[int]) -> tuple[int, int, int]:
        """As ec_pool_degraded's, against the plain Clay reference: parity
        shards that are missing or not clay_plain's bytes; blocks whose
        stored checksum is not the crc32 of the block; objects that the
        plain layered decoder does not rebuild to the payload from the
        stored shards."""
        block = int(self.cell.config["deployment"]["store_block"])
        alive = {d.osd_id for d in self._daemons}
        where = {soid: [(d.store, cid) for d, cid in held]
                 for soid, held in self._holders().items()}
        parity_wrong = csums_wrong = rebuild_wrong = 0
        for index in sample:
            payload = self._payload(index)
            shards = clay_plain.shards_of(payload, self.k, self.m,
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
                rebuilt = clay_plain.object_of(
                    have, self.k, self.m, self.stripe_unit, len(payload))
            except ValueError:
                rebuilt = None
            rebuild_wrong += rebuilt != payload
        return parity_wrong, csums_wrong, rebuild_wrong

