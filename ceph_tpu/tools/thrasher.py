"""Thrasher — randomized fault injection under load
(qa/tasks/ceph_manager.py:98 Thrasher analog).

Drives a MiniCluster with a mixed replicated + EC workload while
randomly killing/reviving OSDs and marking them out/in.  The workload
tracks every ACKED write; during the storm reads may time out or return
stale-epoch errors (retried), but an acked object must NEVER read back
wrong bytes, and after the storm ends and the cluster heals, every
acked object must be present and correct — the durability contract the
reference earns with teuthology.
"""

from __future__ import annotations

import random
import threading
import time

from ceph_tpu.tools.vstart import MiniCluster


class DeviceChaos:
    """Device-runtime chaos: fires failpoints at the dispatch engine's
    device boundaries (common/failpoint.py) while the mixed workload
    runs — the accelerator-side analog of killing OSDs.

    A storm keeps every kernel channel's launch failing at
    ``BASE_RATE`` (the >=10%% chaos-gate floor: transient faults that
    the bounded retry ladder must absorb), and each step may also
    declare a HARD OUTAGE on one channel (mode ``always`` — the
    breaker must open and the bit-exact host oracle must carry the
    channel), heal one, arm the device_put / block_until_ready
    boundaries, or kill an engine run-loop outright (supervision must
    revive it and re-fan its in-flight batches).  ``clear()`` disarms
    everything; afterwards every breaker must re-close via the
    background probes — the reconvergence half of the durability
    contract."""

    #: the kernel channels the chaos gate names (encode, decode, fused
    #: placement ladder, objectstore write-time digests); crush and
    #: scrub channels ride the same machinery
    CHANNELS = ("ec_encode", "ec_decode", "pg_finish",
                "bluestore_data")
    BASE_RATE = 0.15

    def __init__(self, rng: random.Random):
        self.rng = rng
        self.actions = 0
        #: channels currently under a hard outage (breaker expected
        #: open while this is non-empty)
        self.outages: set[str] = set()

    def start(self) -> None:
        from ceph_tpu.common import failpoint
        failpoint.seed(self.rng.randrange(1 << 31))
        for ch in self.CHANNELS:
            failpoint.set(f"dispatch.launch:{ch}",
                          f"prob:{self.BASE_RATE}")

    def step(self) -> str:
        from ceph_tpu.common import failpoint
        roll = self.rng.random()
        ch = self.rng.choice(self.CHANNELS)
        self.actions += 1
        if roll < 0.22:
            failpoint.set(f"dispatch.launch:{ch}", "always")
            self.outages.add(ch)
            return f"chaos outage {ch}"
        if roll < 0.5:
            failpoint.set(f"dispatch.launch:{ch}",
                          f"prob:{self.BASE_RATE}")
            self.outages.discard(ch)
            return f"chaos heal {ch}"
        if roll < 0.68:
            site = self.rng.choice(("dispatch.device_put",
                                    "dispatch.block_until_ready"))
            failpoint.set(f"{site}:{ch}", f"prob:{self.BASE_RATE}")
            return f"chaos arm {site}:{ch}"
        if roll < 0.82:
            role = self.rng.choice(("dispatch", "complete"))
            failpoint.set(f"dispatch.{role}_thread_death", "oneshot")
            return f"chaos kill {role} run-loop"
        return "chaos noop"

    def clear(self) -> None:
        from ceph_tpu.common import failpoint
        failpoint.clear()
        self.outages.clear()

    @staticmethod
    def await_reconverged(timeout: float = 20.0,
                          cluster=None) -> tuple[bool, dict]:
        """After clear(): wait for every channel breaker to re-close
        via the background probes.  When a MiniCluster is given, the
        gate additionally reads each live engine's OWN breaker map:
        the process-global stats sink is shared by every in-process
        daemon and keyed by channel only, so daemon B's re-close there
        is last-writer-wins over daemon A's still-open breaker — the
        per-engine maps are the ground truth the acceptance gate
        needs.  Returns (reconverged, final fault digest)."""
        from ceph_tpu.ops import telemetry

        def engine_states() -> list[int]:
            if cluster is None:
                return []
            states: list[int] = []
            for osd in list(cluster.osds.values()):
                ctx = getattr(osd, "ctx", None)
                # private attrs on purpose: the public accessors
                # lazily BUILD an engine, and a daemon that never
                # dispatched has no breakers to wait on
                for eng in (getattr(ctx, "_dispatch", None),
                            getattr(ctx, "_decode_dispatch", None)):
                    if eng is not None:
                        states.extend(eng.breaker_states().values())
            return states

        deadline = time.time() + timeout
        digest: dict = {}
        while time.time() < deadline:
            digest = telemetry.fault_digest()
            if cluster is not None:
                # live-engine ground truth ONLY: a daemon killed
                # mid-outage leaves its OPEN as the sink's last write
                # for that channel forever (its engine is stopped and
                # can never re-close), which would fail the gate on a
                # healthy cluster
                states = engine_states()
            else:
                states = [st for d in digest.values()
                          for st in d.get("breaker_states", {}).values()]
            if all(st == telemetry.BREAKER_CLOSED for st in states):
                return True, digest
            time.sleep(0.25)
        return False, digest


class ScrubStorm(threading.Thread):
    """``--scrub-storm``: continuous deep scrub + on-disk bit-flip
    injection while the OSD-kill/chaos storm runs.

    A dedicated integrity pool holds a fixed object population with
    KNOWN payloads (the workload pools keep overwriting theirs, which
    would make "repaired back to truth" unverifiable).  The storm
    loop alternates full ``scrub_all_pgs`` sweeps on every live OSD
    with bit flips written straight into a random copy's object store
    — version attrs untouched, so log-based recovery cannot see the
    damage and only integrity checking can.  Gate (``verify()``):
    after heal, every injected corruption was detected and repaired —
    every live copy of every integrity object reads back as its
    written payload — with the cluster scrub ledger alongside.  The
    ledger's ``repair_unverified`` may be transiently non-zero during
    a kill storm (the repair target died mid-verification); the gate
    is final convergence (``unrepaired`` empty), not a zero there."""

    def __init__(self, cluster: MiniCluster, pool: int,
                 rng: random.Random, n_objects: int = 8):
        super().__init__(daemon=True, name="scrub-storm")
        self.cluster = cluster
        self.pool = pool
        self.rng = rng
        self._halt = threading.Event()
        self.payloads: dict[str, bytes] = {}
        self.injected: list[tuple[int, str, str]] = []
        self.sweeps = 0
        self.sweep_errors = 0
        # generous timeout + per-object retries: with --chaos the
        # first writes pay cold jit compiles and may time out once
        client = cluster.client(timeout=60.0)
        try:
            io = client.open_ioctx(pool)
            for i in range(n_objects):
                body = f"integrity-{i}-".encode() * 64
                for _attempt in range(3):
                    try:
                        io.write_full(f"int{i}", body)
                    except (TimeoutError, OSError):
                        time.sleep(1.0)
                        continue
                    self.payloads[f"int{i}"] = body
                    break
        finally:
            client.shutdown()

    def stop(self) -> None:
        self._halt.set()

    def _placement(self, oid: str):
        from ceph_tpu.client.rados import ceph_str_hash_rjenkins
        from ceph_tpu.osd.osdmap import pg_to_pgid
        m = self.cluster.mon.osdmap
        pool = m.pools.get(self.pool)
        if pool is None:
            return None, []
        pg = pg_to_pgid(ceph_str_hash_rjenkins(oid), pool.pg_num)
        up, _primary, _a, _ap = m.pg_to_up_acting_osds(self.pool, pg)
        return pg, [o for o in up if o >= 0]

    def _flip_one(self) -> str | None:
        """Flip one bit of one copy, store-direct (silent corruption:
        no log entry, no version change — scrub's problem to find)."""
        from ceph_tpu.objectstore import Transaction
        if not self.payloads:
            return None     # every seed write failed: nothing to flip
        oid = self.rng.choice(sorted(self.payloads))
        pg, up = self._placement(oid)
        cands = [o for o in up if o in self.cluster.osds]
        if pg is None or not cands:
            return None
        victim = self.rng.choice(cands)
        osd = self.cluster.osds.get(victim)
        if osd is None:
            return None
        cid = f"{self.pool}.{pg}"
        try:
            data = osd.store.read(cid, oid)
            if not data:
                return None
            off = self.rng.randrange(len(data))
            osd.store.apply_transaction(Transaction().write(
                cid, oid, off, bytes([data[off] ^ 0x40])))
        except Exception:
            return None      # victim died under us: the storm goes on
        self.injected.append((victim, cid, oid))
        return f"scrub-storm flip {oid} on osd.{victim}"

    def _sweep_all(self, ignore_halt: bool = False) -> None:
        for _i, osd in sorted(self.cluster.osds.items()):
            if self._halt.is_set() and not ignore_halt:
                return
            try:
                osd.scrub_all_pgs(timeout=60.0)
                self.sweeps += 1
            except Exception:
                self.sweep_errors += 1

    def run(self) -> None:
        while not self._halt.is_set():
            if self.rng.random() < 0.7:
                self._flip_one()
            self._sweep_all()
            self._halt.wait(0.25)

    def _bad_copies(self) -> list[tuple[int, str, str]]:
        bad = []
        for oid, body in sorted(self.payloads.items()):
            pg, up = self._placement(oid)
            if pg is None:
                continue
            cid = f"{self.pool}.{pg}"
            for o in up:
                osd = self.cluster.osds.get(o)
                if osd is None:
                    continue
                try:
                    data = osd.store.read(cid, oid)
                except Exception:
                    bad.append((o, oid, "unreadable"))
                    continue
                if data != body:
                    bad.append((o, oid, "mismatch"))
        return bad

    def verify(self, timeout: float = 90.0) -> dict:
        """Post-heal gate: keep sweeping until every live copy of
        every integrity object matches its written payload (injected
        corruption detected AND repaired), or the deadline."""
        from ceph_tpu.ops import telemetry
        end = time.time() + timeout
        bad = self._bad_copies()
        while bad and time.time() < end:
            self._sweep_all(ignore_halt=True)
            time.sleep(0.5)
            bad = self._bad_copies()
        return {"objects": len(self.payloads),
                "injected": len(self.injected),
                "sweeps": self.sweeps,
                "sweep_errors": self.sweep_errors,
                "unrepaired": [f"osd.{o}:{oid}:{why}"
                               for o, oid, why in bad],
                "ledger": telemetry.scrub_summary()}


class Workload(threading.Thread):
    """Continuous write/read/delete mix against one pool."""

    def __init__(self, cluster: MiniCluster, pool: int, prefix: str,
                 rng: random.Random, payload_scale: int = 2000):
        super().__init__(daemon=True)
        self.cluster = cluster
        self.pool = pool
        self.prefix = prefix
        self.rng = rng
        self.payload_scale = payload_scale
        self.acked: dict[str, bytes | None] = {}  # None = deleted
        #: full submission history per object (a timed-out write is
        #: unacked but MAY land — reads returning any value at or after
        #: the last acked submission are correct rados semantics)
        self.submitted: dict[str, list[bytes | None]] = {}
        self.acked_idx: dict[str, int] = {}
        self.corruptions: list[str] = []
        self.ops = 0
        self.errors = 0
        self._halt = threading.Event()  # Thread has a private _stop

    def stop(self) -> None:
        self._halt.set()

    def run(self) -> None:
        client = self.cluster.client(timeout=6.0)
        io = client.open_ioctx(self.pool)
        try:
            while not self._halt.is_set():
                oid = f"{self.prefix}{self.rng.randrange(24)}"
                roll = self.rng.random()
                hist = self.submitted.setdefault(oid, [])
                try:
                    if roll < 0.5:
                        body = (f"{oid}-{self.ops}-".encode()
                                * self.rng.randrange(
                                    1, self.payload_scale))
                        hist.append(body)
                        io.write_full(oid, body)
                        self.acked[oid] = body   # acked => durable
                        self.acked_idx[oid] = len(hist) - 1
                    elif roll < 0.9:
                        if oid not in self.acked_idx:
                            continue
                        got = io.read(oid)
                        if not self._acceptable(oid, got):
                            self.corruptions.append(oid)
                    else:
                        if self.acked.get(oid) is None:
                            continue
                        hist.append(None)
                        io.remove(oid)
                        self.acked[oid] = None
                        self.acked_idx[oid] = len(hist) - 1
                    self.ops += 1
                except (TimeoutError, OSError):
                    # storms time ops out / error them; the op is not
                    # acked, so no durability claim attaches — but it
                    # may still land, hence the submission history
                    self.errors += 1
        finally:
            client.shutdown()

    def _acceptable(self, oid: str, got: bytes | None) -> bool:
        """True iff `got` is the last acked value or any LATER submitted
        one (unacked writes may land; going backwards past an acked
        write, or returning bytes never written, is the failure)."""
        idx = self.acked_idx.get(oid)
        if idx is None:
            return True
        for v in self.submitted[oid][idx:]:
            if got == v:
                return True
        return False

    def final_verify(self, client) -> list[str]:
        """After heal: every acked object at/after its acked state."""
        io = client.open_ioctx(self.pool)
        bad = []
        for oid, idx in sorted(self.acked_idx.items()):
            suffix = self.submitted[oid][idx:]
            if all(v is None for v in suffix):
                continue   # last acked state is deleted
            for attempt in range(3):
                got: bytes | None
                try:
                    got = io.read(oid)
                except TimeoutError:   # NB: subclass of OSError — first
                    time.sleep(1.0)
                    continue
                except OSError:
                    got = None     # absent: fine if a delete follows
                if self._acceptable(oid, got):
                    break
                time.sleep(1.0)
            else:
                bad.append(oid)
        return bad


class Thrasher:
    def __init__(self, cluster: MiniCluster, seed: int = 0,
                 min_up: int = 4, max_down: int = 1,
                 pools: dict[int, int] | None = None,
                 pg_num_max: int = 32, thrash_mons: bool = False):
        self.cluster = cluster
        self.rng = random.Random(seed)
        self.min_up = min_up
        self.max_down = max_down
        self.downed: list[int] = []
        self.outed: list[int] = []
        self.actions = 0
        #: pool -> current pg_num; the thrasher grows pg_num (PG split
        #: under load) and trails pgp_num behind it, like the reference
        #: Thrasher's thrash_pg_num (qa/tasks/ceph_manager.py)
        self.pg_nums: dict[int, int] = dict(pools or {})
        self.pgp_nums: dict[int, int] = dict(pools or {})
        self.pg_num_max = pg_num_max
        #: mon currently killed (at most one: quorum of 3 needs 2)
        self.thrash_mons = thrash_mons
        self.downed_mon: int | None = None

    def _mon_cmd(self, cmd: dict) -> None:
        client = self.cluster.clients[0]
        try:
            client.mon_command(cmd)
        except (TimeoutError, OSError):
            pass

    def step(self) -> str:
        roll = self.rng.random()
        up = [i for i in self.cluster.osds if i not in self.downed]
        if self.thrash_mons and len(self.cluster.mons) + (
                1 if self.downed_mon is not None else 0) >= 3:
            if self.downed_mon is not None and roll < 0.2:
                mon = self.downed_mon
                self.downed_mon = None
                if self.rng.random() < 0.5:
                    # mon REPLACE: revive with a WIPED store — the
                    # probe + store-sync path must rebuild it from the
                    # quorum (Monitor.cc sync_start)
                    try:
                        self.cluster.replace_mon(mon)
                        self.actions += 1
                        return f"replace mon.{mon} (wiped store)"
                    except (TimeoutError, RuntimeError):
                        self.downed_mon = mon   # retry next step
                        return f"replace mon.{mon} pending"
                self.cluster.run_mon(mon)
                self.actions += 1
                return f"revive mon.{mon}"
            if self.downed_mon is None and roll < 0.1:
                mon = self.rng.choice(sorted(self.cluster.mons))
                self.cluster.kill_mon(mon)
                self.downed_mon = mon
                self.actions += 1
                return f"kill mon.{mon}"
        if self.pg_nums and roll < 0.15:
            pool = self.rng.choice(sorted(self.pg_nums))
            if self.pgp_nums[pool] < self.pg_nums[pool]:
                self.pgp_nums[pool] = self.pg_nums[pool]
                self._mon_cmd({"prefix": "osd pool set", "pool": pool,
                               "var": "pgp_num",
                               "val": str(self.pgp_nums[pool])})
                self.actions += 1
                return f"grow pgp_num pool.{pool} -> {self.pgp_nums[pool]}"
            if self.pg_nums[pool] < self.pg_num_max:
                self.pg_nums[pool] *= 2
                self._mon_cmd({"prefix": "osd pool set", "pool": pool,
                               "var": "pg_num",
                               "val": str(self.pg_nums[pool])})
                self.actions += 1
                return f"grow pg_num pool.{pool} -> {self.pg_nums[pool]}"
            roll = 0.15 + self.rng.random() * 0.85
        if self.downed and (roll < 0.45 or len(self.downed)
                            >= self.max_down):
            osd = self.downed.pop(self.rng.randrange(len(self.downed)))
            self.cluster.run_osd(osd)
            self._mon_cmd({"prefix": "osd in", "id": str(osd)})
            self.actions += 1
            return f"revive osd.{osd}"
        if roll < 0.7 and len(up) > self.min_up \
                and len(self.downed) < self.max_down:
            osd = self.rng.choice(up)
            self.cluster.kill_osd(osd)
            self._mon_cmd({"prefix": "osd down", "id": str(osd)})
            self.downed.append(osd)
            self.actions += 1
            return f"kill osd.{osd}"
        if self.outed:
            osd = self.outed.pop()
            self._mon_cmd({"prefix": "osd in", "id": str(osd)})
            self.actions += 1
            return f"in osd.{osd}"
        candidates = [i for i in up if i not in self.outed]
        if candidates and len(up) - len(self.outed) > self.min_up:
            osd = self.rng.choice(candidates)
            self._mon_cmd({"prefix": "osd out", "id": str(osd)})
            self.outed.append(osd)
            self.actions += 1
            return f"out osd.{osd}"
        return "noop"

    def heal(self) -> None:
        """Revive everything and bring every OSD back in."""
        if self.downed_mon is not None:
            self.cluster.run_mon(self.downed_mon)
            self.downed_mon = None
        for osd in list(self.downed):
            self.cluster.run_osd(osd)
        self.downed.clear()
        for osd in list(self.outed):
            self._mon_cmd({"prefix": "osd in", "id": str(osd)})
        self.outed.clear()


def run_soak(duration: float = 25.0, seed: int = 7,
             n_osds: int = 6, base_path: str = "",
             ms_type: str = "loopback", n_mons: int = 1,
             thrash_mons: bool = False,
             device_chaos: bool = False,
             scrub_storm: bool = False) -> dict:
    """The standalone soak: returns a result dict (the pytest wrapper
    asserts).  OSDs are filestore-backed: kill_osd is PROCESS death with
    the disk surviving, like the reference Thrasher — wiping stores
    faster than recovery completes would lose data in any storage
    system.

    ``device_chaos=True`` additionally storms the DEVICE runtime
    (DeviceChaos): failpoints fire at the dispatch engines' device
    boundaries on every kernel channel while OSDs die around them.
    The acked-object durability contract is unchanged — a device fault
    may slow an op (retry ladder) or degrade it host-side (breaker +
    bit-exact oracle) but never corrupt it — and after the storm every
    breaker must re-close (reconvergence to the device path).

    ``scrub_storm=True`` runs ScrubStorm alongside: continuous deep
    scrub of every PG plus on-disk bit-flip injection into a dedicated
    integrity pool while OSDs die and (with device_chaos) the digest
    channel itself degrades.  Gate: every injected corruption detected
    and repaired, zero acked corruption."""
    if not base_path:
        import tempfile
        base_path = tempfile.mkdtemp(prefix="thrash-")
    ici_t = None
    if ms_type == "ici":
        from ceph_tpu.msg.ici import IciTransport
        ici_t = IciTransport.instance()
    chaos = None
    storm = None
    osd_conf = {}
    if device_chaos:
        # toy pools sit under the osdmap_mapping_min_pgs floor and
        # would never exercise the fused-ladder device channel: lower
        # it so pg_finish traffic is real during the storm
        osd_conf["osdmap_mapping_min_pgs"] = 1
    if scrub_storm:
        # sweeps must not park a whole chunk timeout behind every
        # killed replica: short gathers + verification windows keep
        # the storm's scrub duty cycle high
        osd_conf.setdefault("osd_scrub_chunk_timeout", 4.0)
        osd_conf.setdefault("osd_scrub_verify_timeout", 8.0)
    # toy commits stage a handful of blocks each; drop the batch
    # floors so the bluestore_data channel is live for every storm
    osd_conf.setdefault("bluestore_batched_csum_min", 1)
    osd_conf.setdefault("bluestore_batched_read_min", 1)
    # bluestore-backed soak: kill_osd is a clean shutdown (the store
    # unmounts), so the disk-backed store is safe here AND the
    # bluestore_data channel sees real commit traffic all storm long
    c = MiniCluster(n_osds=n_osds, ms_type=ms_type,
                    store_type="bluestore", n_mons=n_mons,
                    base_path=base_path, heartbeats=True,
                    osd_conf=osd_conf).start()
    try:
        c.wait_for_osd_count(n_osds)
        client = c.client(timeout=20.0)
        # chaos mode runs the fused ladder on these toy pools
        # (min_pgs=1 above): on a COLD process the first map epoch per
        # pool pays the ladder's jit trace+compile inside _handle_map
        # — tens of seconds on a 1-core host — so the epoch wait must
        # be compile-sized or a cold standalone run flakes at setup
        ept = 90.0 if device_chaos else 10.0
        rep = c.create_pool(client, pg_num=8, size=3,
                            epoch_timeout=ept)
        ec = c.create_pool(client, pg_num=8, pool_type="erasure",
                           k=2, m=2, epoch_timeout=ept)
        rng = random.Random(seed)
        w1 = Workload(c, rep, "r", random.Random(seed + 1))
        w2 = Workload(c, ec, "e", random.Random(seed + 2),
                      payload_scale=400)
        w1.start()
        w2.start()
        th = Thrasher(c, seed=seed, pools={rep: 8, ec: 8},
                      thrash_mons=thrash_mons)
        if scrub_storm:
            spool = c.create_pool(client, pg_num=8, size=3,
                                  epoch_timeout=ept)
            storm = ScrubStorm(c, spool, random.Random(seed + 4))
            storm.start()
        if device_chaos:
            # fault-free warmup first: on a cold process the first ops
            # PAY the jit compiles (encode kernel, mapper, ladder);
            # arming failpoints before any op has ever succeeded would
            # storm an empty pipeline and measure nothing.  The wait is
            # compile-sized like the epoch wait above: on a loaded host
            # the first op of each pool alone outlasts its 6 s timeout
            wdl = time.time() + ept
            while w1.ops + w2.ops < 6 and time.time() < wdl:
                time.sleep(0.25)
            chaos = DeviceChaos(random.Random(seed + 3))
            chaos.start()
        deadline = time.time() + duration
        log = []
        health_seen: set[str] = set()

        def sample_health() -> None:
            import json as _json
            try:
                rc, out = client.mon_command({"prefix": "health"})
                if rc == 0:
                    h = _json.loads(out)
                    health_seen.add(h["status"])
                    for ch in h["checks"]:
                        health_seen.add(ch["check"])
            except (TimeoutError, OSError, ValueError):
                pass

        while time.time() < deadline:
            log.append(th.step())
            if chaos is not None:
                log.append(chaos.step())
            sample_health()
            time.sleep(rng.uniform(0.5, 1.5))
        reconverged = None
        fault_digest: dict = {}
        if chaos is not None:
            # faults clear BEFORE the heal/verify phase: the storm is
            # over, the probes must re-close every breaker and traffic
            # must return to the device path while recovery drains
            chaos.clear()
            reconverged, fault_digest = chaos.await_reconverged(cluster=c)
        w1.stop()
        w2.stop()
        if storm is not None:
            storm.stop()
        w1.join(timeout=30)
        w2.join(timeout=30)
        if storm is not None:
            storm.join(timeout=60)
        th.heal()
        c.wait_for_osd_count(n_osds, timeout=30)
        c.wait_for_epoch(c.mon.osdmap.epoch, timeout=30)
        time.sleep(3.0)   # recovery settles
        scrub_result = storm.verify() if storm is not None else None
        vclient = c.client(timeout=20.0)
        # health must transition: WARN during the storm, OK after heal
        import json as _json
        final_health = ""
        hdl = time.time() + 30
        while time.time() < hdl:
            try:
                rc, out = vclient.mon_command({"prefix": "health"})
            except (TimeoutError, OSError):
                time.sleep(0.5)
                continue
            if rc == 0:
                final_health = _json.loads(out)["status"]
                if final_health == "HEALTH_OK":
                    break
            time.sleep(0.5)
        bad1 = w1.final_verify(vclient)
        bad2 = w2.final_verify(vclient)
        ici_outstanding = None
        if ici_t is not None:
            # staged buffers must all be redeemed or reaped: wait out
            # the resend grace + loss TTL.  Keep the reading that hit
            # zero — re-sampling could catch a buffer a still-running
            # daemon staged a moment later
            hdl = time.time() + ici_t.TTL + ici_t.GRACE + 2
            while True:
                ici_outstanding = ici_t.outstanding()
                if ici_outstanding[0] == 0 or time.time() >= hdl:
                    break
                time.sleep(0.5)
        return {
            "actions": th.actions, "log": log,
            "health_seen": sorted(health_seen),
            "final_health": final_health,
            "ici_outstanding": ici_outstanding,
            "rep_ops": w1.ops, "ec_ops": w2.ops,
            "rep_errors": w1.errors, "ec_errors": w2.errors,
            "corruptions": w1.corruptions + w2.corruptions,
            "lost_rep": bad1, "lost_ec": bad2,
            "chaos_actions": chaos.actions if chaos else 0,
            "breakers_reconverged": reconverged,
            "fault_digest": fault_digest,
            "scrub_storm": scrub_result,
        }
    finally:
        if chaos is not None:
            chaos.clear()   # failpoints are process-global: a failed
            # soak must never leave them armed for the next test
        if storm is not None:
            storm.stop()
        c.stop()


if __name__ == "__main__":
    import json
    import sys
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    flags = ("--chaos", "--scrub-storm")
    args = [a for a in sys.argv[1:] if a not in flags]
    res = run_soak(duration=float(args[0]) if args else 25.0,
                   device_chaos="--chaos" in sys.argv,
                   scrub_storm="--scrub-storm" in sys.argv)
    print(json.dumps({k: v for k, v in res.items() if k != "log"}))
    sres = res.get("scrub_storm") or {}
    bad = (res["corruptions"] or res["lost_rep"] or res["lost_ec"]
           or res["breakers_reconverged"] is False
           or bool(sres.get("unrepaired"))
           # a storm that never seeded its integrity pool proved
           # nothing — the gate must not pass vacuously
           or (res.get("scrub_storm") is not None
               and sres.get("objects", 0) == 0))
    sys.exit(1 if bad else 0)
