"""Map-epoch consumption: one replicated pool on a large two-level CRUSH
map, its PG mapping kept by the context's ``SharedPGMappingService``,
and a closed loop of single-OSD epochs applied through ``update_to``.

Epochs come in groups of four on one OSD drawn from the seed — out,
reweight to 0x8000, down, restored (up, full weight) — so the map is
the same after every group and does not drift over the window.  The map
itself (bucket weights, reweights) is drawn here, handed to the program
through its own map types, and to the plain reference
(perfbench/reference/crush_plain.py) as lists.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from perfbench.harness.cell import Check
from perfbench.harness.closed_loop import no_span
from perfbench.harness.manifest import ROOT
from perfbench.harness.window import quantile
from perfbench.reference import crush_plain
from perfbench.systems import engine_faults

SPANS = ("epoch_apply", "generator")
TRAFFIC_KIND = "epoch_groups"
KINDS = ("out", "reweight", "down", "restore")
#: where the reference's table of the whole map is kept from run to run
#: of one checkout, beside the compile cache (.gitignore lists it)
CACHE_DIR = os.path.join(ROOT, ".perfbench_cache")


@dataclass
class Epoch:
    index: int
    group: int
    kind: str
    osd: int
    t_start: float
    t_end: float
    changed: int
    #: only in the groups the check follows
    changed_pgs: frozenset | None = None
    answers: dict = field(default_factory=dict)     # pg -> lookup()


@dataclass
class EpochLog:
    epochs: list[Epoch] = field(default_factory=list)
    t_open: float = 0.0
    seconds: float = 0.0

    def in_window(self) -> list[Epoch]:
        t_close = self.t_open + self.seconds
        return [e for e in self.epochs
                if self.t_open <= e.t_end < t_close and e.group >= 0]


class System:
    def __init__(self, cell, seed: int, span=None):
        self.cell = cell
        self.seed = seed
        self.span = span or no_span
        self.dep = cell.config["deployment"]
        if tuple(cell.traffic["group"]) != KINDS:
            # the check's reference states (_state) follow this order
            raise ValueError(f"a group of epochs is {KINDS}")
        self.log = EpochLog()
        self.ctx = None
        self.svc = None
        self.map = None             # the program's OSDMap, current epoch
        self.plain = None           # the reference's map, base state
        self.full_in: np.ndarray | None = None
        self.rng = np.random.default_rng((seed, 0x0e9c))
        self.first_build_s = 0.0
        self.initial_answers: dict = {}
        self._applied = 0

    # -- the deployment -----------------------------------------------------

    def _draw_map(self):
        """The deployment's map, from the configuration's `map_seed`:
        OSD weights skewed over [0.5, 2.0), a tenth of the OSDs
        reweighted to 0.5 and a fiftieth out, so that the retry ladder
        fires — the shape of the repo's BASELINE config 5 map.  The map
        is the deployment's and the same for every run: the program
        compiles a map's bucket tables into its CRUSH executable, so a
        map drawn from `--seed` would compile for a minute in every
        run.  The run's seed draws the epochs and the samples."""
        dep = self.dep
        hosts, per_host = int(dep["hosts"]), int(dep["osds_per_host"])
        n = hosts * per_host
        rng = np.random.default_rng(int(dep["map_seed"]))
        item_w = rng.integers(0x8000, 0x20000, (hosts, per_host))
        reweight = np.full(n, 0x10000, dtype=np.int64)
        idx = rng.permutation(n)
        reweight[idx[:n // 10]] = 0x8000
        reweight[idx[n // 10:n // 10 + n // 50]] = 0
        return item_w, reweight

    def _build_maps(self):
        """The deployment's map twice: as the program's OSDMap (epoch 2,
        every OSD up) and as the reference's plain lists."""
        from ceph_tpu.crush import build_two_level_map
        from ceph_tpu.osd import OSDMap, PGPool
        dep = self.dep
        hosts, per_host = int(dep["hosts"]), int(dep["osds_per_host"])
        pool_id, pg_num, size = (int(dep["pool_id"]), int(dep["pg_num"]),
                                 int(dep["size"]))
        item_w, reweight = self._draw_map()
        crush, _root, rid = build_two_level_map(hosts, per_host)
        root = crush.bucket(-1)
        plain_hosts = {}
        for h, host_id in enumerate(root.items):
            b = crush.bucket(host_id)
            b.item_weights = [int(w) for w in item_w[h]]
            b.weight = sum(b.item_weights)
            plain_hosts[int(host_id)] = crush_plain.Bucket(
                int(host_id), np.array(b.items, dtype=np.int64),
                item_w[h].astype(np.int64))
        root.item_weights = [crush.bucket(h).weight for h in root.items]
        root.weight = sum(root.item_weights)
        self.plain = crush_plain.PlainMap(
            root=crush_plain.Bucket(
                -1, np.array(root.items, dtype=np.int64),
                item_w.sum(axis=1).astype(np.int64)),
            hosts=plain_hosts, reweight=[int(w) for w in reweight],
            up=[True] * len(reweight), pool_id=pool_id, pg_num=pg_num,
            size=size)
        m = OSDMap(crush=crush, epoch=2)
        m.set_max_osd(len(reweight))
        for o in range(len(reweight)):
            m.osd_state[o] = 3                      # exists | up
            m.osd_weight[o] = int(reweight[o])
        m.pools[pool_id] = PGPool(pool_id=pool_id, size=size,
                                  crush_rule=rid, pg_num=pg_num)
        self.full_in = np.flatnonzero(reweight == 0x10000)
        return m

    def setup(self) -> None:
        from ceph_tpu.common.context import CephTpuContext
        dep = self.dep
        pool_id, pg_num = int(dep["pool_id"]), int(dep["pg_num"])
        m = self._build_maps()
        self.ctx = CephTpuContext("perfbench-map")
        self.ctx.conf.set("kernel_mesh_devices",
                          int(dep["kernel_mesh_devices"]))
        self.svc = self.ctx.mapping_service()
        t0 = time.perf_counter()
        if not self.svc.update_to(m).full:
            raise RuntimeError("the first map was not a full build")
        self.first_build_s = time.perf_counter() - t0
        self.map = m
        # the first table, for the check: a seeded sample of its rows
        pick = np.random.default_rng((self.seed, 0x1a17)).choice(
            pg_num, int(self.cell.traffic["verify_initial_pgs"]),
            replace=False)
        self.initial_answers = {
            int(pg): self.svc.lookup(m, pool_id, int(pg)) for pg in pick}
        for _ in range(int(self.cell.traffic["warm_groups"])):
            self._group(-1, follow=False)

    # -- the traffic ----------------------------------------------------------

    def _next_map(self, kind: str, osd: int):
        from ceph_tpu.osd.osdmap import OSD_UP
        new = self.map.copy()
        new.epoch = self.map.epoch + 1
        if kind == "out":
            new.osd_weight[osd] = 0
        elif kind == "reweight":
            new.osd_weight[osd] = 0x8000
        elif kind == "down":
            new.osd_state[osd] &= ~OSD_UP
        else:
            new.osd_state[osd] |= OSD_UP
            new.osd_weight[osd] = 0x10000
        return new

    def _group(self, group: int, follow: bool, deadline=None,
               on_close=None) -> bool:
        """Four epochs on one OSD.  Returns True once `deadline` has
        passed; the group is finished all the same, so that the map is
        whole again."""
        pool_id = int(self.dep["pool_id"])
        osd = int(self.rng.choice(self.full_in))
        quiet_n = int(self.cell.traffic["verify_quiet_pgs"])
        closed = False
        held: set = set()
        if follow:
            # the PGs that hold the OSD while the map is whole, by the
            # program's own table: the ones an epoch of this group can
            # move, so their answers are kept.  The check holds this set
            # against the reference's (`verify`): a PG that it lacks
            # has a wrong row.
            held = set(np.flatnonzero(
                (self.svc._mapping.get_raw(pool_id) == osd)
                .any(axis=1)).tolist())
        for kind in KINDS:
            with self.span("generator"):
                new = self._next_map(kind, osd)
            with self.span("epoch_apply"):
                t0 = time.perf_counter()
                upd = self.svc.update_to(new, from_epoch=self.map.epoch)
                t1 = time.perf_counter()
            if upd.full:
                raise RuntimeError(f"epoch {new.epoch}: no delta served")
            ep = Epoch(self._applied, group, kind, osd, t0, t1,
                       len(upd.changed))
            self._applied += 1
            if follow:
                changed = frozenset(pg for _pool, pg in upd.changed)
                quiet = self.rng.choice(int(self.dep["pg_num"]), quiet_n,
                                        replace=False).tolist()
                ep.changed_pgs = changed
                ep.answers = {
                    int(pg): self.svc.lookup(new, pool_id, int(pg))
                    for pg in sorted(changed | held | set(quiet))}
            self.log.epochs.append(ep)
            self.map = new
            if (deadline is not None and not closed
                    and time.perf_counter() >= deadline):
                closed = True
                if on_close is not None:
                    on_close()
        return closed

    def run_window(self, seconds: float, on_open, on_close) -> None:
        stride = int(self.cell.traffic["verify_group_stride"])
        offset = int(np.random.default_rng((self.seed, 0x0ff5))
                     .integers(stride))
        on_open()
        self.log.t_open = time.perf_counter()
        self.log.seconds = seconds
        deadline = self.log.t_open + seconds
        group = 0
        while not self._group(group, follow=(group % stride == offset),
                              deadline=deadline, on_close=on_close):
            group += 1

    # -- counters -------------------------------------------------------------

    def counters(self) -> dict:
        from ceph_tpu.ops import telemetry
        c: dict = {}
        mapping = telemetry.mapping_summary()
        for key in ("epoch_updates", "fused_epochs", "unfused_epochs",
                    "lookups", "fused_lookups", "lookup_fallbacks"):
            c[f"mapping.{key}"] = mapping[key]
        phases = telemetry.mapping_stats().phase_summary()
        for key, value in _flat_numbers(phases):
            c[f"mapping.phase.{key}"] = value
        stats = telemetry.dispatch_stats()
        d = stats.dump()
        for key in ("submits", "batches", "sharded_flushes"):
            c[f"encode.{key}"] = d[key]
        c["encode.mesh_devices"] = stats.mesh_devices
        c["encode.faults"] = engine_faults(stats.fault_dump())
        return c

    def notes(self, before: dict, after: dict) -> dict:
        ms = [(e.t_end - e.t_start) * 1e3 for e in self.log.in_window()]
        facts = {"window_epochs": len(ms), "applied": self._applied,
                 "first_build_s": round(self.first_build_s, 3)}
        if ms:
            facts["apply_ms"] = {
                "min": round(min(ms), 2), "p50": round(quantile(ms, .5), 2),
                "p95": round(quantile(ms, .95), 2), "max": round(max(ms), 2)}
        return facts

    # -- the check ------------------------------------------------------------

    def _state(self, osd: int, step: int) -> crush_plain.PlainMap:
        """The reference's map after `step` epochs of a group on `osd`
        (0 = the base map; 4 = the base map again)."""
        base = self.plain
        if step % 4 == 0:
            return base
        reweight, up = list(base.reweight), list(base.up)
        reweight[osd] = 0 if step == 1 else 0x8000
        up[osd] = step < 3
        return crush_plain.PlainMap(base.root, base.hosts, reweight, up,
                                    base.pool_id, base.pg_num, base.size)

    def _base_table(self) -> np.ndarray:
        """The reference's `up` of every PG while the map is whole.
        It takes about a minute to make, so a checkout's first run
        keeps it, under a name made of the deployment and of the
        reference's own source."""
        with open(crush_plain.__file__, "rb") as f:
            key = hashlib.sha256(
                f.read() + json.dumps(self.dep, sort_keys=True).encode())
        path = os.path.join(CACHE_DIR,
                            f"up_table_{key.hexdigest()[:24]}.npy")
        try:
            table = np.load(path)
            if table.shape == (self.plain.pg_num, self.plain.size):
                return table
        except (OSError, ValueError):
            pass
        table = crush_plain.up_table(self.plain)
        os.makedirs(CACHE_DIR, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            np.save(f, table)
        os.replace(tmp, path)
        return table

    def verify(self) -> list[Check]:
        base = self._base_table()
        rows: dict[tuple, tuple] = {}

        def ref(osd: int, step: int, pg: int) -> tuple:
            if step % 4 == 0:
                up = [int(o) for o in base[pg] if o >= 0]
                return (up, up[0] if up else -1) * 2
            key = (osd, step, pg)
            if key not in rows:
                up, primary = crush_plain.up_of(self._state(osd, step), pg)
                rows[key] = (up, primary, up, primary)
            return rows[key]

        rows_wrong = delta_wrong = held_missed = compared = 0
        for ep in self.log.epochs:
            if ep.changed_pgs is None:
                continue
            compared += 1
            step = KINDS.index(ep.kind) + 1
            for pg, got in ep.answers.items():
                want = ref(ep.osd, step, pg)
                rows_wrong += tuple(got) != want
                moved = ref(ep.osd, step - 1, pg) != want
                delta_wrong += moved != (pg in ep.changed_pgs)
            # a PG that holds the OSD by the reference and whose answer
            # was not kept: the program's table of the whole map lacks
            # the OSD in that row, and the epoch did not report it
            held = np.flatnonzero((base == ep.osd).any(axis=1))
            held_missed += sum(int(pg) not in ep.answers for pg in held)
        initial_wrong = sum(tuple(got) != ref(0, 0, pg)
                            for pg, got in self.initial_answers.items())
        after = self.counters()
        host_stood_in = (after["mapping.unfused_epochs"]
                         + after["mapping.lookup_fallbacks"]
                         + after["encode.faults"])
        want_epochs = int(self.cell.traffic["verify_min_epochs"])
        checks = [
            Check("rows_differ_from_reference", rows_wrong, 0),
            Check("changed_set_differs_from_reference", delta_wrong, 0),
            Check("held_pgs_not_answered", held_missed, 0),
            Check("first_table_rows_differ", initial_wrong, 0),
            Check("host_stood_in_for_device", host_stood_in, 0),
            Check("epochs_compared_short",
                  max(0, want_epochs - compared), 0)]
        if self.cell.chips > 1:
            checks.append(Check(
                "mesh_devices_short",
                max(0, self.cell.chips - after["encode.mesh_devices"]), 0))
        return checks

    @property
    def attempted(self) -> int:
        return self._applied

    @property
    def failed(self) -> int:
        return 0        # an epoch that cannot be applied ends the run

    def close(self) -> None:
        if self.ctx is not None:
            for eng in (self.ctx._dispatch, self.ctx._decode_dispatch):
                if eng is not None:
                    eng.stop()


def _flat_numbers(d: dict, prefix: str = ""):
    for key, value in d.items():
        if isinstance(value, dict):
            yield from _flat_numbers(value, f"{prefix}{key}.")
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            yield f"{prefix}{key}", value
