"""Map-epoch consumption on a cluster that is being expanded: the
deployment of osdmap_churn_bulk.py (10,000 OSDs, one pool of 1,048,576
PGs), where every epoch edits the CRUSH map itself.

Epochs come in groups of four — a spare host of `spare_host_osds` OSDs
enters the root (`ceph osd crush add`, one epoch for the host), one OSD
of an old host has its crush weight halved (`crush reweight`), the spare
host and its OSDs leave again (`crush remove`, `osd purge`), the halved
weight comes back — so the CRUSH map is the first one again after every
group and does not drift.  The spare host's weights and the reweighted
OSD are drawn from the seed, for every group anew.

The program gets each state as an OSDMap with an edited CrushMap; the
plain reference (perfbench/reference/crush_plain.py and
crush_plain_bulk.py, as they are) gets it as lists built here, never
from the program's map.  The third guarantee of the configuration — a
CRUSH edit that keeps the rule and the hierarchy's depth is served by
the programs the first map built — is held by the program's own counter
of programs traced, which `setup` looks for first: a program without it
fails at once, before any map is built.
"""

from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np

from perfbench.harness.cell import Check
from perfbench.harness.closed_loop import no_span
from perfbench.reference import crush_plain, crush_plain_bulk
from perfbench.systems import osdmap_churn, osdmap_churn_bulk

SPANS = osdmap_churn.SPANS
TRAFFIC_KIND = osdmap_churn.TRAFFIC_KIND
KINDS = ("host_add", "crush_reweight", "host_remove", "crush_restore")
#: the program's counters of what a changed CRUSH map cost it
TABLE_COUNTERS = ("crush_table_builds", "crush_table_upload_bytes",
                  "crush_program_builds")
EXISTS_UP = 3


class System(osdmap_churn_bulk.System):
    def __init__(self, cell, seed: int, span=None):
        # not osdmap_churn's __init__: it holds the group to its own
        # four kinds
        self.cell = cell
        self.seed = seed
        self.span = span or no_span
        self.dep = cell.config["deployment"]
        if tuple(cell.traffic["group"]) != KINDS:
            # the check's reference states (_states) follow this order
            raise ValueError(f"a group of epochs is {KINDS}")
        self.log = osdmap_churn.EpochLog()
        self.ctx = self.svc = self.map = self.plain = None
        self.full_in: np.ndarray | None = None
        self.rng = np.random.default_rng((seed, 0x0e9c))
        self.first_build_s = 0.0
        self.initial_answers: dict = {}
        self._applied = 0
        self.spare = int(self.dep["spare_host_osds"])
        self.spare_host = 0         # its bucket id, the next free one
        #: group -> (the reweighted OSD, the spare host's weights)
        self.groups: dict[int, tuple[int, np.ndarray]] = {}
        self._builds_after_first_map = 0

    # -- the deployment -----------------------------------------------------

    def setup(self) -> None:
        from ceph_tpu.ops import telemetry
        summary = telemetry.mapping_summary()
        if "crush_program_builds" not in summary:
            raise SystemExit(
                f"perfbench: {self.cell.config_name} needs a program that "
                f"serves an edited CRUSH map without building programs and "
                f"counts those it builds: mapping_summary() has no "
                f"crush_program_builds")
        self._diffs_at_setup = summary["delta_device_diffs"]
        from ceph_tpu.common.context import CephTpuContext
        dep = self.dep
        pool_id, pg_num = int(dep["pool_id"]), int(dep["pg_num"])
        m = self._build_maps()
        self.spare_host = m.crush.next_bucket_id()
        self.ctx = CephTpuContext("perfbench-map")
        self.ctx.conf.set("kernel_mesh_devices",
                          int(dep["kernel_mesh_devices"]))
        self.svc = self.ctx.mapping_service()
        t0 = time.perf_counter()
        if not self.svc.update_to(m).full:
            raise RuntimeError("the first map was not a full build")
        self.first_build_s = time.perf_counter() - t0
        self._builds_after_first_map = telemetry.mapping_summary()[
            "crush_program_builds"]
        self.map = m
        pick = np.random.default_rng((self.seed, 0x1a17)).choice(
            pg_num, int(self.cell.traffic["verify_initial_pgs"]),
            replace=False)
        self.initial_answers = {
            int(pg): self.svc.lookup(m, pool_id, int(pg)) for pg in pick}
        for _ in range(int(self.cell.traffic["warm_groups"])):
            self._group(-1, follow=False)

    # -- the traffic ----------------------------------------------------------

    def _host_of(self, osd: int) -> int:
        """Bucket id of the old host that holds `osd`, as
        build_two_level_map numbers them."""
        return -(osd // int(self.dep["osds_per_host"]) + 2)

    def _spare_osds(self) -> list[int]:
        n = int(self.dep["hosts"]) * int(self.dep["osds_per_host"])
        return list(range(n, n + self.spare))

    def _edited_crush(self, buckets: dict):
        """The current CrushMap with the buckets of `buckets` (id ->
        Bucket, or None to take one out) in the place of its own, and
        the root's weights following; a new object, the old one is
        left as published."""
        old = self.map.crush
        crush = copy.copy(old)
        crush.buckets = list(old.buckets)
        for bid, b in buckets.items():
            pos = -1 - bid
            while pos >= len(crush.buckets):
                crush.buckets.append(None)
            crush.buckets[pos] = b
        while crush.buckets and crush.buckets[-1] is None:
            crush.buckets.pop()
        root = old.bucket(-1)
        hosts = [h for h in root.items if buckets.get(h, 1) is not None]
        hosts += [h for h in buckets if h not in hosts
                  and buckets[h] is not None]
        weights = [crush.bucket(h).weight for h in hosts]
        crush.buckets[0] = dataclasses.replace(
            root, items=hosts, item_weights=weights, weight=sum(weights))
        return crush

    def _reweighted_host(self, osd: int, halved: bool):
        """The bucket of `osd`'s host with the OSD's crush weight halved,
        or as the deployment drew it."""
        host = self.map.crush.bucket(self._host_of(osd))
        plain = self.plain.hosts[host.id]
        weights = [int(w) for w in plain.weights]
        if halved:
            at = host.items.index(osd)
            weights[at] >>= 1
        return dataclasses.replace(host, item_weights=weights,
                                   weight=sum(weights))

    def _next_map(self, kind: str, osd: int, weights: np.ndarray):
        from ceph_tpu.crush.builder import make_bucket
        from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2
        new = self.map.copy()
        new.epoch = self.map.epoch + 1
        spare = self._spare_osds()
        if kind == "host_add":
            new.crush = self._edited_crush({
                self.spare_host: make_bucket(
                    self.spare_host, CRUSH_BUCKET_STRAW2, 1, spare,
                    [int(w) for w in weights])})
            new.crush.max_devices = max(new.crush.max_devices,
                                        spare[-1] + 1)
            if new.max_osd <= spare[-1]:
                new.set_max_osd(spare[-1] + 1)
            for o in spare:
                new.osd_state[o] = EXISTS_UP
                new.osd_weight[o] = 0x10000
        elif kind == "host_remove":
            new.crush = self._edited_crush({self.spare_host: None})
            for o in spare:                     # crush remove, osd purge
                new.osd_state[o] = 0
                new.osd_weight[o] = 0
        else:
            host = self._reweighted_host(osd, kind == "crush_reweight")
            new.crush = self._edited_crush({host.id: host})
        return new

    def _group(self, group: int, follow: bool, deadline=None,
               on_close=None) -> bool:
        """Four epochs: a host in, an OSD's crush weight halved, the
        host out, the weight back.  Returns True once `deadline` has
        passed; the group is finished all the same, so that the CRUSH
        map is the first one again."""
        pool_id, pg_num = int(self.dep["pool_id"]), int(self.dep["pg_num"])
        osd = int(self.rng.choice(self.full_in))
        weights = self.rng.integers(0x8000, 0x20000, self.spare)
        self.groups[group] = (osd, weights)
        quiet_n = int(self.cell.traffic["verify_quiet_pgs"])
        cap = int(self.cell.traffic["verify_changed_sample"])
        closed = False
        held: set = set()
        if follow:
            # the PGs that hold the reweighted OSD while the map is
            # whole, by the program's own table (as osdmap_churn): the
            # check holds this set against the reference's
            held = set(np.flatnonzero(
                (self.svc._mapping.get_raw(pool_id) == osd)
                .any(axis=1)).tolist())
        for kind in KINDS:
            with self.span("generator"):
                new = self._next_map(kind, osd, weights)
            with self.span("epoch_apply"):
                t0 = time.perf_counter()
                upd = self.svc.update_to(new, from_epoch=self.map.epoch)
                t1 = time.perf_counter()
            if upd.full:
                raise RuntimeError(f"epoch {new.epoch}: no delta served")
            ep = osdmap_churn.Epoch(self._applied, group, kind, osd, t0, t1,
                                    len(upd.changed))
            self._applied += 1
            if follow:
                changed = np.array(sorted(pg for _pool, pg in upd.changed),
                                   dtype=np.int64)
                some = (changed if len(changed) <= cap
                        else self.rng.choice(changed, cap, replace=False))
                quiet = self.rng.choice(pg_num, quiet_n, replace=False)
                ep.changed_pgs = frozenset(changed.tolist())
                ep.answers = {
                    int(pg): self.svc.lookup(new, pool_id, int(pg))
                    for pg in sorted(set(some.tolist()) | held
                                     | set(quiet.tolist()))}
            self.log.epochs.append(ep)
            self.map = new
            if (deadline is not None and not closed
                    and time.perf_counter() >= deadline):
                closed = True
                if on_close is not None:
                    on_close()
        return closed

    # -- counters -------------------------------------------------------------

    def counters(self) -> dict:
        from ceph_tpu.ops import telemetry
        c = super().counters()
        mapping = telemetry.mapping_summary()
        for key in TABLE_COUNTERS:
            c[f"mapping.{key}"] = mapping[key]
        return c

    # -- the check ------------------------------------------------------------

    def _states(self, group: int) -> list[crush_plain.PlainMap]:
        """The reference's maps after 0, 1, 2 and 3 epochs of `group`
        (after the fourth it is the first again), from plain lists:
        the deployment's own (`_build_maps`) and the group's draws."""
        osd, weights = self.groups[group]
        base = self.plain
        spare = self._spare_osds()
        hid = self._host_of(osd)
        host = base.hosts[hid]
        halved = host.weights.copy()
        halved[list(host.items).index(osd)] >>= 1
        new_host = crush_plain.Bucket(
            self.spare_host, np.array(spare, dtype=np.int64),
            np.asarray(weights, dtype=np.int64))

        def state(grown: bool, halve: bool) -> crush_plain.PlainMap:
            hosts = dict(base.hosts)
            if halve:
                hosts[hid] = crush_plain.Bucket(hid, host.items, halved)
            items = [int(h) for h in base.root.items]
            if grown:
                hosts[self.spare_host] = new_host
                items.append(self.spare_host)
            root = crush_plain.Bucket(
                -1, np.array(items, dtype=np.int64),
                np.array([int(hosts[h].weights.sum()) for h in items],
                         dtype=np.int64))
            # the spare OSDs' ids exist in the vectors of every state;
            # while the host is out of the map nothing reads them
            fill = (0x10000, True) if grown else (0, False)
            return crush_plain.PlainMap(
                root, hosts, list(base.reweight) + [fill[0]] * self.spare,
                list(base.up) + [fill[1]] * self.spare,
                base.pool_id, base.pg_num, base.size)

        return [state(False, False), state(True, False),
                state(True, True), state(False, True)]

    def verify(self) -> list[Check]:
        base = self._base_table()
        rows_wrong = delta_wrong = held_missed = compared = 0
        host_delta_wrong = 0
        first_group = None
        states: dict[int, list] = {}

        def rows_of(group: int, step: int, pgs: np.ndarray) -> np.ndarray:
            if step % 4 == 0:
                return base[pgs]
            return crush_plain_bulk.up_rows(states[group][step], pgs)

        for ep in self.log.epochs:
            if ep.changed_pgs is None:
                continue
            compared += 1
            if ep.group not in states:
                states[ep.group] = self._states(ep.group)
            if first_group is None:
                first_group = ep.group
            step = KINDS.index(ep.kind) + 1
            pgs = np.array(sorted(ep.answers), dtype=np.int64)
            want = rows_of(ep.group, step, pgs)
            before = rows_of(ep.group, step - 1, pgs)
            for pg, row, old in zip(pgs.tolist(), want, before):
                up = [int(o) for o in row if o >= 0]
                answer = (up, up[0] if up else -1) * 2
                rows_wrong += tuple(ep.answers[pg]) != answer
                moved = not np.array_equal(row, old)
                delta_wrong += moved != (pg in ep.changed_pgs)
            # a PG that holds the reweighted OSD by the reference's
            # first table and whose answer was not kept
            held = np.flatnonzero((base == ep.osd).any(axis=1))
            held_missed += sum(int(pg) not in ep.answers for pg in held)
        if first_group is not None:
            host_delta_wrong = self._host_epochs_differ(
                base, states[first_group],
                {ep.kind: ep.changed_pgs for ep in self.log.epochs
                 if ep.group == first_group})
        initial_wrong = 0
        for pg, got in self.initial_answers.items():
            up = [int(o) for o in base[pg] if o >= 0]
            initial_wrong += tuple(got) != (up, up[0] if up else -1) * 2
        after = self.counters()
        host_stood_in = (after["mapping.unfused_epochs"]
                         + after["mapping.lookup_fallbacks"]
                         + after["encode.faults"])
        want_epochs = int(self.cell.traffic["verify_min_epochs"])
        # every epoch of every phase has a previous table to be diffed
        # with, and one pool: one device diff an epoch
        diffs = after["mapping.delta_device_diffs"] - self._diffs_at_setup
        return [
            Check("rows_differ_from_reference", rows_wrong, 0),
            Check("changed_set_differs_from_reference", delta_wrong, 0),
            Check("held_pgs_not_answered", held_missed, 0),
            Check("first_table_rows_differ", initial_wrong, 0),
            Check("host_epoch_changed_set_differs", host_delta_wrong, 0),
            Check("host_stood_in_for_device", host_stood_in, 0),
            Check("epochs_compared_short",
                  max(0, want_epochs - compared), 0),
            Check("device_diffs_short",
                  max(0, self._applied - diffs), 0),
            Check("crush_programs_built_after_first_map",
                  after["mapping.crush_program_builds"]
                  - self._builds_after_first_map, 0)]

    def _host_epochs_differ(self, base: np.ndarray, states: list,
                            lists: dict) -> int:
        """The first followed group's two host epochs against the
        reference, beyond the samples.  The arrival: its `changed` list
        against the whole pool, both ways — the first table beside the
        table of the grown map (one table a run).  The removal runs
        between two maps with a halved weight, of which no table is
        made: its list is held on every PG of it and on every PG the
        arrival moved (the PGs a removal gives back), both ways, by
        their rows; the other PGs have the epochs' samples."""
        grown = crush_plain_bulk.up_table(states[1])
        moved = set(np.flatnonzero((base != grown).any(axis=1)).tolist())
        wrong = len(moved ^ lists["host_add"])
        pgs = np.array(sorted(moved | lists["host_remove"]), dtype=np.int64)
        if len(pgs):
            before = crush_plain_bulk.up_rows(states[2], pgs)
            after = crush_plain_bulk.up_rows(states[3], pgs)
            gone = (before != after).any(axis=1)
            wrong += sum(bool(g) != (pg in lists["host_remove"])
                         for pg, g in zip(pgs.tolist(), gone))
        return wrong
