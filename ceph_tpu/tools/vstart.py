"""vstart-style in-process cluster harness (src/vstart.sh +
qa/standalone/ceph-helpers.sh analog).

Starts one mon and N osds in this process over the chosen messenger stack,
returns a handle with run_mon/run_osd/kill_osd/wait_for_clean-style helpers,
and a connected RadosClient factory — the surface the standalone QA tier
drives (SURVEY.md §4 tier 3).
"""

from __future__ import annotations

import threading
import time

from ceph_tpu.client import RadosClient
from ceph_tpu.mon import Monitor
from ceph_tpu.osd.daemon import OSDDaemon


class MiniCluster:
    _instances = 0

    def __init__(self, n_osds: int = 3, ms_type: str = "async",
                 store_type: str = "memstore", base_path: str = "",
                 heartbeats: bool = False, n_mons: int = 1,
                 auth_key=None, cephx: bool = False,
                 osd_conf: dict | None = None):
        # namespace loopback addresses per cluster: sequential tests reuse
        # names like "mon.0", and a timer from a dying daemon of the
        # previous cluster must never reach this one
        MiniCluster._instances += 1
        self._ns = f"c{MiniCluster._instances}."
        self.ms_type = ms_type
        self.store_type = store_type
        self.base_path = base_path
        self.heartbeats = heartbeats
        self.mons: dict[int, Monitor] = {}
        self.monmap: list[str] = []
        self.osds: dict[int, OSDDaemon] = {}
        self.clients: list[RadosClient] = []
        self._n_initial = n_osds
        self._n_mons = n_mons
        self.auth_key = auth_key
        #: startup config overrides applied to every OSD's context at
        #: construction (vstart.sh -o analog): knobs read before the
        #: first map lands (osd_op_queue, shard count, qos timeouts)
        self.osd_conf = dict(osd_conf or {})
        #: full cephx mode: per-entity keys + tickets (wire stacks).
        #: The seed keyring (mon keys + admin) is generated here — the
        #: `ceph-authtool` bootstrap step
        self.cephx = cephx
        self.keyring: dict[str, str] = {}
        if cephx:
            from ceph_tpu.auth.cephx import new_secret
            for i in range(n_mons):
                self.keyring[f"mon.{i}"] = new_secret()
            self.keyring["client.admin"] = new_secret()
        self.mgr = None
        self.mds = None
        self.fs_mds: list = []
        #: monotonic: a crashed daemon's loopback name is NEVER reused
        #: (len(fs_mds) would rebind a live daemon's address)
        self._fs_mds_seq = 0

    def _is_wire(self) -> bool:
        """TCP-style stacks bind host:port; loopback/ici bind names."""
        return self.ms_type not in ("loopback", "ici")

    @property
    def mon(self) -> Monitor:
        """A live monitor (prefer the leader — its map is freshest)."""
        for m in self.mons.values():
            if m.is_leader():
                return m
        return next(iter(self.mons.values()))

    @property
    def mon_host(self) -> str:
        return ",".join(self.monmap)

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "MiniCluster":
        # bind all mons first (TCP ports are ephemeral), then hand every
        # mon the complete monmap so elections can begin
        for i in range(self._n_mons):
            self.run_mon(i, defer_monmap=True)
        self.monmap = [self.mons[i].addr for i in range(self._n_mons)]
        for m in self.mons.values():
            m.set_monmap(self.monmap)
        for i in range(self._n_initial):
            self.run_osd(i)
        return self

    def run_mon(self, mon_id: int, defer_monmap: bool = False) -> Monitor:
        addr = ("127.0.0.1:0" if self._is_wire()
                else f"{self._ns}mon.{mon_id}")
        path = (f"{self.base_path}/mon.{mon_id}" if self.base_path else None)
        mon = Monitor(mon_id=mon_id, ms_type=self.ms_type, addr=addr,
                      store_path=path, auth_key=self.auth_key,
                      cephx_keyring=self.keyring if self.cephx else None)
        if defer_monmap:
            mon.init(monmap=[])   # bind only; set_monmap comes later
        else:
            # rejoin: reuse the recorded monmap slot (loopback addrs are
            # stable; TCP rejoin needs the same port, so record it)
            mon.init(monmap=[])
            if self.monmap:
                self.monmap[mon_id] = mon.addr
                monmap = list(self.monmap)
                mon.set_monmap(monmap)
                for other in self.mons.values():
                    other.monmap[mon_id] = mon.addr
        self.mons[mon_id] = mon
        return mon

    def kill_mon(self, mon_id: int) -> None:
        mon = self.mons.pop(mon_id)
        mon.shutdown()

    def add_mon(self, mon_id: int, timeout: float = 30.0) -> Monitor:
        """GROW the mon cluster at runtime (`ceph mon add` + probe):
        the new mon starts probing the existing quorum, the membership
        commits through paxos, and this returns once the joiner has
        entered the committed monmap and elections settled."""
        import json as _json
        import time as _time
        addr = ("127.0.0.1:0" if self._is_wire()
                else f"{self._ns}mon.{mon_id}")
        path = (f"{self.base_path}/mon.{mon_id}" if self.base_path
                else None)
        seeds = [m.addr for m in self.mons.values()]
        mon = Monitor(mon_id=mon_id, ms_type=self.ms_type, addr=addr,
                      store_path=path, auth_key=self.auth_key,
                      cephx_keyring=self.keyring if self.cephx else None)
        mon.init(probe=seeds)
        client = self.client(timeout=20.0)
        rc, out = client.mon_command({"prefix": "mon add",
                                      "id": mon_id, "addr": mon.addr})
        if rc != 0:
            mon.shutdown()
            raise RuntimeError(f"mon add failed: {out}")
        self.mons[mon_id] = mon
        while len(self.monmap) <= mon_id:
            self.monmap.append("")
        self.monmap[mon_id] = mon.addr
        deadline = _time.time() + timeout
        while _time.time() < deadline:
            if mon.elector is not None and not mon.elector.electing \
                    and mon.mon_id in (mon.quorum() or []):
                return mon
            _time.sleep(0.1)
        self.mons.pop(mon_id, None)
        mon.shutdown()
        raise TimeoutError(
            f"mon.{mon_id} did not join quorum: elector="
            f"{mon.elector is not None}, quorum={mon.quorum()}")

    def replace_mon(self, mon_id: int, timeout: float = 30.0) -> Monitor:
        """Kill a mon, WIPE its store, and rejoin it via probe +
        store-sync (the dead-mon-replacement flow: the fresh store pulls
        the paxos tail from the quorum before electing)."""
        import shutil
        import time as _time
        if mon_id in self.mons:
            self.kill_mon(mon_id)
        path = (f"{self.base_path}/mon.{mon_id}" if self.base_path
                else None)
        if path:
            shutil.rmtree(path, ignore_errors=True)
        addr = ("127.0.0.1:0" if self._is_wire()
                else f"{self._ns}mon.{mon_id}")
        seeds = [m.addr for m in self.mons.values()]
        mon = Monitor(mon_id=mon_id, ms_type=self.ms_type, addr=addr,
                      store_path=path, auth_key=self.auth_key,
                      cephx_keyring=self.keyring if self.cephx else None)
        mon.init(probe=seeds)
        if self._is_wire():
            # the wiped mon's new ephemeral port must replace the old
            # monmap entry before the probe can match it
            client = self.client(timeout=20.0)
            client.mon_command({"prefix": "mon add", "id": mon_id,
                                "addr": mon.addr})
        self.mons[mon_id] = mon
        if mon_id < len(self.monmap):
            self.monmap[mon_id] = mon.addr
        deadline = _time.time() + timeout
        while _time.time() < deadline:
            if mon.elector is not None and not mon.elector.electing:
                return mon
            _time.sleep(0.1)
        # clean up the half-joined mon: leaving it registered (and its
        # threads running) would let a later run_mon bind a SECOND
        # monitor over the same address/store
        self.mons.pop(mon_id, None)
        mon.shutdown()
        raise TimeoutError(f"replaced mon.{mon_id} did not rejoin")

    def run_mgr(self, mgr_id: int = 0):
        """Start a manager; OSDs started AFTERWARDS stream reports to
        the one the mon names active (restart existing ones to pick it
        up).  Additional mgr_ids are standbys the mon promotes when the
        active's session dies."""
        from ceph_tpu.mgr import MgrDaemon
        addr = ("127.0.0.1:0" if self._is_wire()
                else f"{self._ns}mgr.{mgr_id}")
        cephx = None
        if self.cephx:
            who = f"mgr.{mgr_id}"
            key = self.keyring.get(who) or self.provision_key(who)
            cephx = (who, key)
        mgr = MgrDaemon(self.mon_host, ms_type=self.ms_type,
                        addr=addr, auth_key=self.auth_key,
                        cephx=cephx, mgr_id=mgr_id)
        mgr.init()
        self.mgrs = getattr(self, "mgrs", {})
        self.mgrs[mgr_id] = mgr
        if mgr_id == 0 or self.mgr is None:
            self.mgr = mgr
        return mgr

    def kill_mgr(self, mgr_id: int = 0):
        mgr = self.mgrs.pop(mgr_id, None) if getattr(self, "mgrs", None) \
            else None
        if mgr is None:
            mgr, self.mgr = self.mgr, None
        if mgr is not None:
            if self.mgr is mgr:
                self.mgr = next(iter(getattr(self, "mgrs", {}).values()),
                                None)
            mgr.shutdown()

    def run_mds(self, metadata_pool: int, data_pool: int):
        """Start the metadata server over the given pools (the `fs new
        meta data` + ceph-mds step)."""
        from ceph_tpu.mds import MDSDaemon
        addr = ("127.0.0.1:0" if self._is_wire()
                else f"{self._ns}mds.0")
        cephx = None
        if self.cephx:
            key = self.keyring.get("mds.0") or self.provision_key("mds.0")
            cephx = ("mds.0", key)
        self.mds = MDSDaemon(self.mon_host, metadata_pool, data_pool,
                             ms_type=self.ms_type, addr=addr,
                             auth_key=self.auth_key, cephx=cephx)
        self.mds.init()
        return self.mds

    def run_fs_mds(self, n: int = 1):
        """FSMap mode: start n beaconing MDS daemons; the mon assigns
        ranks (up to max_mds), the rest idle as standbys.  Run `fs new`
        first."""
        from ceph_tpu.mds import MDSDaemon
        out = []
        for i in range(n):
            idx = self._fs_mds_seq
            self._fs_mds_seq += 1
            addr = ("127.0.0.1:0" if self._is_wire()
                    else f"{self._ns}mds.g{idx}")
            cephx = None
            if self.cephx:
                ent = f"mds.{idx}"
                key = self.keyring.get(ent) or self.provision_key(ent)
                cephx = (ent, key)
            d = MDSDaemon(self.mon_host, ms_type=self.ms_type,
                          addr=addr, auth_key=self.auth_key,
                          cephx=cephx)
            d.init_standby()
            self.fs_mds.append(d)
            out.append(d)
        return out

    def crash_fs_mds(self, d) -> None:
        """SIGKILL-style: no flush, no journal trim, no goodbye."""
        d._stop = True
        for t in (d._tick_timer, d._beacon_timer):
            if t:
                t.cancel()
        d.msgr.shutdown()
        d.objecter.shutdown()
        if d in self.fs_mds:
            self.fs_mds.remove(d)

    def kill_mds(self) -> None:
        mds = self.mds
        self.mds = None
        mds.shutdown()

    def provision_key(self, entity: str) -> str:
        """`ceph auth get-or-create` as admin; returns the secret."""
        admin = self.client()
        rc, out = admin.mon_command({"prefix": "auth get-or-create",
                                     "entity": entity})
        assert rc == 0, out
        rc, key = admin.mon_command({"prefix": "auth print-key",
                                     "entity": entity})
        assert rc == 0, key
        self.keyring[entity] = key
        return key

    def run_osd(self, osd_id: int) -> OSDDaemon:
        addr = (f"127.0.0.1:0" if self._is_wire()
                else f"{self._ns}osd.{osd_id}")
        path = (f"{self.base_path}/osd.{osd_id}" if self.base_path else "")
        cephx = None
        if self.cephx:
            ent = f"osd.{osd_id}"
            key = self.keyring.get(ent) or self.provision_key(ent)
            cephx = (ent, key)
        osd = OSDDaemon(osd_id, self.mon_host, store_type=self.store_type,
                        store_path=path, ms_type=self.ms_type, addr=addr,
                        heartbeats=self.heartbeats,
                        auth_key=self.auth_key, cephx=cephx,
                        mgr_addr=self.mgr.addr if self.mgr else None,
                        conf=self.osd_conf)
        osd.init()
        self.osds[osd_id] = osd
        return osd

    def kill_osd(self, osd_id: int) -> None:
        """Hard kill (Thrasher kill_osd analog)."""
        osd = self.osds.pop(osd_id)
        osd.shutdown()

    def client(self, timeout: float = 10.0) -> RadosClient:
        cephx = (("client.admin", self.keyring["client.admin"])
                 if self.cephx else None)
        c = RadosClient(self.mon_host, ms_type=self.ms_type,
                        timeout=timeout, auth_key=self.auth_key,
                        cephx=cephx)
        c.connect()
        self.clients.append(c)
        return c

    def client_as(self, entity: str, key: str,
                  timeout: float = 10.0) -> RadosClient:
        """A client with SPECIFIC cephx credentials (not admin)."""
        c = RadosClient(self.mon_host, ms_type=self.ms_type,
                        timeout=timeout, cephx=(entity, key))
        c.connect()
        self.clients.append(c)
        return c

    def stop(self) -> None:
        for c in self.clients:
            c.shutdown()
        if self.mds:
            self.mds.shutdown()
            self.mds = None
        for d in list(self.fs_mds):
            d.shutdown()
        self.fs_mds = []
        for osd in list(self.osds.values()):
            osd.shutdown()
        self.osds.clear()
        if self.mgr:
            self.mgr.shutdown()
        for mon in list(self.mons.values()):
            mon.shutdown()
        self.mons.clear()

    # -- helpers (ceph-helpers.sh analog) -------------------------------------

    def wait_for_epoch(self, epoch: int, timeout: float = 10.0) -> None:
        """All live daemons have seen at least `epoch`."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if all(o.osdmap.epoch >= epoch for o in self.osds.values()):
                return
            time.sleep(0.02)
        raise TimeoutError(f"cluster did not reach epoch {epoch}")

    def wait_for_osd_count(self, n: int, timeout: float = 10.0) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.mon.status()["num_up_osds"] == n:
                return
            time.sleep(0.02)
        raise TimeoutError(f"never saw {n} up osds")

    def create_pool(self, client: RadosClient, *,
                    epoch_timeout: float = 10.0,
                    ec_overwrites: bool = False, **cmd) -> int:
        """``epoch_timeout``: a new pool's first map application can
        pay a cold jit trace+compile inside _handle_map (the fused
        placement ladder, when osdmap_mapping_min_pgs admits toy
        pools) — tens of seconds on a 1-core host; callers running
        fused-on-toy-pools setups pass a compile-sized timeout.
        ``ec_overwrites``: then `osd pool set <pool>
        allow_ec_overwrites true` (BlueStore OSDs only)."""
        res, out = client.mon_command(
            dict({"prefix": "osd pool create"}, **cmd))
        assert res == 0, out
        pool_id = int(out.split()[1])
        if ec_overwrites:
            res, out = client.mon_command({
                "prefix": "osd pool set", "pool": str(pool_id),
                "var": "allow_ec_overwrites", "val": "true"})
            assert res == 0, out
        epoch = self.mon.osdmap.epoch
        self.wait_for_epoch(epoch, timeout=epoch_timeout)
        client.wait_for_epoch(epoch)
        return pool_id


class ProcCluster:
    """Multi-PROCESS cluster harness: every mon/OSD is a separate OS
    process over the TCP stack (the reference's tier-3 QA model —
    vstart.sh spawns real daemons; qa/standalone/ceph-helpers.sh
    run_mon:437 / run_osd:596).  kill_osd(9) is real SIGKILL process
    death; the filestore survives for the restart.

    One process per chip: an accelerator belongs to the first process
    that touches JAX, and a second one that needs it fails or hangs.
    Every child is therefore started pinned to the CPU platform
    (``--jax-cpu-devices``), except the single daemon the caller names
    in ``chip_owner`` ("osd.0"), which keeps the default backend.  The
    parent must then stay off JAX itself.
    """

    def __init__(self, n_osds: int = 3, n_mons: int = 1,
                 base_path: str = "", auth_key: str = "",
                 ms_type: str = "async", jax_cpu_devices: int = 0,
                 chip_owner: str | None = None):
        import tempfile
        self.n_osds = n_osds
        self.n_mons = n_mons
        self.base_path = base_path or tempfile.mkdtemp(prefix="proccluster-")
        self.auth_key = auth_key
        #: OSD messenger stack: "ici" = cross-process ici-wire (TCP
        #: control plane + device transfer data plane), which needs at
        #: least two local devices per process
        self.ms_type = ms_type
        #: virtual CPU devices each pinned child sees
        self.jax_cpu_devices = jax_cpu_devices or (
            2 if ms_type == "ici" else 1)
        self.chip_owner = chip_owner
        self.procs: dict[str, object] = {}   # "mon.0" / "osd.2" -> Popen
        self.mon_addrs: list[str] = []
        self.clients: list[RadosClient] = []

    @property
    def mon_host(self) -> str:
        return ",".join(self.mon_addrs)

    def _spawn(self, role: str, rid: int, extra: list[str]):
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "ceph_tpu.tools.daemon_main",
               "--role", role, "--id", str(rid),
               "--store-path", f"{self.base_path}/{role}.{rid}"]
        if self.auth_key:
            cmd += ["--auth-key", self.auth_key]
        if f"{role}.{rid}" != self.chip_owner:
            cmd += ["--jax-cpu-devices", str(self.jax_cpu_devices)]
        cmd += extra
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True)
        # wait for the readiness line (bounded: a wedged daemon must
        # fail the harness, not hang it — including one that emits a
        # partial line), then keep the pipe drained so later daemon
        # output cannot fill the buffer and block it
        import os as _os
        import selectors
        fd = proc.stdout.fileno()
        _os.set_blocking(fd, False)
        sel = selectors.DefaultSelector()
        sel.register(proc.stdout, selectors.EVENT_READ)
        buf = b""
        deadline = time.time() + 60.0
        while b"\n" not in buf and time.time() < deadline:
            if sel.select(timeout=max(0.05, deadline - time.time())):
                chunk = _os.read(fd, 4096)
                if not chunk:
                    break
                buf += chunk
        sel.close()
        _os.set_blocking(fd, True)
        line = buf.split(b"\n", 1)[0].decode(errors="replace")
        if not line.startswith("ready"):
            proc.kill()
            raise RuntimeError(f"{role}.{rid} failed to start: {line!r}")
        threading.Thread(target=proc.stdout.read, daemon=True).start()
        #: the full ready line (rgw appends its bound HTTP address)
        proc.ready_line = line
        self.procs[f"{role}.{rid}"] = proc
        return proc

    def start(self) -> "ProcCluster":
        from ceph_tpu.common import free_port
        self.mon_addrs = [f"127.0.0.1:{free_port()}"
                          for _ in range(self.n_mons)]
        monmap = ",".join(self.mon_addrs)
        for i in range(self.n_mons):
            self._spawn("mon", i, ["--addr", self.mon_addrs[i],
                                   "--monmap", monmap])
        for i in range(self.n_osds):
            self.run_osd(i)
        return self

    def run_osd(self, osd_id: int):
        extra = ["--mon-host", self.mon_host, "--heartbeats"]
        if self.ms_type != "async":
            extra += ["--ms-type", self.ms_type]
        return self._spawn("osd", osd_id, extra)

    def kill_osd(self, osd_id: int) -> None:
        """SIGKILL — crash-grade process death (Thrasher kill_osd)."""
        proc = self.procs.pop(f"osd.{osd_id}")
        proc.kill()
        proc.wait(timeout=10)

    def run_rgw(self, pool: int, rgw_id: int = 0) -> str:
        """Spawn a radosgw process over `pool`; returns its HTTP
        address, read from the ready line — the daemon binds an
        ephemeral port itself, so there is no pick-then-bind race."""
        proc = self._spawn("rgw", rgw_id,
                           ["--mon-host", self.mon_host,
                            "--rgw-pool", str(pool)])
        parts = proc.ready_line.split()
        if len(parts) < 3:
            raise RuntimeError(
                f"rgw ready line carried no address: "
                f"{proc.ready_line!r}")
        return parts[2]

    def client(self, timeout: float = 20.0) -> RadosClient:
        c = RadosClient(self.mon_host, ms_type="async", timeout=timeout,
                        auth_key=self.auth_key.encode()
                        if self.auth_key else None)
        c.connect()
        self.clients.append(c)
        return c

    def wait_for_osd_count(self, n: int, timeout: float = 30.0) -> None:
        import json
        deadline = time.time() + timeout
        client = self.clients[0] if self.clients else self.client()
        while time.time() < deadline:
            try:
                rc, out = client.mon_command({"prefix": "status"})
                if rc == 0 and json.loads(out)["num_up_osds"] == n:
                    return
            except (TimeoutError, OSError, ValueError, KeyError):
                pass
            time.sleep(0.25)
        raise TimeoutError(f"never saw {n} up osds")

    def create_pool(self, client: RadosClient, **cmd) -> int:
        import json
        res, out = client.mon_command(
            dict({"prefix": "osd pool create"}, **cmd))
        assert res == 0, out
        pool_id = int(out.split()[1])
        rc, st = client.mon_command({"prefix": "status"})
        assert rc == 0, st
        client.wait_for_epoch(json.loads(st)["epoch"])
        return pool_id

    def stop(self) -> None:
        for c in self.clients:
            try:
                c.shutdown()
            except Exception:
                pass
        self.clients.clear()
        for name, proc in list(self.procs.items()):
            proc.terminate()
        for name, proc in list(self.procs.items()):
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
        self.procs.clear()
