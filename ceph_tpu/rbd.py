"""librbd-lite — block images striped over RADOS objects
(src/librbd/ analog: ImageRequest -> ObjectRequest over a striped
layout; header object + rbd_data.<id>.<objno> data objects).

An image is a fixed-size virtual block device: create/open/read/write
at arbitrary byte offsets, resize, stat, remove.  On top of the basic
I/O path:

  * rbd_directory — pool-level image registry (librbd's rbd_directory
    omap object), so `list_images` needs no name probes
  * exclusive lock — the managed lock over the cls lock object class
    on the header (librbd ManagedLock/ExclusiveLock): acquire/release/
    break, and writes refuse while another owner holds it
  * snapshots — snap_create/list/remove/rollback + read(snap=...),
    riding pool snapshots namespaced per image (`rbd.<image>.<snap>`),
    with the image size frozen in the header's snap table
  * clone — COW layering (CloneRequest/CopyupRequest): a child links
    to a PROTECTED parent@snap and shares its objects; reads fall
    through to the parent, the first write to an object copies it up,
    and `flatten` severs the link.  Child snapshots freeze their own
    parent record, so flatten/resize of the head never rewrites what a
    snap could see
  * a separate data pool (`rbd create --data-pool`, librbd's data-pool
    feature): header, directory, object map and journal stay in the
    image's pool, the rbd_data objects go to the data pool — an
    erasure-coded one only with allow_ec_overwrites
"""

from __future__ import annotations

import binascii
import json
import threading

from ceph_tpu.common.lockdep import make_lock
from ceph_tpu.osdc.journaler import Journaler
from ceph_tpu.osdc.striper import StripeLayout, StripedObject

RBD_DIRECTORY = "rbd_directory"
#: pool-level parent@snap -> [child image names] registry (the
#: reference's rbd_children object)
RBD_CHILDREN = "rbd_children"

#: image feature bits (librbd feature flags; journaling gates the
#: write-ahead event journal that rbd-mirror replays; object-map keeps
#: the per-object allocation bitmap, fast-diff derives diffs from it)
FEATURE_JOURNALING = "journaling"
FEATURE_OBJECT_MAP = "object-map"
FEATURE_FAST_DIFF = "fast-diff"


class Image:
    HEADER_FMT = "rbd_header.{name}"
    DATA_FMT = "rbd_data.{name}"

    def __init__(self, ioctx, name: str):
        self.io = ioctx
        self.name = name
        self._meta = None
        self._data_ioctx = None
        #: the data objects' high-water mark this handle last saw, and
        #: the image size it saw it at: aio_write reads it again past
        #: it, or once the image was resized
        self._hwm = (0, 0)

    # -- lifecycle ------------------------------------------------------------

    @classmethod
    def create(cls, ioctx, name: str, size: int,
               order: int = 22, stripe_unit: int = 1 << 16,
               stripe_count: int = 4, primary: bool = True,
               features: list[str] | None = None,
               data_pool=None) -> "Image":
        """order = log2(object size), like rbd create --order.
        primary=False creates a demoted replication target atomically
        (no primary window for a mirror-daemon crash to leave open).
        `data_pool` (an IoCtx; rbd create --data-pool) holds the data
        objects; an erasure-coded pool must allow overwrites."""
        if data_pool is not None:
            pool = data_pool.client.osdmap.pools.get(data_pool.pool_id)
            if pool is None:
                raise OSError(2, f"data pool {data_pool.pool_id} "
                                 "does not exist")
            if pool.is_erasure() and not pool.allows_ecoverwrites():
                raise OSError(95, f"data pool {data_pool.pool_id} is "
                                  "erasure-coded without "
                                  "allow_ec_overwrites")
        header = cls.HEADER_FMT.format(name=name)
        exists = True
        try:
            ioctx.stat(header)
        except OSError:
            exists = False
        if exists:
            raise FileExistsError(f"image {name!r} exists")
        meta = {"size": size, "order": order,
                "stripe_unit": stripe_unit,
                "stripe_count": stripe_count, "snaps": {},
                "features": list(features or []), "primary": primary}
        if data_pool is not None:
            meta["data_pool"] = data_pool.pool_id
        ioctx.write_full(header, json.dumps(meta).encode())
        ioctx.set_omap(RBD_DIRECTORY, {name: b"1"})
        img = cls(ioctx, name)
        img._meta = meta
        if FEATURE_OBJECT_MAP in (features or []):
            # feature present from birth (clone inheritance, mirror
            # targets): the map object must exist even before the first
            # write, or du/diff on the fresh image error out
            from ceph_tpu.rbd_object_map import ObjectMap
            om = ObjectMap(ioctx, name)
            om.resize(img._striped().layout.num_objects(size))
            om.save()
        return img

    def _load(self) -> dict:
        if self._meta is None:
            blob = self.io.read(self.HEADER_FMT.format(name=self.name))
            self._meta = json.loads(blob.decode())
        return self._meta

    def _data_io(self):
        """The IoCtx of the data objects: the data pool's, or the
        image's own pool."""
        pool = self._load().get("data_pool")
        if pool is None:
            return self.io
        if self._data_ioctx is None:
            self._data_ioctx = self.io.client.open_ioctx(pool)
        return self._data_ioctx

    def _striped(self) -> StripedObject:
        m = self._load()
        layout = StripeLayout(stripe_unit=m["stripe_unit"],
                              stripe_count=m["stripe_count"],
                              object_size=1 << m["order"])
        return StripedObject(self._data_io(),
                             self.DATA_FMT.format(name=self.name),
                             layout, meta_io=self.io)

    # -- features / journaling (librbd/Journal.h:43 analog) -------------------

    JOURNAL_FMT = "journal_rbd.{name}"

    def features(self) -> list[str]:
        return list(self._load().get("features", []))

    def feature_enable(self, feature: str) -> None:
        m = self._load()
        feats = m.setdefault("features", [])
        if feature in feats:
            return
        if feature == FEATURE_FAST_DIFF \
                and FEATURE_OBJECT_MAP not in feats:
            raise ValueError("fast-diff requires object-map")
        feats.append(feature)
        if feature == FEATURE_JOURNALING:
            j = self._journal()
            try:
                j.open()
            except OSError:
                j.create()
        self._save_meta(m)
        if feature == FEATURE_OBJECT_MAP:
            # build the map from reality on enable (ObjectMap<I>::open
            # falls back to a rebuild when the map object is absent)
            self.rebuild_object_map()

    def feature_disable(self, feature: str) -> None:
        m = self._load()
        if feature in m.get("features", []):
            if feature == FEATURE_OBJECT_MAP \
                    and FEATURE_FAST_DIFF in m["features"]:
                raise ValueError("disable fast-diff first")
            m["features"].remove(feature)
            self._save_meta(m)
            if feature == FEATURE_OBJECT_MAP:
                from ceph_tpu.rbd_object_map import ObjectMap
                ObjectMap(self.io, self.name).remove()
                for ent in m.get("snaps", {}).values():
                    ObjectMap(self.io, self.name,
                              ent["snapid"]).remove()
                self._om_invalidate()

    def _journal(self) -> Journaler:
        return Journaler(self.io, self.JOURNAL_FMT.format(name=self.name))

    def _journal_event(self, event: dict) -> None:
        """Write-ahead: mutations on a journaled image append the event
        and flush BEFORE touching image data (librbd Journal ordering);
        rbd-mirror replays these on the peer cluster.  Events carry
        absolute offsets/states so replay is idempotent."""
        if FEATURE_JOURNALING not in self._load().get("features", []):
            return
        j = self._journal()
        try:
            j.open()
        except OSError:
            j.create()   # feature set at create-time (mirror targets)
        j.append_entry(json.dumps(event).encode())
        j.flush()

    # -- primary / demote (rbd mirror promote/demote) -------------------------

    def is_primary(self) -> bool:
        return bool(self._load().get("primary", True))

    def promote(self) -> None:
        m = self._load()
        m["primary"] = True
        self._save_meta(m)

    def demote(self) -> None:
        """Non-primary images are read-only replication targets; only
        the mirror daemon's replay applies to them (mirror_apply)."""
        m = self._load()
        m["primary"] = False
        self._save_meta(m)

    def _check_primary(self) -> None:
        # re-read the header: another handle (the mirror daemon, an
        # operator CLI) may have demoted us — librbd learns this through
        # its header watch; here a read per gated mutation is the analog
        self._meta = None
        if not self._load().get("primary", True):
            raise OSError(30, f"image {self.name!r} is non-primary "
                              "(demoted mirror target)")  # EROFS

    # -- I/O ------------------------------------------------------------------

    def stat(self) -> dict:
        m = self._load()
        return {"size": m["size"], "order": m["order"],
                "stripe_unit": m["stripe_unit"],
                "stripe_count": m["stripe_count"],
                "features": list(m.get("features", [])),
                "primary": m.get("primary", True)}

    def _om_invalidate(self) -> None:
        self._om_cache = None

    def _om_enabled(self) -> bool:
        return FEATURE_OBJECT_MAP in self._load().get("features", [])

    def _om_load(self, snapid: int = 0):
        from ceph_tpu.rbd_object_map import ObjectMap
        try:
            return ObjectMap.load(self.io, self.name, snapid)
        except (OSError, ValueError):
            return None

    def _om_mark_write(self, offset: int, length: int) -> None:
        """Write-ahead map update: touched objects go EXISTS before any
        data byte lands (ObjectMap::aio_update pre-write) — a crash
        between map and data can only over-report.  A missing/corrupt
        map is REBUILT from the backing objects first: silently starting
        a fresh empty map here would under-report every earlier write
        and turn a later clone/export-diff into data loss."""
        if not self._om_enabled() or length <= 0:
            return
        from ceph_tpu.rbd_object_map import OBJECT_EXISTS
        om = getattr(self, "_om_cache", None)
        if om is None:
            om = self._om_load()
            if om is None:
                self.rebuild_object_map()
                om = self._om_load()
                if om is None:
                    return   # map stays absent; du/diff will error loudly
        st = self._striped()
        dirty = False
        for objno, _off, _n in st.layout.extents(offset, length):
            if om.get(objno) != OBJECT_EXISTS:
                om.set(objno, OBJECT_EXISTS)
                dirty = True
        if dirty:
            om.save()
        # the exclusive-lock holder owns the map (librbd keeps it in
        # memory under the lock); lockless handles reload per write
        if getattr(self, "_owner", None) is not None:
            self._om_cache = om
        else:
            self._om_cache = None

    def write(self, data: bytes, offset: int = 0) -> int:
        self._write_gate(data, offset)
        self._striped().write(data, offset)
        return len(data)

    def _write_gate(self, data: bytes, offset: int) -> None:
        """What a write does before its data: primary and lock checks,
        journal, object map, copy-up."""
        self._check_primary()   # refreshes the header cache too
        m = self._load()
        if offset + len(data) > m["size"]:
            raise ValueError("write past end of image")
        self._check_lock()
        self._journal_event({"op": "write", "off": offset,
                             "data": binascii.hexlify(data).decode()})
        self._om_mark_write(offset, len(data))
        self._copyup(offset, len(data))

    def aio_write(self, data: bytes, offset: int = 0):
        """librbd rbd_aio_write: the gating of write(), then a ranged
        write of each object extent, all in flight at once.  Returns
        the object's AioCompletion, or one over all of them when the
        write crosses objects."""
        self._write_gate(data, offset)
        st = self._striped()
        end = offset + len(data)
        hwm, at = self._hwm
        if end > hwm or at != self._load()["size"]:
            hwm = st.size()
            if end > hwm:
                st._set_size(end)
                hwm = end
            self._hwm = (hwm, self._load()["size"])
        parts = []
        pos = 0
        for objno, obj_off, n in st.layout.extents(offset, len(data)):
            parts.append(st.io.aio_write(
                st.striper.object_name(st.name, objno),
                data[pos:pos + n], obj_off))
            pos += n
        return parts[0] if len(parts) == 1 else ImageCompletion(parts)

    def mirror_apply(self, event: dict) -> None:
        """Apply one replayed journal event (rbd-mirror's Replayer):
        bypasses the primary gate — replication IS how a demoted image
        changes — but still respects sizes and is idempotent."""
        op = event["op"]
        if op == "write":
            data = binascii.unhexlify(event["data"])
            m = self._load()
            end = event["off"] + len(data)
            if end > m["size"]:
                m["size"] = end
                self._save_meta(m)
            self._om_mark_write(event["off"], len(data))
            self._striped().write(data, event["off"])
        elif op == "resize":
            m = self._load()
            if event["size"] < m["size"]:
                self._striped().truncate(event["size"])
            m["size"] = event["size"]
            self._save_meta(m)
        elif op == "snap_create":
            if event["snap"] not in self.snap_list():
                self._snap_create_internal(event["snap"])
        elif op == "snap_remove":
            if event["snap"] in self.snap_list():
                self._snap_remove_internal(event["snap"])
        elif op == "snap_rollback":
            # the target rolls back against ITS copy of the snapshot
            # (created by the replayed snap_create at the same journal
            # position, so contents match the primary's at rollback time)
            self._snap_rollback_internal(event["snap"])
        else:
            raise ValueError(f"unknown journal event {op!r}")

    def read(self, offset: int = 0, length: int = 0,
             snap: str | None = None) -> bytes:
        m = self._load()
        snapid = 0
        size = m["size"]
        if snap is not None:
            ent = m.get("snaps", {}).get(snap)
            if ent is None:
                raise KeyError(f"no snapshot {snap!r}")
            snapid, size = ent["snapid"], ent["size"]
        if length <= 0 or offset + length > size:
            length = max(0, size - offset)
        # clone layering: a SNAP read uses the parent record frozen in
        # that snap entry (flatten/shrink only rewrite the head's);
        # a head read uses the live head record
        if snap is not None:
            prec = m.get("snaps", {}).get(snap, {}).get("parent")
        else:
            prec = m.get("parent")
        if prec:
            return self._clone_read(offset, length, snapid, prec)
        data = self._striped().read(offset, length, snapid=snapid)
        if len(data) < length:      # unwritten space reads as zeros
            data = data + bytes(length - len(data))
        return data

    # -- exclusive lock (librbd ManagedLock over cls lock) --------------------

    def _header(self) -> str:
        return self.HEADER_FMT.format(name=self.name)

    def lock_acquire(self, owner: str) -> None:
        self.io.execute(self._header(), "lock", "lock",
                        json.dumps({"owner": owner}).encode())
        self._owner = owner

    def lock_release(self, owner: str | None = None) -> None:
        self.io.execute(self._header(), "lock", "unlock",
                        json.dumps({"owner": owner
                                    or getattr(self, "_owner",
                                               None)}).encode())
        self._owner = None
        self._om_invalidate()

    def lock_info(self) -> dict:
        return json.loads(self.io.execute(self._header(), "lock", "info"))

    def break_lock(self) -> None:
        """Steal a dead client's lock (rbd lock break)."""
        holder = self.lock_info().get("holder")
        if holder:
            self.io.execute(self._header(), "lock", "unlock",
                            json.dumps({"owner": holder}).encode())

    def _check_lock(self) -> None:
        """Writes respect an exclusive lock held by another owner.  A
        handle that holds the lock itself skips the round trip (its
        ownership stands until it releases; a concurrent break_lock is
        the operator declaring this writer dead, as in the reference,
        where the broken client is blocklisted).  Any other handle pays
        one lock_info per write — correctness over latency here."""
        if getattr(self, "_owner", None) is not None:
            return
        try:
            holder = self.lock_info().get("holder")
        except OSError:
            holder = None
        if holder is not None:
            raise OSError(16, f"image locked by {holder!r}")  # EBUSY

    # -- snapshots (pool snaps namespaced per image) --------------------------

    def _save_meta(self, m: dict) -> None:
        self.io.write_full(self._header(), json.dumps(m).encode())
        self._meta = m

    def snap_create(self, snap: str) -> int:
        if self._load().get("data_pool") is not None:
            # the pool snapshots below would freeze the header's pool,
            # not the data pool's objects
            raise OSError(95, "snapshots of an image with a data pool "
                              "are not supported")
        self._check_primary()
        snapid = self._snap_create_internal(snap)
        # journal AFTER the mon op succeeds: a failed snap must never
        # replay onto the mirror (the reverse window — snap taken, crash
        # before journaling — loses only the mirror's copy of the snap,
        # the recoverable direction)
        self._journal_event({"op": "snap_create", "snap": snap})
        return snapid

    def _snap_create_internal(self, snap: str) -> int:
        """Snapshot without the primary gate or journaling: the public
        path wraps this; mirror replay (mirror_apply) calls it directly
        so replicated snaps neither re-journal on the target nor bounce
        off its demoted state."""
        m = self._load()
        if snap in m.get("snaps", {}):
            raise FileExistsError(f"snapshot {snap!r} exists")
        rc, out = self.io.client.mon_command({
            "prefix": "osd pool mksnap", "pool": self.io.pool_id,
            "snap": f"rbd.{self.name}.{snap}"})
        if rc != 0:
            raise OSError(-rc or 5, out)
        reply = json.loads(out)
        snapid = reply["snapid"]
        # map-propagation barrier: a write issued right after this must
        # carry the post-snap epoch, or a stale primary could skip the
        # pre-write COW clone and silently corrupt the snapshot
        if "epoch" in reply:
            self.io.client.wait_for_epoch(reply["epoch"])
        ent = {"snapid": snapid, "size": m["size"]}
        if m.get("parent"):
            # freeze the parent linkage AS OF this snapshot: a later
            # flatten or shrink (which rewrites the head's parent
            # record) must never change what this snap reads
            ent["parent"] = dict(m["parent"])
        m.setdefault("snaps", {})[snap] = ent
        self._save_meta(m)
        if self._om_enabled():
            om = self._om_load()
            if om is not None:
                # freeze the map under the snap; head EXISTS demote to
                # EXISTS_CLEAN so fast-diff can tell dirty from clean
                om.snapshot_copy(snapid)
                self._om_invalidate()
        return snapid

    def snap_list(self) -> dict:
        return dict(self._load().get("snaps", {}))

    def snap_remove(self, snap: str) -> None:
        self._check_primary()
        self._snap_remove_internal(snap)
        self._journal_event({"op": "snap_remove", "snap": snap})

    def _snap_remove_internal(self, snap: str) -> None:
        m = self._load()
        if snap not in m.get("snaps", {}):
            raise KeyError(f"no snapshot {snap!r}")
        if m["snaps"][snap].get("protected"):
            raise OSError(16, f"snapshot {snap!r} is protected "
                          "(unprotect first)")   # EBUSY
        rc, out = self.io.client.mon_command({
            "prefix": "osd pool rmsnap", "pool": self.io.pool_id,
            "snap": f"rbd.{self.name}.{snap}"})
        if rc != 0:
            raise OSError(-rc or 5, out)
        snapid = m["snaps"][snap]["snapid"]
        removed_prec = m["snaps"][snap].get("parent")
        del m["snaps"][snap]
        self._save_meta(m)
        if removed_prec and not m.get("parent") \
                and not any(e.get("parent")
                            for e in m.get("snaps", {}).values()):
            # the last parent-referencing snap of a flattened clone is
            # gone: nothing of this image reads the parent any more —
            # release the children registration that blocked unprotect
            Image(self.io, removed_prec["image"])._unregister_child(
                removed_prec["snap"], self.name)
        from ceph_tpu.rbd_object_map import (
            OBJECT_EXISTS, OBJECT_EXISTS_CLEAN, OBJECT_PENDING,
            ObjectMap)
        if self._om_enabled():
            # the removed map's dirty bits record "changed since the
            # PREVIOUS snap"; fold them into the next-younger map (or
            # the head) so a later diff spanning this hole still sees
            # the rewrite (the reference re-flags clean objects the
            # same way when a snap in the middle goes away)
            gone = self._om_load(snapid)
            if gone is not None:
                younger = [e["snapid"] for e in m["snaps"].values()
                           if e["snapid"] > snapid]
                heir = self._om_load(min(younger)) if younger \
                    else self._om_load()
                if heir is not None:
                    dirty = False
                    for objno in range(gone.n_objs):
                        if gone.get(objno) in (OBJECT_EXISTS,
                                               OBJECT_PENDING) \
                                and heir.get(objno) \
                                == OBJECT_EXISTS_CLEAN:
                            heir.set(objno, OBJECT_EXISTS)
                            dirty = True
                    if dirty:
                        heir.save()
                    self._om_invalidate()
            ObjectMap(self.io, self.name, snapid).remove()

    def snap_rollback(self, snap: str) -> None:
        """Restore image content to the snapshot (rbd snap rollback —
        object-by-object copy-back, librbd's simple_rollback).  On a
        journaled image the rollback is journaled like any other mutation
        (write-ahead, before the data moves): the mirror replays it
        against its own replicated snapshot, so the pair stays converged
        instead of silently diverging on an unjournaled full rewrite."""
        self._check_primary()
        if snap not in self._load().get("snaps", {}):
            raise KeyError(f"no snapshot {snap!r}")
        self._check_lock()
        self._journal_event({"op": "snap_rollback", "snap": snap})
        self._snap_rollback_internal(snap)

    def _snap_rollback_internal(self, snap: str) -> None:
        m = self._load()
        ent = m.get("snaps", {}).get(snap)
        if ent is None:
            raise KeyError(f"no snapshot {snap!r}")
        data = self.read(0, ent["size"], snap=snap)
        st = self._striped()
        self._om_mark_write(0, max(ent["size"], m["size"]))
        st.truncate(0)
        st.write(data, 0)
        m["size"] = ent["size"]
        self._save_meta(m)

    # -- snapshot protection + COW clone layering -----------------------------
    # (src/librbd/image/CloneRequest.cc:80-220 parent linkage,
    #  src/librbd/io/CopyupRequest.cc:120-260 first-write copy-up,
    #  src/librbd/Operations.cc snap_protect/unprotect/flatten)

    def snap_protect(self, snap: str) -> None:
        """Mark a snapshot clone-able: children may link to it, and it
        cannot be removed until unprotected (which in turn requires no
        children)."""
        self._check_primary()
        m = self._load()
        ent = m.get("snaps", {}).get(snap)
        if ent is None:
            raise KeyError(f"no snapshot {snap!r}")
        ent["protected"] = True
        self._save_meta(m)

    def snap_unprotect(self, snap: str) -> None:
        self._check_primary()
        m = self._load()
        ent = m.get("snaps", {}).get(snap)
        if ent is None:
            raise KeyError(f"no snapshot {snap!r}")
        if self.list_children(snap):
            raise OSError(16, f"snapshot {snap!r} has children")  # EBUSY
        ent["protected"] = False
        self._save_meta(m)

    def snap_is_protected(self, snap: str) -> bool:
        ent = self._load().get("snaps", {}).get(snap)
        if ent is None:
            raise KeyError(f"no snapshot {snap!r}")
        return bool(ent.get("protected"))

    @staticmethod
    def _children_key(parent: str, snap: str) -> str:
        return f"{parent}@{snap}"

    def list_children(self, snap: str) -> list[str]:
        """Child images cloned from parent@snap (rbd children)."""
        try:
            omap = self.io.get_omap(RBD_CHILDREN)
        except OSError:
            return []
        blob = omap.get(self._children_key(self.name, snap))
        return json.loads(blob.decode()) if blob else []

    def _register_child(self, snap: str, child: str) -> None:
        kids = self.list_children(snap)
        if child not in kids:
            kids.append(child)
            self.io.set_omap(RBD_CHILDREN, {
                self._children_key(self.name, snap):
                json.dumps(kids).encode()})

    def _unregister_child(self, snap: str, child: str) -> None:
        kids = self.list_children(snap)
        if child in kids:
            kids.remove(child)
            key = self._children_key(self.name, snap)
            if kids:
                self.io.set_omap(RBD_CHILDREN, {
                    key: json.dumps(kids).encode()})
            else:
                try:
                    self.io.rm_omap_keys(RBD_CHILDREN, [key])
                except OSError:
                    pass

    def _parent(self) -> tuple["Image", str, int] | None:
        """(parent image, parent snap, overlap bytes) for a clone."""
        p = self._load().get("parent")
        if not p:
            return None
        return Image(self.io, p["image"]), p["snap"], int(p["overlap"])

    def _obj_name(self, objno: int) -> str:
        st = self._striped()
        return st.striper.object_name(
            self.DATA_FMT.format(name=self.name), objno)

    def _obj_exists(self, objno: int) -> bool:
        try:
            self._data_io().stat(self._obj_name(objno))
            return True
        except OSError:
            return False

    def _copyup(self, offset: int, length: int) -> None:
        """First write to a clone-backed object pulls the parent's
        bytes for that WHOLE object into the child first (CopyupRequest
        ordering: copy-up, then the client write overwrites its part) —
        after which reads of the object's other ranges come from the
        child, never a torn child/parent mix."""
        parent = self._parent()
        if parent is None or length <= 0:
            return
        parent_img, psnap, overlap = parent
        m = self._load()
        st = self._striped()
        span = min(overlap, m["size"])
        end = offset + length
        touched = {objno for objno, _o, _n in
                   st.layout.extents(offset, length)}
        for objno in sorted(touched):
            if self._obj_exists(objno):
                continue
            extents = st.layout.object_logical_extents(objno, span)
            if all(offset <= lo and lo + ln <= end
                   for lo, ln in extents):
                # the incoming write fully covers this object's bytes:
                # nothing parent-backed survives it (CopyupRequest's
                # full-overwrite fast path)
                continue
            self._materialize_object(st, extents, parent_img, psnap)

    def _materialize_object(self, st, extents, parent_img,
                            psnap: str, mark_om: bool = False) -> bool:
        """Pull one object's parent-backed bytes into the child (the
        shared copy-up/flatten loop).  All-zero parent bytes create no
        object — reads keep falling through to the parent's zeros, and
        a rerun is idempotent.  Returns True if anything was written."""
        wrote = False
        for log_off, ln in extents:
            data = parent_img.read(log_off, ln, snap=psnap)
            if data.rstrip(b"\x00"):
                if mark_om:
                    self._om_mark_write(log_off, ln)
                st.write(data, log_off)
                wrote = True
        return wrote

    def _clone_read(self, offset: int, length: int, snapid: int,
                    prec: dict) -> bytes:
        """Clone read path: objects the child has are served locally;
        missing objects (or objects with no state at the requested
        child snap) read THROUGH to parent@snap, clipped to the
        overlap (beyond it the clone reads zeros).  prec is the parent
        record governing THIS read (the head's, or the one frozen in
        the child snap being read)."""
        parent_img = Image(self.io, prec["image"])
        psnap, overlap = prec["snap"], int(prec["overlap"])
        st = self._striped()
        parts: list[bytes] = []
        pos = offset
        for objno, obj_off, n in st.layout.extents(offset, length):
            chunk: bytes | None = None
            if self._obj_exists(objno):
                try:
                    chunk = self.io.read(self._obj_name(objno),
                                         length=n, offset=obj_off,
                                         snapid=snapid)
                except OSError:
                    chunk = None    # no state at that child snap
            if chunk is None:
                if pos < overlap:
                    pn = min(n, overlap - pos)
                    chunk = parent_img.read(pos, pn, snap=psnap)
                else:
                    chunk = b""
            if len(chunk) < n:
                chunk = chunk + bytes(n - len(chunk))
            parts.append(chunk)
            pos += n
        return b"".join(parts)

    def clone(self, dst_name: str, snap: str) -> "Image":
        """COW clone (CloneRequest.cc): the child links to
        parent@snap and shares its objects — no data is copied.  Reads
        fall through to the parent; the first write to an object
        copies it up (see _copyup); `flatten` severs the link.  The
        snapshot must be PROTECTED first (and stays unremovable while
        children exist)."""
        m = self._load()
        ent = m.get("snaps", {}).get(snap)
        if ent is None:
            raise KeyError(f"no snapshot {snap!r}")
        if not ent.get("protected"):
            raise OSError(22, f"snapshot {snap!r} is not protected")
        inherit = [f for f in m.get("features", [])
                   if f in (FEATURE_OBJECT_MAP, FEATURE_FAST_DIFF)]
        dst = Image.create(self.io, dst_name, size=ent["size"],
                           order=m["order"], stripe_unit=m["stripe_unit"],
                           stripe_count=m["stripe_count"],
                           features=inherit)
        dm = dst._load()
        dm["parent"] = {"image": self.name, "snap": snap,
                        "snapid": ent["snapid"],
                        "overlap": ent["size"]}
        dst._save_meta(dm)
        self._register_child(snap, dst_name)
        return dst

    def flatten(self) -> int:
        """Copy every still-parent-backed object into the child's HEAD
        and sever the head's parent link (librbd flatten — the explicit
        end of thin provisioning).  Returns objects materialized.

        Child snapshots keep the parent record frozen at their
        creation, so their view survives the flatten — and while any
        such snap exists the child stays in the parent's children
        registry, keeping unprotect refused (the reference's
        snapshots-remain-clones semantics)."""
        parent = self._parent()
        if parent is None:
            return 0
        self._check_primary()
        self._check_lock()
        parent_img, psnap, overlap = parent
        m = self._load()
        st = self._striped()
        span = min(overlap, m["size"])
        copied = 0
        for objno in range(st.layout.num_objects(span)):
            if self._obj_exists(objno):
                continue
            if self._materialize_object(
                    st, st.layout.object_logical_extents(objno, span),
                    parent_img, psnap, mark_om=True):
                copied += 1
        del m["parent"]
        self._save_meta(m)
        if not any(e.get("parent") for e in
                   m.get("snaps", {}).values()):
            parent_img._unregister_child(psnap, self.name)
        return copied

    def resize(self, new_size: int) -> None:
        self._check_primary()
        m = self._load()
        self._check_lock()
        self._journal_event({"op": "resize", "size": new_size})
        if new_size < m["size"]:
            # shrink trims the discarded extent (real rbd semantics):
            # growing back later must read zeros, not stale payload
            self._striped().truncate(new_size)
            # a clone shrunk below its parent overlap must never grow
            # back into parent bytes it discarded
            p = m.get("parent")
            if p and new_size < int(p["overlap"]):
                p["overlap"] = new_size
        m["size"] = new_size
        self._save_meta(m)
        if self._om_enabled():
            om = self._om_load()
            if om is not None:
                st = self._striped()
                om.resize(st.layout.num_objects(new_size))
                om.save()
            self._om_invalidate()

    # -- object map / fast-diff (src/librbd/object_map/) ----------------------

    def rebuild_object_map(self) -> int:
        """Reconstruct the allocation bitmap from the actual backing
        objects (object_map::RebuildRequest — what `rbd object-map
        rebuild` and scrub-on-corruption run).  Returns objects found."""
        from ceph_tpu.rbd_object_map import OBJECT_EXISTS, ObjectMap
        m = self._load()
        st = self._striped()
        om = ObjectMap(self.io, self.name)
        om.resize(st.layout.num_objects(m["size"]))
        found = 0
        for objno in range(om.n_objs):
            try:
                st.io.stat(st.striper.object_name(st.name, objno))
            except OSError:
                continue
            om.set(objno, OBJECT_EXISTS)
            found += 1
        om.flags = 0     # rebuilt: the map is trustworthy again
        om.save()
        self._om_invalidate()
        return found

    def _om_for(self, snap: str | None):
        """(ObjectMap, size) as of a snapshot name or the head; raises
        if the map is missing/corrupt (callers rebuild or fall back)."""
        m = self._load()
        if snap is None:
            om = self._om_load()
            size = m["size"]
        else:
            ent = m.get("snaps", {}).get(snap)
            if ent is None:
                raise KeyError(f"no snapshot {snap!r}")
            om = self._om_load(ent["snapid"])
            size = ent["size"]
        if om is None:
            raise OSError(5, "object map missing or corrupt "
                             "(run rebuild_object_map)")
        if om.flags & 1:
            raise OSError(5, "object map flagged invalid")
        return om, size

    def diff(self, from_snap: str | None = None,
             to_snap: str | None = None) -> list[tuple[int, int, bool]]:
        """Fast-diff: [(offset, length, exists)] logical extents that
        changed between from_snap (None = the beginning) and to_snap
        (None = head), computed ENTIRELY from object maps — no data
        object is read or stat'ed (DiffRequest semantics).  Walks every
        snapshot map in (from, to]: each map's EXISTS bits are "dirty
        since the previous snap", so intermediate rewrites are caught."""
        from ceph_tpu.rbd_object_map import diff_objnos
        m = self._load()
        snaps = m.get("snaps", {})
        from_id = snaps[from_snap]["snapid"] if from_snap else 0
        to_id = (snaps[to_snap]["snapid"] if to_snap
                 else float("inf"))
        to_om, to_size = self._om_for(to_snap)
        from_om = self._om_for(from_snap)[0] if from_snap else None
        chain = []
        if from_snap:
            for _name, ent in sorted(snaps.items(),
                                     key=lambda kv: kv[1]["snapid"]):
                sid = ent["snapid"]
                if from_id < sid and sid < to_id:
                    om = self._om_load(sid)
                    if om is None:
                        # a lost intermediate map would silently drop
                        # rewrites made in its window: fail loudly like
                        # the endpoint maps do
                        raise OSError(
                            5, f"object map for snapshot id {sid} "
                               "missing or corrupt")
                    chain.append(om)
        chain.append(to_om)
        st = self._striped()
        out: list[tuple[int, int, bool]] = []
        for objno, exists in sorted(
                diff_objnos(from_om, chain).items()):
            for off, ln in st.layout.object_logical_extents(
                    objno, to_size):
                out.append((off, ln, exists))
        out.sort()
        return out

    def du(self, snap: str | None = None) -> dict:
        """Object-granular space usage from the map alone (`rbd du`
        with fast-diff: no per-object stats)."""
        om, size = self._om_for(snap)
        obj_size = 1 << self._load()["order"]
        present = om.count()
        return {"size": size, "used_objects": present,
                "provisioned_objects": om.n_objs,
                "used_bytes": min(present * obj_size, size)}

    def export_diff(self, from_snap: str | None = None,
                    to_snap: str | None = None) -> bytes:
        """Serialized changed-extent stream (`rbd export-diff`): header
        json line + per-extent records, readable by import_diff on any
        image.  Reads ONLY the changed extents' data."""
        recs = []
        m = self._load()
        to_size = (m["size"] if to_snap is None
                   else m["snaps"][to_snap]["size"])
        for off, ln, exists in self.diff(from_snap, to_snap):
            if exists:
                data = self.read(off, ln, snap=to_snap)
                recs.append({"off": off, "len": ln,
                             "data": binascii.hexlify(data).decode()})
            else:
                recs.append({"off": off, "len": ln, "zero": True})
        return json.dumps({"v": 1, "size": to_size,
                           "from": from_snap, "to": to_snap,
                           "extents": recs}).encode()

    def import_diff(self, blob: bytes) -> int:
        """Apply an export_diff stream (`rbd import-diff`).  An
        incremental stream (one exported with from_snap) names its base
        snapshot; the target must HOLD that snapshot or the apply is
        refused — applying a delta onto the wrong base silently yields
        a frankenimage (the reference embeds and checks the start snap
        the same way).  Returns bytes written."""
        doc = json.loads(blob.decode())
        m = self._load()
        base = doc.get("from")
        if base and base not in m.get("snaps", {}):
            raise ValueError(
                f"diff stream is incremental from snapshot {base!r}, "
                f"which this image does not have")
        if doc["size"] != m["size"]:
            self.resize(doc["size"])
        written = 0
        for rec in doc["extents"]:
            if rec.get("zero"):
                self.write(bytes(rec["len"]), rec["off"])
            else:
                self.write(binascii.unhexlify(rec["data"]), rec["off"])
            written += rec["len"]
        return written

    def remove(self) -> None:
        # librbd refuses removal while snapshots exist: the pool snaps
        # are only reachable through this header's name->snapid table
        if self._load().get("snaps"):
            raise OSError(16, "image has snapshots (remove them first)")
        self._check_lock()   # and while another owner holds the lock
        parent = self._parent()
        if parent is not None:
            parent_img, psnap, _ov = parent
            parent_img._unregister_child(psnap, self.name)
        from ceph_tpu.rbd_object_map import ObjectMap
        ObjectMap(self.io, self.name).remove()
        self._striped().remove()
        try:
            self.io.remove(self.HEADER_FMT.format(name=self.name))
        except OSError:
            pass
        try:
            self.io.rm_omap_keys(RBD_DIRECTORY, [self.name])
        except OSError:
            pass
        self._meta = None


class ImageCompletion:
    """A librbd AioCompletion over the object writes of one image
    write: complete when they all are; the first failure is its
    return value.  ``_w.event`` is set with the last part, as a
    rados completion's waiter event is set with its reply."""

    def __init__(self, parts: list):
        self._parts = parts
        self._w = _Whole()
        self._left = len(parts)
        self._mu = make_lock("rbd::ImageCompletion")
        for part in parts:
            theirs, part._w.event = part._w.event, _PartDone(self)
            if theirs.is_set():
                part._w.event.set()

    def _part_done(self) -> None:
        with self._mu:
            self._left -= 1
            last = self._left == 0
        if last:
            self._w.event.set()

    def is_complete(self) -> bool:
        return self._w.event.is_set()

    def wait_for_complete(self, timeout: float | None = None) -> bool:
        return self._w.event.wait(timeout)

    def get_return_value(self) -> int:
        return min(p.get_return_value() for p in self._parts)

    def cancel(self) -> None:
        for part in self._parts:
            if not part.is_complete():
                part.cancel()


class _Whole:
    def __init__(self):
        self.event = threading.Event()


class _PartDone(threading.Event):
    """A part's waiter event that tells its image completion."""

    def __init__(self, whole: ImageCompletion):
        super().__init__()
        self._whole = whole
        self._told = False

    def set(self) -> None:
        super().set()
        if not self._told:
            self._told = True
            self._whole._part_done()


def list_images(ioctx, probe: list[str] | None = None) -> list[str]:
    """Pool image listing from the rbd_directory omap object, unioned
    with probe hits (legacy images created before the directory existed
    still appear, even once the directory object does)."""
    found = set()
    try:
        found.update(ioctx.get_omap(RBD_DIRECTORY))
    except OSError:
        pass
    for name in probe or []:
        if name in found:
            continue
        try:
            ioctx.stat(Image.HEADER_FMT.format(name=name))
            found.add(name)
        except OSError:
            continue
    return sorted(found)
