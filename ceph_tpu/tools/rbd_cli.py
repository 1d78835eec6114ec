"""`rbd` command-line tool (src/tools/rbd/ analog): image lifecycle,
snapshots, COW clones, object-map-aware du/diff and export/import —
the operator surface over ceph_tpu.rbd's librbd-lite.

    python -m ceph_tpu.tools.rbd_cli --mon <host> -p <pool> <command>

Commands (the rbd verbs they mirror):
    create NAME --size BYTES [--order N] [--features f1,f2]
    ls | info NAME | rm NAME | resize NAME --size BYTES
    snap create|rm|protect|unprotect|rollback NAME@SNAP
    snap ls NAME
    clone PARENT@SNAP CHILD           (COW; parent snap must be protected)
    flatten NAME | children PARENT@SNAP
    du NAME [--snap S] | diff NAME [--from-snap A] [--to-snap B]
    export NAME FILE | import FILE NAME
"""

from __future__ import annotations

import argparse
import json
import sys


def _split_at(spec: str) -> tuple[str, str]:
    if "@" not in spec:
        raise SystemExit(f"expected IMAGE@SNAP, got {spec!r}")
    name, snap = spec.split("@", 1)
    return name, snap


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="rbd")
    p.add_argument("--mon", required=True, help="mon host(s)")
    p.add_argument("-p", "--pool", type=int, required=True)
    p.add_argument("--ms-type", default="async")
    p.add_argument("--auth-key", default="",
                   help="cluster shared key (authenticated clusters)")
    p.add_argument("words", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if not args.words:
        p.error("missing command")

    from ceph_tpu.client import RadosClient
    from ceph_tpu.rbd import Image, list_images
    client = RadosClient(args.mon, ms_type=args.ms_type,
                         auth_key=args.auth_key.encode()
                         if args.auth_key else None)
    client.connect()
    io = client.open_ioctx(args.pool)
    w = args.words
    try:
        cmd = w[0]
        if cmd == "create":
            sub = argparse.ArgumentParser(prog="rbd create")
            sub.add_argument("name")
            sub.add_argument("--size", type=int, required=True)
            sub.add_argument("--order", type=int, default=22)
            sub.add_argument("--features", default="")
            a = sub.parse_args(w[1:])
            feats = [f for f in a.features.split(",") if f]
            Image.create(io, a.name, size=a.size, order=a.order,
                         features=feats)
            return 0
        if cmd == "ls":
            for n in list_images(io):
                print(n)
            return 0
        if cmd == "info":
            img = Image(io, w[1])
            st = img.stat()
            st["features"] = img.features()
            parent = img._parent()
            if parent is not None:
                pi, ps, ov = parent
                st["parent"] = f"{pi.name}@{ps} (overlap {ov})"
            print(json.dumps(st, indent=1))
            return 0
        if cmd == "rm":
            Image(io, w[1]).remove()
            return 0
        if cmd == "resize":
            sub = argparse.ArgumentParser(prog="rbd resize")
            sub.add_argument("name")
            sub.add_argument("--size", type=int, required=True)
            a = sub.parse_args(w[1:])
            Image(io, a.name).resize(a.size)
            return 0
        if cmd == "snap":
            verb = w[1]
            if verb == "ls":
                for s, ent in Image(io, w[2]).snap_list().items():
                    flag = " (protected)" if ent.get("protected") else ""
                    print(f"{s}\tsize {ent['size']}{flag}")
                return 0
            name, snap = _split_at(w[2])
            img = Image(io, name)
            if verb == "create":
                img.snap_create(snap)
            elif verb == "rm":
                img.snap_remove(snap)
            elif verb == "protect":
                img.snap_protect(snap)
            elif verb == "unprotect":
                img.snap_unprotect(snap)
            elif verb == "rollback":
                img.snap_rollback(snap)
            else:
                raise SystemExit(f"unknown snap verb {verb!r}")
            return 0
        if cmd == "clone":
            pname, psnap = _split_at(w[1])
            Image(io, pname).clone(w[2], psnap)
            return 0
        if cmd == "flatten":
            n = Image(io, w[1]).flatten()
            print(f"flattened: {n} objects materialized")
            return 0
        if cmd == "children":
            pname, psnap = _split_at(w[1])
            for c in Image(io, pname).list_children(psnap):
                print(c)
            return 0
        if cmd == "du":
            sub = argparse.ArgumentParser(prog="rbd du")
            sub.add_argument("name")
            sub.add_argument("--snap", default=None)
            a = sub.parse_args(w[1:])
            print(json.dumps(Image(io, a.name).du(snap=a.snap)))
            return 0
        if cmd == "diff":
            sub = argparse.ArgumentParser(prog="rbd diff")
            sub.add_argument("name")
            sub.add_argument("--from-snap", default=None)
            sub.add_argument("--to-snap", default=None)
            a = sub.parse_args(w[1:])
            for off, ln, exists in Image(io, a.name).diff(
                    from_snap=a.from_snap, to_snap=a.to_snap):
                print(f"{off}\t{ln}\t{'data' if exists else 'zero'}")
            return 0
        if cmd == "export":
            img = Image(io, w[1])
            data = img.read(0, img.stat()["size"])
            with open(w[2], "wb") as f:
                f.write(data)
            print(f"exported {len(data)} bytes")
            return 0
        if cmd == "import":
            with open(w[1], "rb") as f:
                data = f.read()
            img = Image.create(io, w[2], size=len(data))
            if data.rstrip(b"\x00"):
                img.write(data, 0)
            print(f"imported {len(data)} bytes")
            return 0
        raise SystemExit(f"unknown rbd command {cmd!r}")
    except IndexError:
        print(f"rbd: missing operand for {w[0]!r}", file=sys.stderr)
        return 2
    except (OSError, KeyError, FileExistsError) as e:
        print(f"rbd: {e}", file=sys.stderr)
        return 1
    finally:
        client.shutdown()


if __name__ == "__main__":
    sys.exit(main())
