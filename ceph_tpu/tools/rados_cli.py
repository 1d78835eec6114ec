"""`rados` command-line tool (src/tools/rados/rados.cc analog): direct
object operations against a pool — the lowest-level operator surface.

    python -m ceph_tpu.tools.rados_cli --mon <host> -p <pool> <command>

Commands (the rados verbs they mirror):
    put OBJ FILE | get OBJ FILE | rm OBJ
    ls | stat OBJ
    listomapvals OBJ | setomapval OBJ KEY VALUE | rmomapkey OBJ KEY
    df                 (per-pool usage from the mgr's aggregates)
    bench ...          -> use ceph_tpu.tools.rados_bench (obj_bencher)
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="rados")
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
    client = RadosClient(args.mon, ms_type=args.ms_type,
                         auth_key=args.auth_key.encode()
                         if args.auth_key else None)
    client.connect()
    io = client.open_ioctx(args.pool)
    w = args.words
    try:
        cmd = w[0]
        if cmd == "put":
            with open(w[2], "rb") as f:
                io.write_full(w[1], f.read())
            return 0
        if cmd == "get":
            st = io.stat(w[1])
            data = io.read(w[1], st["size"])
            if w[2] == "-":
                sys.stdout.buffer.write(data)
            else:
                with open(w[2], "wb") as f:
                    f.write(data)
            return 0
        if cmd == "rm":
            io.remove(w[1])
            return 0
        if cmd == "ls":
            for oid in sorted(io.list_objects()):
                print(oid)
            return 0
        if cmd == "stat":
            st = io.stat(w[1])
            print(f"{w[1]} size {st['size']}")
            return 0
        if cmd == "listomapvals":
            for k, v in sorted(io.get_omap(w[1]).items()):
                print(f"{k}\t{v!r}")
            return 0
        if cmd == "setomapval":
            io.set_omap(w[1], {w[2]: w[3].encode()})
            return 0
        if cmd == "rmomapkey":
            io.rm_omap_keys(w[1], [w[2]])
            return 0
        if cmd == "df":
            import json
            res, out = client.mgr_command({"prefix": "pg dump"})
            if res != 0:
                print(f"rados: mgr unavailable: {out}", file=sys.stderr)
                return 1
            dump = json.loads(out)
            per_pool: dict[int, list[int]] = {}
            for row in dump["pg_stats"]:
                pid = int(row["pgid"].split(".")[0])
                agg = per_pool.setdefault(pid, [0, 0, 0])
                agg[0] += 1
                agg[1] += int(row.get("num_objects", 0))
                agg[2] += int(row.get("bytes", 0))
            print("POOL\tPGS\tOBJECTS\tBYTES")
            for pid in sorted(per_pool):
                pgs, objs, byts = per_pool[pid]
                print(f"{pid}\t{pgs}\t{objs}\t{byts}")
            return 0
        raise SystemExit(f"unknown rados command {cmd!r}")
    except IndexError:
        print(f"rados: missing operand for {w[0]!r}", file=sys.stderr)
        return 2
    except OSError as e:
        print(f"rados: {e}", file=sys.stderr)
        return 1
    finally:
        client.shutdown()


if __name__ == "__main__":
    sys.exit(main())
