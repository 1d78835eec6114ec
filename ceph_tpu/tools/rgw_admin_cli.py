"""`radosgw-admin` command-line tool (src/rgw/rgw_admin.cc analog,
the user-management core): S3 users live as records in the gateway's
backing pool (`.users.registry`), so every radosgw over that pool
serves them — created here, usable through any gateway within its
short user-cache TTL, no restarts.

    python -m ceph_tpu.tools.rgw_admin_cli --mon <host> -p <pool> <cmd>

Commands:
    user create --uid NAME [--access A] [--secret S] [--tenant T]
    user ls | user info --uid NAME | user rm --uid NAME
    bucket ls                       (the pool's bucket registry)
"""

from __future__ import annotations

import argparse
import json
import secrets
import sys


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="radosgw-admin")
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
    from ceph_tpu.rgw_rest import (
        S3Gateway, load_pool_users, remove_pool_user, save_pool_user)
    client = RadosClient(args.mon, ms_type=args.ms_type,
                         auth_key=args.auth_key.encode()
                         if args.auth_key else None)
    client.connect()
    io = client.open_ioctx(args.pool)
    w = args.words
    try:
        if w[0] == "user":
            verb = w[1]
            sub = argparse.ArgumentParser(prog=f"radosgw-admin user {verb}")
            if verb != "ls":
                sub.add_argument("--uid", required=True)
            if verb == "create":
                sub.add_argument("--access", default="")
                sub.add_argument("--secret", default="")
                sub.add_argument("--tenant", default="",
                                 help="QoS tenant lane (defaults to "
                                      "the uid; see docs/QOS.md)")
            a = sub.parse_args(w[2:])
            users = load_pool_users(io)
            if verb == "ls":
                for access, rec in sorted(users.items()):
                    print(f"{rec.get('uid', '?')}\t{access}")
                return 0
            if verb == "create":
                if any(r.get("uid") == a.uid for r in users.values()):
                    print(f"user {a.uid!r} exists", file=sys.stderr)
                    return 1
                if a.access and a.access in users:
                    print(f"access key {a.access!r} belongs to "
                          f"{users[a.access].get('uid')!r}",
                          file=sys.stderr)
                    return 1
                access = a.access or \
                    "AK" + secrets.token_hex(9).upper()
                secret = a.secret or secrets.token_hex(20)
                save_pool_user(io, access, secret, a.uid,
                               tenant=a.tenant or None)
                print(json.dumps({"uid": a.uid, "access_key": access,
                                  "secret_key": secret,
                                  "tenant": a.tenant or a.uid},
                                 indent=1))
                return 0
            mine = {acc: r for acc, r in users.items()
                    if r.get("uid") == a.uid}
            if not mine:
                print(f"no such user {a.uid!r}", file=sys.stderr)
                return 1
            if verb == "info":
                print(json.dumps(
                    {"uid": a.uid,
                     "keys": [{"access_key": acc,
                               "created": r.get("created")}
                              for acc, r in sorted(mine.items())]},
                    indent=1))
                return 0
            if verb == "rm":
                for acc in mine:
                    remove_pool_user(io, acc)
                return 0
            raise SystemExit(f"unknown user verb {verb!r}")
        if w[0] == "bucket" and w[1] == "ls":
            try:
                reg = io.get_omap(S3Gateway.REGISTRY)
            except OSError:
                reg = {}
            for name in sorted(reg):
                print(name)
            return 0
        raise SystemExit(f"unknown command {' '.join(w)!r}")
    except IndexError:
        print(f"radosgw-admin: missing operand for {w[0]!r}",
              file=sys.stderr)
        return 2
    except OSError as e:
        print(f"radosgw-admin: {e}", file=sys.stderr)
        return 1
    finally:
        client.shutdown()


if __name__ == "__main__":
    sys.exit(main())
