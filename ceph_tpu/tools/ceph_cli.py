"""The `ceph` CLI (src/ceph.in analog): argv -> JSON mon command ->
leader (any mon forwards), printing the JSON/text reply.

    python -m ceph_tpu.tools.ceph_cli -m 127.0.0.1:6789 status
    python -m ceph_tpu.tools.ceph_cli -m ... osd tree
    python -m ceph_tpu.tools.ceph_cli -m ... osd pool create pg_num=8 size=3
    python -m ceph_tpu.tools.ceph_cli -m ... osd out 3
    python -m ceph_tpu.tools.ceph_cli -m ... osd pool mksnap pool=1 snap=s1
"""

from __future__ import annotations

import argparse
import sys


#: prefix -> positional argument names (mirrors MonCommands.h schemas)
COMMANDS = {
    ("status",): [],
    ("health",): [],
    ("health", "detail"): [],
    ("config", "set"): ["who", "name", "value"],
    ("config", "get"): ["who", "name"],
    ("config", "rm"): ["who", "name"],
    ("config", "dump"): [],
    ("auth", "get-or-create"): ["entity"],
    ("auth", "get"): ["entity"],
    ("auth", "print-key"): ["entity"],
    ("auth", "ls"): [],
    ("auth", "del"): ["entity"],
    ("quorum_status",): [],
    ("mon", "dump"): [],
    ("log", "last"): ["num"],
    ("log",): ["message"],
    ("mon", "add"): ["id", "addr"],
    ("mon", "rm"): ["id"],
    ("fs", "new"): ["fs_name", "metadata", "data"],
    ("fs", "status"): [],
    ("fs", "set"): ["var", "val"],
    ("osd", "tree"): [],
    ("osd", "getmap"): [],
    ("osd", "pool", "create"): [],
    ("osd", "pool", "set"): ["pool", "var", "val"],
    ("osd", "pool", "mksnap"): [],
    ("osd", "pool", "rmsnap"): [],
    ("osd", "getcrushmap"): [],
    ("osd", "setcrushmap"): [],
    ("osd", "reweight"): ["id", "weight"],
    ("osd", "reweight-by-utilization"): [],
    ("osd", "out"): ["id"],
    ("osd", "in"): ["id"],
    ("osd", "down"): ["id"],
    ("osd", "pg-upmap-items"): ["pgid", "*id_pairs"],
    ("osd", "rm-pg-upmap-items"): ["pgid"],
    ("mgr", "dump"): [],
    ("mgr", "module", "ls"): [],
    ("mgr", "module", "enable"): ["module"],
    ("mgr", "module", "disable"): ["module"],
    ("pg", "dump"): [],
    ("df",): [],
    ("pg", "ls"): ["pool"],
    ("iostat",): [],
    ("balancer", "status"): [],
    ("balancer", "optimize"): [],
    ("telemetry", "show"): [],
    ("osd", "pool", "autoscale-status"): [],
    ("config-key", "set"): ["key", "value"],
    ("config-key", "get"): ["key"],
    ("config-key", "rm"): ["key"],
    ("config-key", "dump"): [],
    ("tracing", "ls"): [],
    ("tracing", "show"): ["trace_id"],
    ("slow_ops",): [],
    ("qos", "set"): ["tenant"],
    ("qos", "rm"): ["tenant"],
    ("qos", "ls"): [],
    ("qos", "slo", "set"): ["tenant"],
    ("qos", "slo", "rm"): ["tenant"],
    ("qos", "slo", "ls"): [],
    ("slo", "status"): [],
    ("usage", "top"): [],
}

#: prefixes served by the active MGR (re-targeted via `mgr dump`),
#: like the reference's mgr command routing
MGR_COMMANDS = {"pg dump", "pg ls", "iostat", "df", "balancer status",
                "balancer optimize", "telemetry show",
                "mgr module ls", "mgr module enable",
                "mgr module disable", "osd pool autoscale-status",
                "tracing ls", "tracing show", "slow_ops",
                "slo status", "usage top"}


def parse_command(words: list[str]) -> dict:
    """Longest matching prefix wins; remaining words become positional
    schema args or key=value pairs."""
    for n in range(min(3, len(words)), 0, -1):
        key = tuple(words[:n])
        if key in COMMANDS:
            cmd = {"prefix": " ".join(key)}
            rest = words[n:]
            schema = COMMANDS[key]
            pos = 0
            for w in rest:
                if pos < len(schema) and schema[pos].startswith("*"):
                    # rest-list argument swallows remaining words
                    cmd.setdefault(schema[pos][1:], []).append(w)
                elif "=" in w:
                    k, v = w.split("=", 1)
                    cmd[k] = v
                elif pos < len(schema):
                    cmd[schema[pos]] = w
                    pos += 1
                else:
                    raise ValueError(f"unexpected argument {w!r}")
            return cmd
    raise ValueError(f"unknown command {' '.join(words)!r}; known: "
                     + ", ".join(" ".join(k) for k in sorted(COMMANDS)))


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    ap = argparse.ArgumentParser(prog="ceph")
    ap.add_argument("-m", "--mon-host", required=True,
                    help="comma-separated monitor addresses")
    ap.add_argument("--timeout", type=float, default=15.0)
    ap.add_argument("--auth-key", default=None)
    ap.add_argument("-i", "--infile",
                    help="crush binary for setcrushmap")
    ap.add_argument("-o", "--outfile",
                    help="write getcrushmap output here")
    ap.add_argument("words", nargs="+")
    args = ap.parse_args(argv)
    try:
        cmd = parse_command(args.words)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 22
    if cmd["prefix"] == "osd setcrushmap":
        import base64
        from ceph_tpu.tools.crushtool import read_binary as _rb
        if not args.infile:
            print("setcrushmap needs -i <crushtool binary>",
                  file=sys.stderr)
            return 22
        from ceph_tpu.msg.encoding import Encoder
        from ceph_tpu.osd.map_codec import encode_crush
        try:
            m, names = _rb(args.infile)   # validates framing + names
        except (SystemExit, Exception) as e:   # DecodeError/struct/...
            print(f"cannot read {args.infile}: {e}", file=sys.stderr)
            return 22
        e = Encoder()
        encode_crush(m, e)
        cmd["crush_b64"] = base64.b64encode(e.tobytes()).decode()
        cmd["names"] = {"types": names.types, "items": names.items,
                        "rules": names.rules, "classes": names.classes}
    from ceph_tpu.client.rados import RadosClient
    client = RadosClient(args.mon_host, timeout=args.timeout,
                         auth_key=args.auth_key)
    try:
        client.msgr.bind("127.0.0.1:0")
        client.msgr.start()
        if cmd["prefix"] in MGR_COMMANDS:
            res, out = client.mgr_command(cmd)
        else:
            res, out = client.mon_command(cmd)
        if res == 0 and cmd["prefix"] == "osd getcrushmap" \
                and args.outfile:
            import base64, json
            from ceph_tpu.tools.crushtool import write_binary_blob
            reply = json.loads(out)
            write_binary_blob(args.outfile,
                              base64.b64decode(reply["crush_b64"]),
                              reply.get("names") or {})
            print(f"wrote {args.outfile}")
        else:
            print(out)
        return -res if res < 0 else res
    finally:
        client.shutdown()


if __name__ == "__main__":
    sys.exit(main())
