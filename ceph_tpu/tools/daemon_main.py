"""Daemon process entry point — the ceph-osd / ceph-mon `main()` analog.

Each daemon runs as its own OS process over the TCP messenger stack
(`python -m ceph_tpu.tools.daemon_main --role osd --id 2 ...`), the
reference's deployment model (src/ceph_osd.cc, src/ceph_mon.cc; spawned
by vstart.sh / qa/standalone/ceph-helpers.sh run_mon:437 run_osd:596).
The process stays up until SIGTERM/SIGINT; SIGKILL models crash-death
(the thrasher's kill mode) with the store surviving on disk.

The mon's listen address must be pre-agreed (it IS the cluster's
bootstrap identity), so `--addr` takes an explicit host:port; OSDs bind
an ephemeral port and advertise it through MOSDBoot as usual.
"""

from __future__ import annotations

import argparse
import signal
import sys
import os
import threading


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="ceph-tpu-daemon")
    p.add_argument("--role", required=True,
                   choices=["mon", "osd", "mgr", "mds", "rgw"])
    p.add_argument("--id", type=int, default=0)
    p.add_argument("--addr", default="127.0.0.1:0",
                   help="bind address (mons need an agreed host:port)")
    p.add_argument("--mon-host", default="",
                   help="comma-separated mon addresses")
    p.add_argument("--monmap", default="",
                   help="mon only: comma-separated monmap (all mons)")
    p.add_argument("--ms-type", default="async",
                   help="messenger stack; 'ici' selects the cross-"
                        "process ici-wire stack (TCP control plane + "
                        "device transfer data plane)")
    p.add_argument("--jax-cpu-devices", type=int, default=0,
                   help="force the cpu platform with N local devices "
                        "BEFORE jax initializes: a chip belongs to one "
                        "process, so every daemon of a multi-process "
                        "cluster but the one that owns it is started "
                        "with this")
    p.add_argument("--store-type", default="filestore")
    p.add_argument("--store-path", default="")
    p.add_argument("--auth-key", default="")
    p.add_argument("--heartbeats", action="store_true")
    p.add_argument("--metadata-pool", type=int, default=1)
    p.add_argument("--data-pool", type=int, default=2)
    p.add_argument("--rgw-pool", type=int, default=1,
                   help="rgw only: backing pool id")
    p.add_argument("--rgw-access", default="",
                   help="rgw only: explicit S3 access key (with "
                        "--rgw-secret; else derived from --auth-key)")
    p.add_argument("--rgw-secret", default="")
    p.add_argument("--rgw-port", type=int, default=0,
                   help="rgw only: HTTP listen port (0 = ephemeral; "
                        "the bound address prints on the ready line)")
    args = p.parse_args(argv)
    auth_key = args.auth_key.encode() if args.auth_key else None
    if args.jax_cpu_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count="
            f"{args.jax_cpu_devices}").strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
    ms_type = "ici-wire" if args.ms_type == "ici" else args.ms_type

    if args.role == "mon":
        from ceph_tpu.mon import Monitor
        d = Monitor(mon_id=args.id, ms_type="async", addr=args.addr,
                    store_path=args.store_path or None, auth_key=auth_key)
        d.init(monmap=[])
        monmap = (args.monmap or args.addr).split(",")
        if args.id >= len(monmap):
            print(f"error: --id {args.id} outside the {len(monmap)}-entry "
                  "monmap (pass --monmap with every mon's address)",
                  file=sys.stderr)
            return 2
        # substitute my own resolved addr (port 0 binds resolve late)
        monmap[args.id] = d.addr
        d.set_monmap(monmap)
    elif args.role == "osd":
        from ceph_tpu.osd.daemon import OSDDaemon
        d = OSDDaemon(args.id, args.mon_host, store_type=args.store_type,
                      store_path=args.store_path, ms_type=ms_type,
                      addr=args.addr, heartbeats=args.heartbeats,
                      auth_key=auth_key)
        d.init()
    elif args.role == "mgr":
        from ceph_tpu.mgr import MgrDaemon
        d = MgrDaemon(args.mon_host, ms_type="async", addr=args.addr,
                      auth_key=auth_key)
        d.init()
    elif args.role == "rgw":
        # the radosgw daemon shell: a RadosClient into the backing
        # pool + the S3 REST frontend; S3 credentials derive from the
        # cluster key (provision_from_cephx), so every rgw in the
        # cluster serves the same access/secret pair
        from ceph_tpu.client import RadosClient
        from ceph_tpu.rgw_rest import RgwRestServer
        if not auth_key and not (args.rgw_access and args.rgw_secret):
            print("error: an rgw needs credentials — pass --auth-key "
                  "(S3 keys derive from it) or --rgw-access/"
                  "--rgw-secret; an empty key table would 403 every "
                  "request", file=sys.stderr)
            return 2
        rc = RadosClient(args.mon_host, ms_type="async",
                         auth_key=auth_key)
        rc.connect()
        d = RgwRestServer(rc.open_ioctx(args.rgw_pool),
                          addr=f"127.0.0.1:{args.rgw_port}")
        if args.rgw_access and args.rgw_secret:
            d.add_key(args.rgw_access, args.rgw_secret)
        if auth_key:
            d.provision_from_cephx(auth_key)
        d.start()
    else:
        from ceph_tpu.mds import MDSDaemon
        d = MDSDaemon(args.mon_host, args.metadata_pool, args.data_pool,
                      ms_type="async", addr=args.addr, auth_key=auth_key)
        d.init()

    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    # readiness marker for the spawning harness (rgw appends its bound
    # HTTP address — the operator's endpoint)
    extra_info = f" {d.addr}" if args.role == "rgw" else ""
    sys.stdout.write(f"ready {args.role}.{args.id}{extra_info}\n")
    sys.stdout.flush()
    stop.wait()
    d.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
