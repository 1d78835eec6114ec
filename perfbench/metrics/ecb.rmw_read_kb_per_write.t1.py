"""KiB that the OSDs' rmw gathers moved (the primary's own shard
included) per overwrite that went the stripe-ranged read-modify-write
way, over the window: k chunks of each stripe a write touches, 32.0 for
a 4 KiB write inside one stripe of the k = 8, 4 KiB-unit pool (a
whole-object gather moves 4,096)."""


def read(r):
    if "osd.ec_rmw_writes" not in r.after:
        return None
    writes = r.delta("osd.ec_rmw_writes")
    return (r.delta("osd.ec_rmw_read_bytes") / writes / 1024
            if writes else None)
