"""Decode program device time in the traced slice against the roofline
of the shards its calls rebuilt: the calls seen in the slice, each with
the window's mean stripes and mean rebuilt shards per device call.

Rebuilding t shards of a stripe from k is the product an encode of t
parity chunks is (`work.gf_encode_work` with m = t), whatever implements
it.  The stripes are counted from the OSDs' submissions (each carries
the whole object's stripes) and not from the decode engine's
`stripes_out`, which counts BlueStore's digest blocks too: they ride the
same engine."""
from perfbench.harness import work


def read(r):
    if r.trace is None:
        return None
    submits = r.delta("osd.ec_decode_submits")
    calls = r.delta("decode.ec_batches")
    targets = r.delta("osd.ec_decode_targets")
    if not (submits and calls and targets):
        return None
    dep = r.cell.config["deployment"]
    k, su = int(dep["k"]), int(dep["stripe_unit"])
    programs = r.cell.config["programs"]["decode"]
    object_stripes = -(-int(r.cell.traffic["object_size"]) // (k * su))
    per_call = submits * object_stripes / calls
    w = work.gf_encode_work(r.trace.calls_of(*programs) * per_call, k,
                            targets / submits, su)
    return work.roofline_share(w, r.peaks, r.trace.seconds_of(*programs))
