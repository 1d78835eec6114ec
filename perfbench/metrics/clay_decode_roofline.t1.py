"""Clay decode program device time in the traced slice against the
roofline of the rebuilds its calls did: the calls seen in the slice,
each with the window's mean stripes and mean rebuilt chunks a call
(`work_clay.clay_decode_work`: bytes-bound, whichever way the program
computes the layers).

The stripes are counted from the OSDs' submissions (each carries the
whole object's stripes) and the calls from the Clay kernel's own
batches, as `gf_decode_roofline.t1` counts them for Reed-Solomon's; a
program without the Clay kernel's counters gives nothing."""
from perfbench.harness import work, work_clay


def read(r):
    if r.trace is None or "decode.clay_batches" not in r.after:
        return None
    submits = r.delta("osd.ec_decode_submits")
    calls = r.delta("decode.clay_batches")
    targets = r.delta("osd.ec_decode_targets")
    if not (submits and calls and targets):
        return None
    dep = r.cell.config["deployment"]
    k, m, su = int(dep["k"]), int(dep["m"]), int(dep["stripe_unit"])
    programs = r.cell.config["programs"]["decode"]
    object_stripes = -(-int(r.cell.traffic["object_size"]) // (k * su))
    per_call = submits * object_stripes / calls
    w = work_clay.clay_decode_work(
        r.trace.calls_of(*programs) * per_call, k, m, targets / submits,
        su, int(dep["sub_chunks"]))
    return work.roofline_share(w, r.peaks, r.trace.seconds_of(*programs))
