"""Encode program device time in the traced slice against the roofline
of the stripes its calls carried: the calls seen in the slice, each with
the window's mean stripes per device call."""
from perfbench.harness import work


def read(r):
    if r.trace is None or not r.delta("encode.batches"):
        return None
    dep = r.cell.config["deployment"]
    programs = r.cell.config["programs"]["encode"]
    per_call = r.delta("encode.stripes_out") / r.delta("encode.batches")
    w = work.gf_encode_work(r.trace.calls_of(*programs) * per_call,
                            int(dep["k"]), int(dep["m"]),
                            int(dep["stripe_unit"]))
    return work.roofline_share(w, r.peaks, r.trace.seconds_of(*programs))
