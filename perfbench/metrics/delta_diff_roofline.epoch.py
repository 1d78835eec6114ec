"""Device time of the epoch diff in the traced slice against the least
time the chip's memory bandwidth allows for comparing two tables of the
pool's answers, for the epochs that lie whole in the slice.  An answer
is a PG's up and acting sets and their two primaries: 2 x size + 2
words of four bytes, whatever layout a program keeps them in.  Nothing
where the configuration names no diff program or the trace holds none.
"""

from perfbench.harness import work, work_mapping


def read(r):
    if r.trace is None or r.slice_t is None:
        return None
    programs = r.cell.config.get("programs", {}).get("delta_diff")
    if not programs:
        return None
    dep = r.cell.config["deployment"]
    t_a, t_b = r.slice_t
    epochs = sum(1 for e in r.log.epochs
                 if t_a <= e.t_start and e.t_end <= t_b)
    w = work_mapping.table_diff_work(
        rows=epochs * int(dep["pg_num"]),
        row_bytes=4 * (2 * int(dep["size"]) + 2))
    return work.roofline_share(w, r.peaks, r.trace.seconds_of(*programs))
