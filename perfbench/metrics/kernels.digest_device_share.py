"""The digest program's share of all program time on the device in the
traced slice."""


def read(r):
    if r.trace is None:
        return None
    total = sum(r.trace.program_s.values())
    digest = r.trace.seconds_of(*r.cell.config["programs"]["digest"])
    return 100.0 * digest / total if total > 0 and digest > 0 else None
