"""Percent of the window's table diffs (an epoch's delta against the
previous epoch's table) that the device served.  Nothing on a program
that does not count them."""


def read(r):
    if "mapping.delta_device_diffs" not in r.after:
        return None
    device = r.delta("mapping.delta_device_diffs")
    total = device + r.delta("mapping.delta_host_diffs")
    return 100.0 * device / total if total else None
