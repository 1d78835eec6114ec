"""Megabytes of CRUSH bucket tables the mapping path put on the device
over the window, per epoch the service computed between the same two
readings of its counters.  Nothing on a program that does not count
them."""


def read(r):
    key = "mapping.crush_table_upload_bytes"
    if key not in r.after:
        return None
    epochs = r.delta("mapping.epoch_updates")
    return r.delta(key) / epochs / 1e6 if epochs else None
