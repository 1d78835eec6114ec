"""Megabytes of tables the mapping service uploaded for the device
diffs of the window's epochs, per epoch the service computed between
the same two readings of its counters.  Nothing on a program that does
not count them."""


def read(r):
    key = "mapping.delta_upload_bytes"
    if key not in r.after:
        return None
    epochs = r.delta("mapping.epoch_updates")
    return r.delta(key) / epochs / 1e6 if epochs else None
