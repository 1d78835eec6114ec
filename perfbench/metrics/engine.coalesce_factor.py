"""Requests per device call of the encode engine over the window."""


def read(r):
    calls = r.delta("encode.batches")
    return r.delta("encode.submits") / calls if calls else None
