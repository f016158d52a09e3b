"""Bytes the live runtime copied host to device over the window's slices,
per request they answered."""


def read(rec):
    return rec.per_answer("in_bytes")
