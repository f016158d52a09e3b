"""Bytes the program's ``msched.fetch`` spans moved host to device over
their time (GB/s), in the traced window: from a copy's start until every
fetched array is on the device (``bench.spans.h2d_gbps``).
``h2d_gbps.open`` and ``h2d_gbps.closed`` are this reader in the open-loop
and the closed-loop cell that migrate."""


def read(rec):
    return rec.span_number("h2d_gbps")
