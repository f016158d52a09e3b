"""Nearest-rank median latency of the requests due in the window, from when
each was due to when its answer came back."""


def read(rec):
    return rec.latency_ms(50)
