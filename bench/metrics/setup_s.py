"""Process start to the window's first request: init, weights, profiling,
warm-up, and in a first run the compiles."""


def read(rec):
    return rec.setup_s
