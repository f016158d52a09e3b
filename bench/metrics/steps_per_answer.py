"""Decode steps the runtime ran over the window's slices, per request they
answered: a slice runs a fixed number of steps, whatever its queue holds."""


def read(rec):
    return rec.per_answer("steps")
