"""Median host-clock time of one ``run_step`` in the window. It ends in a
host copy of the logits, so it includes the device's completion."""


def read(rec):
    return rec.median_step_ms()
