"""Share (%) of the time inside the program's ``msched.step`` spans in the
traced window in which the chip ran no operation
(``bench.spans.step_idle_share``): the wait for the step's dispatch and for
its logits. ``step_idle_share.open`` and ``step_idle_share.closed`` are this
reader in the open-loop and the closed-loop cells."""


def read(rec):
    return rec.span_number("step_idle_share")
