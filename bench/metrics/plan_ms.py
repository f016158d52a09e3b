"""Median host time of the coordinator's plan, the program's
``msched.plan`` span, in the traced window (``bench.spans.plan_ms``).
``plan_ms.open`` and ``plan_ms.closed`` are this reader in the open-loop
and the closed-loop cells."""


def read(rec):
    return rec.span_number("plan_ms")
