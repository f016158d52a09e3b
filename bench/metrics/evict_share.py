"""Share (%) of the time spent moving tenants in (the program's
``msched.switch`` and ``msched.fault_service`` spans) that goes to
``msched.evict``, in the traced window (``bench.spans.evict_share``).
``evict_share.open`` and ``evict_share.closed`` are this reader in the
open-loop and the closed-loop cell that migrate."""


def read(rec):
    return rec.span_number("evict_share")
