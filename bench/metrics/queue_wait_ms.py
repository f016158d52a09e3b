"""Nearest-rank 95th percentile of the time the window's answered requests
waited in the server's queue: from ``MultiModelServer.submit`` to the start
of the slice that answered them, as the program stamps each request
(``Request.submitted_s`` and ``started_s``). It leaves out how late the
harness submitted a request after it was due. ``queue_wait_ms.open`` is
this reader in the open-loop cell; a program that does not stamp its
requests gives nothing."""
from bench.record import percentile


def read(rec):
    waits = sorted(
        t.req.started_s - t.req.submitted_s
        for t in rec.answered_in_window()
        if getattr(t.req, "started_s", None) is not None
    )
    return 1000.0 * percentile(waits, 95) if waits else None
