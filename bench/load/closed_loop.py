"""Closed loop: a fixed number of requests outstanding for the whole window.

Mix parameters: ``concurrency``, ``zipf_s`` and ``block``. Every answered
request is replaced at once by a new one; the models come in blocks of
``block`` with exact Zipf counts, in seeded order. The server serves the
model whose request has waited longest, so each model's queue holds about
its share of ``concurrency`` at its turn: 128 over three models at Zipf(1)
keeps every 32-step slice full. This measures what the server completes per
second; latency here is only queue position and is not reported. A request counts as attempted when it is
answered inside the window; the ones still outstanding at its close have not
failed.
"""
from __future__ import annotations

from bench import traffic_gen
from bench.record import Outcome


def drive(sess, mix, seconds, rng) -> Outcome:
    shares = traffic_gen.popularity(sess.n_models, mix["zipf_s"])
    stream = traffic_gen.model_stream(rng, shares, mix["block"])
    t0 = sess.begin(seconds)
    for _ in range(mix["concurrency"]):
        sess.submit(next(stream), t0, True)

    def replace(now, answered):
        for _ in answered:
            sess.submit(next(stream), now, True)

    sess.serve(t0 + seconds, replace)
    sess.end()
    return Outcome(attempted=len(sess.answered_in_window()), failed=0)
