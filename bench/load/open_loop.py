"""Open loop: requests arrive at a fixed mean rate, whatever the server does.

Mix parameters: ``rate_rps`` (mean arrivals per second), ``zipf_s`` (model
popularity by rank) and ``drain_s``. The window offers exactly
round(rate x seconds) requests, Poisson gaps in seeded order (see
``bench.traffic_gen``), the first due at the window's start. Each request is
timed from when it was due. After the window the same process goes on
offering load until every request due in the window is answered or
``drain_s`` has passed; those still unanswered then have failed.
"""
from __future__ import annotations

from collections import deque

import numpy as np

from bench import traffic_gen
from bench.record import Outcome


def _block(rng, mix, shares, n, seconds, start):
    gaps = traffic_gen.exponential_gaps(rng, mix["rate_rps"], n, seconds)
    dues = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return zip(dues.tolist(), traffic_gen.model_sequence(rng, shares, n).tolist())


def drive(sess, mix, seconds, rng) -> Outcome:
    shares = traffic_gen.popularity(sess.n_models, mix["zipf_s"])
    n = int(round(mix["rate_rps"] * seconds))
    if n < 1:
        raise ValueError(f"rate {mix['rate_rps']} req/s offers nothing in {seconds} s")
    t0 = sess.begin(seconds)
    t_end = t0 + seconds
    deadline = t_end + mix["drain_s"]
    plan = deque((due, m, True) for due, m in _block(rng, mix, shares, n, seconds, t0))
    later = [t_end]  # start of the next block of after-window arrivals
    window_reqs = []

    def settled():
        return len(window_reqs) == n and all(t.done is not None for t in window_reqs)

    def submit_due(now, _answered=()):
        while True:
            if not plan:
                if now < t_end or settled():
                    return
                plan.extend((due, m, False) for due, m in _block(rng, mix, shares, n, seconds, later[0]))
                later[0] += seconds
            due, m, in_window = plan[0]
            if due > now or (not in_window and settled()):
                return
            plan.popleft()
            t = sess.submit(m, due, in_window)
            if in_window:
                window_reqs.append(t)

    while True:
        now = sess.clock()
        submit_due(now)
        if (now >= t_end and settled()) or now >= deadline:
            break
        if sess.queued():
            sess.serve(deadline, submit_due)
        else:
            sess.wait(min(plan[0][0] if plan else t_end, deadline))
    sess.end()
    failed = sum(t.done is None for t in window_reqs)
    latencies = [(t.done if t.done is not None else deadline) - t.due for t in window_reqs]
    return Outcome(attempted=n, failed=failed, latencies_s=latencies)
