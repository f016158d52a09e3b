"""Seeded arrivals and model popularity, shared by the load kinds.

Every seed offers the same work in another order: the same number of
requests to each model and the same set of gaps between arrivals, shuffled
by the seed. Runs with different seeds then differ in the order of the work
and not in its amount, which keeps the spread between runs to what the
system does.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np


def popularity(n_models: int, zipf_s: float) -> np.ndarray:
    """Zipf shares by rank: the i-th model (0-based) gets (i + 1) ** -s."""
    w = np.arange(1, n_models + 1, dtype=np.float64) ** -zipf_s
    return w / w.sum()


def exact_counts(shares: np.ndarray, n: int) -> np.ndarray:
    """Integer counts summing to ``n``, by the largest remainder."""
    raw = shares * n
    counts = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts


def model_sequence(rng: np.random.Generator, shares: np.ndarray, n: int) -> np.ndarray:
    """``n`` model indices with exactly ``exact_counts(shares, n)`` of each,
    in seeded order."""
    seq = np.repeat(np.arange(len(shares)), exact_counts(shares, n))
    return rng.permutation(seq)


def model_stream(rng: np.random.Generator, shares: np.ndarray, block: int) -> Iterator[int]:
    """Endless model indices, in blocks of ``block`` with exact counts."""
    while True:
        yield from (int(m) for m in model_sequence(rng, shares, block))


def exponential_gaps(rng: np.random.Generator, rate: float, n: int, total: float) -> np.ndarray:
    """``n`` gaps between Poisson arrivals at ``rate``: the exponential
    distribution's quantiles at (k + 1/2) / n, in seeded order, scaled so
    that they sum to ``total`` seconds."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return rng.permutation(q * (total / q.sum()))
