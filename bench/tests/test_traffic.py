"""Seeded traffic: the same seed gives the same requests, another seed the
same work in another order."""
import numpy as np

from bench import traffic_gen


def _open_plan(seed, n=200, rate=4.0, seconds=50.0):
    rng = np.random.default_rng([seed, 0])
    gaps = traffic_gen.exponential_gaps(rng, rate, n, seconds)
    models = traffic_gen.model_sequence(rng, traffic_gen.popularity(3, 1.0), n)
    return gaps, models


def test_same_seed_same_traffic():
    a, b = _open_plan(2**33 + 1), _open_plan(2**33 + 1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_other_seed_same_work_other_order():
    (ga, ma), (gb, mb) = _open_plan(5), _open_plan(6)
    assert not np.array_equal(ga, gb) and not np.array_equal(ma, mb)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert np.array_equal(np.bincount(ma), np.bincount(mb))


def test_gaps_fill_the_window_at_the_rate():
    gaps, _ = _open_plan(3, n=200, rate=4.0, seconds=50.0)
    assert np.isclose(gaps.sum(), 50.0)
    # exponential quantiles: mean 1/rate, and about as spread as a Poisson process
    assert abs(gaps.mean() - 0.25) < 1e-9
    assert 0.8 < gaps.std() / gaps.mean() < 1.1


def test_zipf_counts_by_rank():
    shares = traffic_gen.popularity(3, 1.0)
    assert np.allclose(shares, [6 / 11, 3 / 11, 2 / 11])
    assert traffic_gen.exact_counts(shares, 11).tolist() == [6, 3, 2]
    assert traffic_gen.exact_counts(shares, 200).sum() == 200


def test_closed_stream_blocks_exact_and_seeded():
    shares = traffic_gen.popularity(3, 1.0)
    take = lambda seed: [m for m, _ in zip(traffic_gen.model_stream(np.random.default_rng(seed), shares, 11), range(33))]  # noqa: E731
    a, b, c = take(1), take(1), take(2)
    assert a == b and a != c
    for k in range(3):
        assert sorted(a[11 * k:11 * k + 11]) == [0] * 6 + [1] * 3 + [2] * 2
