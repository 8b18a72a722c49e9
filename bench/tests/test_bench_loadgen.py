"""The load generator is a function of the mix and the seed alone."""
import numpy as np
import pytest

from bench.lib import loadgen

POISSON = {"generator": "poisson", "rate_qps": 1200.0, "pool": 4096, "draw": "zipf", "zipf_s": 1.1}
BULK = {"generator": "closed", "batch": 256, "pool": 4096, "draw": "uniform"}
SEED = 2**31 + 12345


@pytest.mark.parametrize("mix", [POISSON, BULK], ids=["poisson", "bulk"])
def test_same_seed_same_requests(mix):
    a, b = loadgen.plan(mix, SEED, 20.0), loadgen.plan(mix, SEED, 20.0)
    assert np.array_equal(a.picks(1000), b.picks(1000))
    if a.loop == "open":
        assert np.array_equal(a.due, b.due)
    c = loadgen.plan(mix, SEED + 1, 20.0)
    assert not np.array_equal(loadgen.plan(mix, SEED, 20.0).picks(1000), c.picks(1000))


def test_poisson_gaps_have_the_rate_as_mean():
    due = loadgen.plan(POISSON, SEED, 60.0).due
    gaps = np.diff(due)
    assert abs(gaps.mean() * POISSON["rate_qps"] - 1.0) < 0.02
    # Exponential gaps: the standard deviation equals the mean.
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.03
    assert due[-1] < 60.0 and due[0] >= 0.0


def test_zipf_draws_skew_to_a_seeded_head():
    picks = loadgen.plan(POISSON, SEED, 1.0).picks(200_000)
    counts = np.bincount(picks, minlength=4096)
    top = np.sort(counts)[::-1]
    # Rank 1 against rank 10 of Zipf(1.1): 10^1.1 = 12.6.
    assert 9 < top[0] / top[9] < 17
    other = np.bincount(loadgen.plan(POISSON, SEED + 7, 1.0).picks(200_000), minlength=4096)
    assert np.argmax(counts) != np.argmax(other)


def test_uniform_draws_cover_the_pool():
    picks = loadgen.plan(BULK, SEED, 1.0).picks(256 * 200)
    assert picks.min() >= 0 and picks.max() < 4096
    assert len(np.unique(picks)) > 4000
