import numpy as np
import pytest

from harness.stats import beyond, iqr_share, percentile


@pytest.mark.parametrize("p", [0, 10, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(p):
    xs = np.random.default_rng(3).lognormal(size=257)
    assert percentile(list(xs), p) == pytest.approx(np.percentile(xs, p))


def test_percentile_of_nothing_is_absent():
    assert percentile([], 90) is None


def test_percentile_small_samples():
    assert percentile([5.0], 95) == 5.0
    assert percentile([1.0, 3.0], 50) == 2.0


def test_beyond_counts_the_tail():
    assert beyond(100, 90) == 10
    assert beyond(1000, 99) == 10


def test_iqr_share_uses_statistics_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles(n=4), method 'exclusive': 10.75 and 14.25
    assert iqr_share(vals) == pytest.approx((14.25 - 10.75) / 12.5)
