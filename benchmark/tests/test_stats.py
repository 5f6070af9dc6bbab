from __future__ import annotations

import numpy as np
import pytest

from benchmark.lib import stats


@pytest.mark.parametrize("q", [0, 5, 25, 50, 75, 95, 99, 100])
def test_percentile_is_numpys_linear_interpolation(q):
    xs = np.random.default_rng(0).exponential(30.0, size=601).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_small_samples():
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([1.0, 3.0], 50) == 2.0
    assert stats.median([3, 1, 2]) == 2.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_a_tail_needs_ten_samples_beyond_it():
    # 600 queries leave 30 beyond the 95th percentile and 6 beyond the 99th
    assert stats.samples_beyond(600, 95) == 30
    assert stats.samples_beyond(600, 99) == 6


def test_spread_is_interquartile_distance_over_median():
    assert stats.spread([98, 99, 100, 101, 102]) == pytest.approx(0.02)
    with pytest.raises(ValueError):
        stats.spread([-1, 0, 1])
