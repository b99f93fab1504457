import numpy as np
import pytest

from bench.metrics._stats import percentile


def test_percentile_is_nearest_rank_over_every_request():
    v = list(range(1, 101))                  # 1..100
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile([7.0], 95) == 7.0
    # 20 requests: the 19th smallest is the 95th percentile
    assert percentile(np.arange(20.0), 95) == 18.0


def test_failed_requests_count_as_late():
    lat = [10.0] * 95 + [np.inf] * 5
    assert percentile(lat, 95) == 10.0
    assert percentile(lat + [np.inf], 95) == np.inf


def test_percentile_needs_values():
    with pytest.raises(ValueError):
        percentile([], 95)
