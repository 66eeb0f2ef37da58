"""Percentile and window-rate arithmetic of the end-to-end metrics."""

import math

import numpy as np
import pytest

import winstats


@pytest.mark.parametrize("q, want", [(0.5, 50.0), (0.99, 99.0),
                                     (1.0, 100.0), (0.001, 1.0)])
def test_nearest_rank_quantile(q, want):
    assert winstats.quantile(np.arange(100, 0, -1.0), q) == want


def test_quantile_counts_missing_acks_as_infinite():
    lat = [1.0] * 98 + [math.inf, math.inf]
    assert winstats.quantile(lat, 0.98) == 1.0
    assert winstats.quantile(lat, 0.99) == math.inf


def test_quantile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        winstats.quantile([], 0.5)


def test_window_rate_is_half_open():
    t = [0.9, 1.0, 1.5, 2.99, 3.0, math.inf]
    assert winstats.window_rate(t, 1.0, 3.0) == 1.5
