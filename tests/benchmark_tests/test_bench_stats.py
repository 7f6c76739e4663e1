"""The benchmark's sample arithmetic (benchmark/harness/stats.py)."""

import pytest

from benchmark.harness import stats


@pytest.mark.parametrize("samples, want", [
    ([3.0], 3.0),
    ([1.0, 3.0], 2.0),
    ([5.0, 1.0, 3.0], 3.0),
    ([4.0, 1.0, 3.0, 2.0], 2.5),
    (list(range(101)), 50.0),
])
def test_median(samples, want):
    assert stats.median(samples) == want


@pytest.mark.parametrize("q, want", [(0.0, 0.0), (0.25, 2.5), (0.5, 5.0), (0.95, 9.5), (1.0, 10.0)])
def test_quantile_interpolates_between_closest_ranks(q, want):
    assert stats.quantile(list(range(11)), q) == pytest.approx(want)


def test_quantile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.quantile([], 0.5)


def test_quartiles_and_spread():
    samples = [10.0, 12.0, 11.0, 13.0, 9.0]
    assert stats.quartiles(samples) == (10.0, 11.0, 12.0)
    assert stats.spread(samples) == pytest.approx(2.0 / 11.0)


@pytest.mark.parametrize("n, want", [
    (5, None),  # not even the median has ten beyond it
    (19, None), (20, 50.0), (99, 50.0), (100, 90.0), (199, 90.0),
    (200, 95.0), (999, 95.0), (1000, 99.0), (10000, 99.9),
])
def test_highest_percentile_with_ten_samples_beyond_it(n, want):
    assert stats.highest_percentile(n) == want


@pytest.mark.parametrize("n, reported", [(15, False), (199, False), (200, True), (450, True)])
def test_a_window_with_too_few_requests_reports_no_p95(n, reported):
    samples = [float(i) for i in range(n)]
    p95 = stats.tail(samples, 95.0)
    assert (p95 is not None) == reported
    if reported:
        assert p95 == pytest.approx(0.95 * (n - 1))
