"""Percentile, rate and lateness arithmetic on hand-made samples."""
import pytest

from benchmark.lib import stats


def test_percentile_interpolates_like_numpy():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([], 95) is None
    assert stats.percentile([7], 95) == 7


def test_samples_beyond_p95():
    assert stats.samples_beyond(200, 95) == 10
    assert stats.samples_beyond(100, 99) == 1


def test_rate_latency_lateness():
    assert stats.rate(900, 45.0) == 20.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    assert stats.ttft_ms(10.0, 10.25) == pytest.approx(250.0)
    assert stats.tpot_ms(1.0, 2.0, 11) == pytest.approx(100.0)
    assert stats.tpot_ms(1.0, 2.0, 1) is None
    assert stats.lateness_ms(5.0, 5.002) == pytest.approx(2.0)
    assert stats.lateness_ms(5.0, 4.9) == 0.0
