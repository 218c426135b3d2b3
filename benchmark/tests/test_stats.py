"""Percentile, trimmed mean and spread on hand-made samples."""
import pytest

import metrics
import stats
from client import Result
from traffic import Request


def test_percentile_interpolates_and_carries_failures():
    xs = [10, 20, 30, 40, 50, 60, 70, 80, 90, 100, 110]
    assert stats.percentile(xs, 50) == 60
    assert stats.percentile(xs, 90) == 100
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([], 90) is None
    assert stats.percentile([1.0] * 8 + [stats.INF] * 2, 90) == stats.INF  # failures reach the tail
    assert stats.percentile([1.0] * 19 + [stats.INF], 90) == 1.0


def test_trimmed_mean_drops_a_tenth_at_each_end():
    xs = list(range(1, 21))  # drops 1, 2 and 19, 20
    assert stats.trimmed_mean(xs, 0.1) == sum(range(3, 19)) / 16
    assert stats.trimmed_mean([5.0], 0.1) == 5.0
    assert stats.trimmed_mean([1.0] * 5 + [stats.INF] * 5, 0.1) == stats.INF


def test_spread_is_the_contracts():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q[2] - q[0]) / statistics.median(vals))


def _res(due, sent, first, last, n, max_new=16, error=""):
    return Result(Request(0, "window", 100, max_new, 0.0), due=due, sent=sent,
                  first=first, last=last, tokens=list(range(n)), error=error)


def test_ttft_is_timed_from_due_and_tpot_needs_eight_tokens():
    rs = [_res(1.0, 1.2, 1.5, 2.5, 11),          # sent late: still timed from due
          _res(2.0, 2.0, 2.1, 2.2, 3),           # short: TTFT counts, TPOT left out
          _res(3.0, 3.0, None, None, 0, error="HTTP 500")]
    assert metrics.ttft_ms(rs) == [pytest.approx(500.0), pytest.approx(100.0), stats.INF]
    assert metrics.tpot_ms(rs) == [pytest.approx(100.0), stats.INF]


def test_the_judged_metrics_are_the_trimmed_means_of_all_window_requests():
    rs = [_res(0.0, 0.0, 0.1 * (i + 1), 0.1 * (i + 1) + 1.0, 11) for i in range(20)]
    obs = metrics.Obs(samples=rs, setup_s=40.0)
    # TTFT 100 .. 2000 ms: the two smallest and the two largest are dropped
    assert metrics.END_TO_END["ttft_mid80_ms"](obs) == pytest.approx(1050.0)
    assert metrics.END_TO_END["tpot_mid80_ms"](obs) == pytest.approx(100.0)
    assert metrics.END_TO_END["setup_s"](obs) == 40.0
    rs[3] = _res(0.0, 0.0, None, None, 0, error="HTTP 503")   # one failure is trimmed away
    assert metrics.finite(metrics.END_TO_END["ttft_mid80_ms"](obs))
    for i in (4, 5):                                          # three are not
        rs[i] = _res(0.0, 0.0, None, None, 0, error="HTTP 503")
    assert metrics.END_TO_END["ttft_mid80_ms"](obs) == stats.INF
