import math

import pytest
from harness import stats


def _records(latencies, start=100.0, gap=0.0):
    """One client's back-to-back requests with the given latencies."""
    out, t = [], start
    for lat in latencies:
        out.append({"send": t, "recv": t + lat})
        t += lat + gap
    return out


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 50) == 50
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_counts_only_correct_answers_inside_the_window():
    recs = _records([1.0] * 10)             # replies at 101 .. 110
    ok = [True] * 10
    assert stats.completed_per_s(recs, ok, 100.0, 10.0) == 1.0
    assert stats.completed_per_s(recs, ok, 100.0, 5.0) == 1.0
    ok[3] = False                           # a wrong answer is not completed
    assert stats.completed_per_s(recs, ok, 100.0, 10.0) == 0.9
    late = _records([1.0] * 9 + [2.5])      # the last reply after the close
    assert stats.completed_per_s(late, [True] * 10, 100.0, 10.0) == 0.9


def test_a_stall_in_the_window_moves_qps_and_p95():
    steady = _records([0.1] * 100)
    stalled = _records([0.1] * 40 + [3.0] * 6 + [0.1] * 25)
    ok_s, ok_t = [True] * len(steady), [True] * len(stalled)
    qps_s = stats.completed_per_s(steady, ok_s, 100.0, 10.0)
    qps_t = stats.completed_per_s(stalled, ok_t, 100.0, 10.0)
    assert qps_t < 0.6 * qps_s
    p95_s = stats.percentile(stats.latencies_ms(steady, ok_s, 1e6), 95)
    p95_t = stats.percentile(stats.latencies_ms(stalled, ok_t, 1e6), 95)
    assert p95_s == pytest.approx(100.0) and p95_t == pytest.approx(3000.0)
    # the median does not see it: that is why qps and p95 stand beside it
    assert stats.percentile(stats.latencies_ms(stalled, ok_t, 1e6), 50) \
        == pytest.approx(100.0)


def test_failures_are_worse_than_any_latency():
    recs = _records([0.1] * 20)
    ok = [True] * 18 + [False] * 2
    lat = stats.latencies_ms(recs, ok, worst_ms=160000.0)
    assert stats.percentile(lat, 95) == 160000.0
    assert stats.percentile(lat, 50) == pytest.approx(100.0)


def test_spread_is_the_contracts():
    values = [10.0, 10.1, 9.9, 10.2, 9.8, 10.0]
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert math.isclose(stats.spread(values), (q3 - q1) / 10.0)
