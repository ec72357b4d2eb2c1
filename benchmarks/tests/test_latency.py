import numpy as np

from benchmarks.harness import latency


def test_failed_counts_as_miss():
    due = np.array([0.0, 0.0, 0.0, 0.0])
    emitted = np.array([0.010, 0.020, np.nan, 0.005])
    failed = np.array([False, False, False, True])
    lat = latency.latencies_ms(due, emitted, failed, miss_ms=9000.0)
    # never emitted -> miss; emitted but marked failed -> miss all the same
    assert lat.tolist() == [10.0, 20.0, 9000.0, 9000.0]
    assert latency.share_over(lat, 20.0) == 50.0
    assert latency.share_over(lat, 5.0) == 100.0


def test_percentile_needs_ten_samples_beyond():
    x = np.arange(999, dtype=float)
    assert latency.percentile(x, 0.99) is None          # 9.99 beyond
    assert latency.percentile(np.arange(1000.0), 0.99) is not None
    assert latency.percentile(np.arange(199.0), 0.95) is None
    assert latency.percentile(np.arange(200.0), 0.95) is not None
    # the median needs no tail
    assert latency.percentile(np.array([1.0, 3.0]), 0.5) == 2.0
    assert latency.percentile(np.array([]), 0.5) is None


def test_percentile_is_linear_interpolation():
    x = np.arange(1001, dtype=float)
    assert latency.percentile(x, 0.99) == 990.0
    assert latency.percentile(x, 0.5) == 500.0


def test_a_failure_moves_the_tail_not_the_median():
    ok = latency.latencies_ms(np.zeros(2000), np.full(2000, 0.01),
                              np.zeros(2000, bool), 5000.0)
    failed = np.zeros(2000, bool)
    failed[:40] = True                                    # 2% fail
    bad = latency.latencies_ms(np.zeros(2000), np.full(2000, 0.01), failed,
                               5000.0)
    assert latency.percentile(ok, 0.99) == 10.0
    assert latency.percentile(bad, 0.99) == 5000.0
    assert latency.percentile(bad, 0.5) == 10.0
