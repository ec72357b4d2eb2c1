"""Readers on hand-made ``Run``-like records with known answers."""

import types

import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.harness.runner import memory_peak_bytes


def batches(n, period, rows=256, t0=100.0):
    return np.concatenate([t0 + i * period + np.arange(rows) * 2e-6
                           for i in range(n)])


def fake_run(**kw):
    run = types.SimpleNamespace(**kw)
    run.in_window = lambda: (run.emitted >= run.t_open) & (
        run.emitted < run.t_count_end)
    return run


def test_txn_per_s_does_not_step_with_the_window_edges():
    read = spec.reader_for("txn_per_s", "end_to_end")
    ts = batches(120, 0.19)
    rates = []
    for edge in (100.0, 100.07, 100.15):        # same stream, moved window
        run = fake_run(emitted=ts, t_open=edge, t_count_end=edge + 20.0)
        rates.append(read(run))
    assert rates == pytest.approx([256 / 0.19] * 3, rel=1e-4)
    # the plain count over the same windows moves by a whole batch
    counts = {int(((ts >= e) & (ts < e + 20.0)).sum()) for e in
              (100.0, 100.07, 100.15)}
    assert len(counts) > 1


def test_nothing_to_read_gives_none():
    read = spec.reader_for("txn_per_s", "end_to_end")
    run = fake_run(emitted=np.array([np.nan, 5.0]), t_open=0.0,
                   t_count_end=10.0)
    assert read(run) is None
    imbalance = spec.reader_for("replica_imbalance_pct", "per_layer")
    assert imbalance(types.SimpleNamespace(pool_completed=None)) is None
    assert imbalance(types.SimpleNamespace(
        pool_completed=[10, 10, 12, 8])) == pytest.approx(40.0)


def test_span_and_completion_time_per_txn():
    run = types.SimpleNamespace(
        counters={"scored": 2560, "batches": 10},
        stages={"assemble": {"total_s": 0.10}, "pack": {"total_s": 0.02},
                "dispatch": {"total_s": 0.03},
                "device_wait": {"total_s": 0.5}},
        bench_spans={"job.complete_batch": [10, 0.6]})
    assert spec.reader_for("assemble_us_per_txn", "per_layer")(run) \
        == pytest.approx(1e6 * 0.15 / 2560)
    assert spec.reader_for("complete_us_per_txn", "per_layer")(run) \
        == pytest.approx(1e6 * 0.1 / 2560)
    assert spec.reader_for("batch_rows_mean", "per_layer")(run) == 256.0
    run.bench_spans = {}
    assert spec.reader_for("complete_us_per_txn", "per_layer")(run) is None


def test_device_metrics_from_a_trace_summary():
    run = types.SimpleNamespace(
        trace={"per_device": {"/device:TPU:0": 2.4}, "idle_share": 0.2},
        counters_slice={"batches": 100, "scored": 25600},
        extra={"cfg": {"job": {"max_batch": 256}}, "flops_per_batch": 1e12,
               "device": {"kind": "TPU v5 lite"}})
    assert spec.reader_for("device_ms_per_batch", "per_layer")(run) \
        == pytest.approx(24.0)
    assert spec.reader_for("device_idle_pct", "per_layer")(run) \
        == pytest.approx(20.0)
    assert spec.reader_for("matmul_util_pct", "per_layer")(run) \
        == pytest.approx(100 * 1e12 * 100 / 2.4 / 197e12)
    run.counters_slice["scored"] = 20000      # batches not full: no claim
    assert spec.reader_for("matmul_util_pct", "per_layer")(run) is None
    run.extra["device"]["kind"] = "TPU v9"
    run.counters_slice["scored"] = 25600
    with pytest.raises(ValueError, match="no published"):
        spec.reader_for("matmul_util_pct", "per_layer")(run)


def test_memory_peak_is_in_use_plus_reserved():
    # the v5e's own numbers at 512 tokens (my chip run, PR 22)
    stats = {"peak_bytes_in_use": 326300672,
             "peak_bytes_reserved": 4233166848,
             "bytes_limit": 16909336064,
             "largest_free_block_bytes": 12349206528}
    assert memory_peak_bytes(stats) == 4559467520
    assert stats["bytes_limit"] - stats["largest_free_block_bytes"] \
        == pytest.approx(memory_peak_bytes(stats), rel=2e-3)
    assert memory_peak_bytes({}) == 0


def test_collector_clock_times_collections_and_the_reader_takes_the_share():
    """The ``gc.callbacks`` clock sees a forced collection of a large
    cyclic heap; the reader divides by the counted part of the window and
    returns nothing where no clock ran."""
    import gc

    from benchmarks.harness import load

    clock = load.GcClock()
    try:
        junk = []
        for _ in range(20000):
            a, b = [], []
            a.append(b)
            b.append(a)
            junk.append(a)
        del junk
        gc.collect()
        seen = clock.read()
    finally:
        clock.close()
    assert seen["runs"][2] >= 1 and 0 < seen["longest_s"] <= seen["seconds"]
    gc.collect()
    assert clock.read() == seen             # closed: no longer counting
    for name in ("gc_pause_pct", "steady_gc_pause_pct"):
        read = spec.reader_for(name, "per_layer")
        run = types.SimpleNamespace(counted_s=20.0, extra={
            "collector": {"seconds": 3.0, "longest_s": 0.5, "runs": [9, 2, 1]}})
        assert read(run) == pytest.approx(15.0)
        assert read(types.SimpleNamespace(counted_s=20.0, extra={})) is None


def test_collector_clock_keeps_end_and_seconds_of_full_collections_only():
    import gc
    import time

    from benchmarks.harness import load

    clock = load.GcClock()
    try:
        t0 = time.time()
        gc.collect(0)
        gc.collect(2)
        gc.collect(1)
        gc.collect(2)
        t1 = time.time()
        seen = clock.read()
    finally:
        clock.close()
    assert seen["runs"][2] == len(seen["full"]) >= 2
    (e1, s1), (e2, s2) = seen["full"][-2:]
    assert t0 <= e1 <= e2 <= t1 and 0 < s1 <= seen["longest_s"]
    assert s1 + s2 < seen["seconds"]        # the young ones are timed too
    clock.reset()
    assert clock.read()["full"] == []
