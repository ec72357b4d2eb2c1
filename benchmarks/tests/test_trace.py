"""The reduction from trace events to busy / idle / gaps / top ops, on a
hand-made list with known answers and on the recorded fixture
(``fixtures/trace_events.json``: the first 40 ms of a traced slice of
``s64-saturated`` on the v5e (PR 22), as ``read_xplane`` flattened it,
times made relative to the slice's start)."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "trace_events.json"
D, H = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start_us, dur_us):
    return (plane, line, name, start_us * 1e3, dur_us * 1e3)


def hand_made():
    return [
        ev(H, "main", "bench:slice", 0, 1000),
        ev(H, "main", "bench:job.dispatch_batch", 0, 300),
        ev(H, "main", "bench:scorer.assemble", 10, 200),
        ev(H, "main", "bench:job.complete_batch", 400, 250),
        ev(D, "XLA Ops", "fusion.1", 100, 100),
        ev(D, "XLA Ops", "fusion.2", 150, 100),     # overlaps fusion.1
        ev(D, "XLA Ops", "fusion.1", 255, 45),      # 5 us after fusion.2
        ev(D, "XLA Ops", "copy.3", 700, 100),
        ev(D, "XLA Modules", "jit_score", 100, 200),    # not an op line
        ev(D, "XLA Ops", "fusion.9", 2000, 50),     # outside the window
    ]


def test_busy_is_the_union_and_the_window_edges_count_as_idle():
    r = T.reduce(hand_made())
    assert r["window_s"] == pytest.approx(1000e-6)
    # [100,250] + [255,300] + [700,800] = 150 + 45 + 100
    assert r["busy_s"] == pytest.approx(295e-6)
    assert r["idle_share"] == pytest.approx(1 - 0.295)
    assert r["per_device"] == {D: pytest.approx(295e-6)}


def test_top_ops_sum_by_name_inside_the_window():
    ops = dict(T.reduce(hand_made())["device_ops"])
    assert ops == {"fusion.1": pytest.approx(145e-6),
                   "fusion.2": pytest.approx(100e-6),
                   "copy.3": pytest.approx(100e-6)}


def test_gaps_go_to_the_innermost_annotation_covering_them():
    gaps = dict(T.reduce(hand_made())["idle_gaps"])
    # [0,100]: dispatch_batch covers all of it, assemble 90 of 100 and is
    # the shorter span -> assemble. [250,255]: under 20 us. [300,700]:
    # complete_batch covers 250 of 400 -> it. [800,1000]: nothing.
    assert gaps == {
        "scorer.assemble": pytest.approx(100e-6),
        T.BETWEEN_OPS: pytest.approx(5e-6),
        "job.complete_batch": pytest.approx(400e-6),
        T.UNCOVERED: pytest.approx(200e-6),
    }
    assert sum(gaps.values()) + 295e-6 == pytest.approx(1000e-6)


def test_several_devices_mean_busy_and_summed_ops():
    events = hand_made() + [ev("/device:TPU:1", "XLA Ops", "fusion.1",
                               0, 1000)]
    r = T.reduce(events)
    assert r["busy_s"] == pytest.approx((295e-6 + 1000e-6) / 2)
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(1145e-6)


def test_a_trace_without_a_device_or_a_window_is_refused():
    with pytest.raises(ValueError, match="no device plane"):
        T.reduce([e for e in hand_made() if e[0] != D])
    with pytest.raises(ValueError, match="bench:slice"):
        T.reduce([e for e in hand_made() if e[2] != "bench:slice"])


def test_recorded_fixture():
    """One device period of the chip's trace: the union against an
    independent raster of the same intervals, the books against the
    window, and the names the chip really gives its spans and ops."""
    import numpy as np

    assert FIXTURE.stat().st_size < 200 * 1024
    events = [tuple(e) for e in json.loads(FIXTURE.read_text())]
    r = T.reduce(events, top=10 ** 6)
    assert r["window_s"] == pytest.approx(0.04)
    assert list(r["per_device"]) == ["/device:TPU:0"]
    grid = np.zeros(int(40e6 / 10), bool)           # 10 ns cells
    for plane, line, _, start, dur in events:
        if plane.startswith("/device:") and line == T.OPS_LINE:
            grid[int(round(start / 10)):int(round((start + dur) / 10))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 10 / 1e9, rel=1e-4)
    assert r["busy_s"] == pytest.approx(0.019715517, rel=1e-6)
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] \
        == pytest.approx(r["window_s"])
    # the device waited while the host assembled the next batch
    assert r["idle_gaps"][0][0] == "scorer.assemble"
    assert set(r["annotations"]) == {"job.dispatch_batch", "scorer.assemble",
                                     "scorer.dispatch_assembled"}
    top = r["device_ops"][0][0]
    assert top.startswith("%fusion.") and "ffn1" in top and len(top) <= 160
    # XLA Modules events are whole programs, not operations: not counted
    modules = sum(d for p, l, _, _, d in events if l == "XLA Modules") / 1e9
    assert modules > r["busy_s"]
