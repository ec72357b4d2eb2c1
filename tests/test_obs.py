"""Observability plane: metrics registry, drift monitor, logs, span timer,
and the per-transaction tracing plane (flight recorder, critical-path
analyzer, SLO burn rate, Prometheus mirror, overhead guard)."""

import json
import logging

import numpy as np
import pytest

from realtime_fraud_detection_tpu.obs import (
    DriftConfig,
    FeatureDriftMonitor,
    JsonFormatter,
    MetricsCollector,
    Registry,
    SloTracker,
    SpanTimer,
    Tracer,
    log_prediction_result,
)
from realtime_fraud_detection_tpu.utils.config import TracingSettings


def _vclock_tracer(clock, **kw):
    defaults = dict(enabled=True, ring_size=256, slowest_n=4,
                    slo_objective_ms=20.0, slo_fast_window_s=1.0,
                    slo_slow_window_s=4.0, slo_bucket_s=0.05)
    defaults.update(kw)
    return Tracer(TracingSettings(**defaults), clock=lambda: clock[0])


class TestRegistry:
    def test_counter_labels_and_total(self):
        r = Registry()
        c = r.counter("preds_total", "predictions", ("model", "decision"))
        c.inc(model="xgb", decision="APPROVE")
        c.inc(2, model="xgb", decision="DECLINE")
        assert c.value(model="xgb", decision="APPROVE") == 1
        assert c.total() == 3

    def test_counter_rejects_negative(self):
        c = Registry().counter("c", "h")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = Registry().gauge("g", "h")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_histogram_buckets_and_quantile(self):
        h = Registry().histogram("h", "lat", buckets=(0.01, 0.1, 1.0))
        for v in [0.005] * 98 + [0.5, 0.5]:
            h.observe(v)
        assert h.count() == 100
        assert h.quantile(0.5) == 0.01
        assert h.quantile(0.99) == pytest.approx(1.0)

    def test_prometheus_text_format(self):
        r = Registry()
        c = r.counter("x_total", "things", ("k",))
        c.inc(k="v")
        h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        text = r.render()
        assert "# TYPE x_total counter" in text
        assert 'x_total{k="v"} 1' in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_count 1" in text

    def test_non_finite_observation_dropped(self):
        h = Registry().histogram("h", "lat", buckets=(0.1, 1.0))
        h.observe(float("nan"))
        h.observe(float("inf"))
        h.observe(0.05)
        assert h.count() == 1
        assert h.sum() == pytest.approx(0.05)
        sum_line = [ln for ln in h.render() if "_sum" in ln][0]
        assert "nan" not in sum_line and "inf" not in sum_line.lower()

    def test_quantile_in_overflow_bucket_reports_max(self):
        h = Registry().histogram("h", "lat", buckets=(0.1, 1.0))
        for _ in range(10):
            h.observe(60.0)
        assert h.quantile(0.99) == pytest.approx(60.0)

    def test_label_values_escaped(self):
        c = Registry().counter("c_total", "h", ("k",))
        c.inc(k='say "hi"\nnewline\\slash')
        line = [ln for ln in c.render() if ln.startswith("c_total{")][0]
        assert '\\"hi\\"' in line and "\\n" in line and "\\\\" in line
        assert "\n" not in line

    def test_duplicate_name_rejected(self):
        r = Registry()
        r.counter("dup", "h")
        with pytest.raises(ValueError):
            r.gauge("dup", "h")


class TestMetricsCollector:
    def test_record_and_summary(self):
        t = [0.0]
        m = MetricsCollector(clock=lambda: t[0])
        for i in range(10):
            t[0] = float(i)
            m.record_prediction(
                "APPROVE" if i < 8 else "DECLINE",
                fraud_score=0.1 * i, duration_s=0.004,
                model_predictions={"xgboost_primary": 0.2},
            )
        s = m.summary()
        assert s["total_predictions"] == 10
        assert s["decision_counts"] == {"APPROVE": 8, "DECLINE": 2}
        assert s["throughput_tps_60s"] == pytest.approx(10 / 60.0)
        assert s["latency_ms"]["p99"] <= 5.0 + 1e-9
        assert m.predictions_total.value(
            model="xgboost_primary", decision="APPROVE") == 8

    def test_prometheus_render_includes_domain_metrics(self):
        m = MetricsCollector()
        m.record_prediction("REVIEW", 0.9, 0.002)
        m.record_error("assemble")
        text = m.render_prometheus()
        assert 'ml_predictions_total{decision="REVIEW",model="ensemble"} 1' in text
        assert 'ml_prediction_errors_total{stage="assemble"} 1' in text

    def test_throughput_not_capped_by_latency_window(self):
        t = [0.0]
        m = MetricsCollector(window=100, clock=lambda: t[0])
        for i in range(1000):           # 1000 events in 10 "seconds"
            t[0] = i / 100.0
            m.record_prediction("APPROVE", 0.1, 0.001)
        s = m.summary()
        assert s["throughput_tps_60s"] == pytest.approx(1000 / 60.0)
        assert s["recent_predictions"] == 100   # latency window stays capped

    def test_batch_duration_recorded(self):
        m = MetricsCollector()
        m.record_batch(32, 0.008)
        assert m.batch_duration.count() == 1
        assert m.batch_duration.sum() == pytest.approx(0.008)

    def test_reset_clears_window_not_counters(self):
        m = MetricsCollector()
        m.record_prediction("APPROVE", 0.1, 0.001)
        m.reset()
        s = m.summary()
        assert s["recent_predictions"] == 0
        assert s["throughput_tps_60s"] == 0.0
        assert m.predictions_total.total() > 0


class TestDrift:
    def _warm(self, mon, rng, rows, loc=0.0, scale=1.0):
        mon.update(rng.normal(loc, scale, size=(rows, 8)))

    def test_no_drift_on_same_distribution(self):
        rng = np.random.default_rng(0)
        mon = FeatureDriftMonitor(DriftConfig(num_features=8,
                                              warmup_rows=1000,
                                              window_rows=1000))
        self._warm(mon, rng, 1200)
        assert mon.baseline_frozen
        self._warm(mon, rng, 1000)
        rep = mon.report()
        assert not rep.drifted
        assert rep.max_psi < 0.1

    def test_detects_mean_shift(self):
        rng = np.random.default_rng(1)
        mon = FeatureDriftMonitor(DriftConfig(num_features=8,
                                              warmup_rows=1000,
                                              window_rows=1000))
        self._warm(mon, rng, 1200)
        shifted = rng.normal(0, 1, size=(1000, 8))
        shifted[:, 3] += 3.0                       # feature 3 drifts hard
        mon.update(shifted)
        rep = mon.report()
        assert rep.drifted
        assert 3 in rep.top_features
        assert rep.psi[3] > 0.25
        assert rep.psi[0] < 0.25

    def test_report_before_freeze_is_quiet(self):
        mon = FeatureDriftMonitor(DriftConfig(num_features=4, warmup_rows=100))
        mon.update(np.zeros((10, 4)))
        rep = mon.report()
        assert not rep.drifted and not rep.baseline_frozen

    def test_shape_validation(self):
        mon = FeatureDriftMonitor(DriftConfig(num_features=4))
        with pytest.raises(ValueError):
            mon.update(np.zeros((10, 5)))

    def test_tiny_window_does_not_false_alarm(self):
        rng = np.random.default_rng(2)
        mon = FeatureDriftMonitor(DriftConfig(num_features=8,
                                              warmup_rows=500,
                                              window_rows=500,
                                              min_report_rows=200))
        mon.update(rng.normal(size=(600, 8)))
        mon.update(rng.normal(size=(1, 8)))       # near-empty window
        rep = mon.report()
        assert not rep.drifted and rep.max_psi == 0.0


class TestLogs:
    def test_json_formatter_fields(self):
        rec = logging.LogRecord("t", logging.INFO, __file__, 1, "hello",
                                (), None)
        rec.transaction_id = "tx1"
        out = json.loads(JsonFormatter().format(rec))
        assert out["message"] == "hello"
        assert out["transaction_id"] == "tx1"
        assert out["level"] == "INFO"

    def test_log_prediction_result_structured(self, caplog):
        logger = logging.getLogger("test.pred")
        with caplog.at_level(logging.INFO, logger="test.pred"):
            log_prediction_result(logger, "tx9", 0.87, "REVIEW", 3.2)
        rec = caplog.records[-1]
        assert rec.transaction_id == "tx9"
        assert rec.decision == "REVIEW"
        assert rec.fraud_score == pytest.approx(0.87)


class TestSpanTimer:
    def test_span_stats(self):
        t = [0.0]
        timer = SpanTimer(clock=lambda: t[0])
        for dt in (0.001, 0.002, 0.010):
            with timer.span("assemble"):
                t[0] += dt
        st = timer.stats("assemble")["assemble"]
        assert st["count"] == 3
        assert st["max_ms"] == pytest.approx(10.0)
        assert st["total_s"] == pytest.approx(0.013)
        timer.reset()
        assert timer.stats() == {}

    def test_percentiles_interpolate(self):
        """Satellite: p50/p99 interpolate between order statistics —
        raw index selection made p99 on small n simply the max."""
        t = [0.0]
        timer = SpanTimer(clock=lambda: t[0])
        for ms in range(1, 101):            # 1..100 ms
            with timer.span("s"):
                t[0] += ms / 1e3
        st = timer.stats("s")["s"]
        assert st["p50_ms"] == pytest.approx(50.5)       # numpy default
        assert st["p99_ms"] == pytest.approx(99.01)
        assert st["p99_ms"] < st["max_ms"]               # not just the max
        np.testing.assert_allclose(
            [st["p50_ms"], st["p99_ms"]],
            np.percentile(np.arange(1.0, 101.0), [50, 99]))

    def test_small_n_p99_not_max(self):
        t = [0.0]
        timer = SpanTimer(clock=lambda: t[0])
        for ms in (1.0, 2.0, 100.0):
            with timer.span("s"):
                t[0] += ms / 1e3
        st = timer.stats("s")["s"]
        assert st["p99_ms"] < 100.0
        assert st["p99_ms"] == pytest.approx(
            np.percentile([1.0, 2.0, 100.0], 99))


    # ---- the one span primitive (ISSUE 23) -------------------------------
    def test_spans_nest_and_report_parent_and_self_time(self):
        t = [0.0]
        timer = SpanTimer(clock=lambda: t[0], annotation=_RecordingAnnotation)
        for _ in range(3):
            with timer.span("job.dispatch_batch"):
                t[0] += 0.001                    # own work
                with timer.span("assemble"):
                    t[0] += 0.002
                    with timer.span("graph"):
                        t[0] += 0.004
                    with timer.span("assemble.tokenize"):
                        t[0] += 0.008
                with timer.span("pack"):
                    t[0] += 0.016
        st = timer.stats()
        assert {n: s["parent"] for n, s in st.items()} == {
            "job.dispatch_batch": "", "assemble": "job.dispatch_batch",
            "graph": "assemble", "assemble.tokenize": "assemble",
            "pack": "job.dispatch_batch"}
        assert st["job.dispatch_batch"]["total_s"] == pytest.approx(0.093)
        assert st["job.dispatch_batch"]["self_s"] == pytest.approx(0.003)
        assert st["assemble"]["total_s"] == pytest.approx(0.042)
        assert st["assemble"]["self_s"] == pytest.approx(0.006)
        assert st["graph"]["self_s"] == st["graph"]["total_s"] \
            == pytest.approx(0.012)
        # self times partition the root's total
        assert sum(s["self_s"] for s in st.values()) == pytest.approx(
            st["job.dispatch_batch"]["total_s"])
        assert all(s["count"] == 3 for s in st.values())

    def test_running_totals_are_exact_past_the_sample_cap(self):
        """count / total_s are running totals; only the percentiles are
        over the newest ``max_samples`` (total_s used to be their sum)."""
        t = [0.0]
        timer = SpanTimer(clock=lambda: t[0], max_samples=100,
                          annotation=_RecordingAnnotation)
        for _ in range(10_050):
            with timer.span("s"):
                t[0] += 0.001
        for _ in range(50):
            with timer.span("s"):
                t[0] += 0.003
        st = timer.stats("s")["s"]
        assert st["count"] == 10_100
        assert st["total_s"] == pytest.approx(10_050 * 0.001 + 50 * 0.003)
        assert st["mean_ms"] == pytest.approx(1e3 * st["total_s"] / 10_100)
        assert st["p50_ms"] == pytest.approx(2.0)    # newest 100: 50 + 50
        timer.reset()
        assert timer.stats() == {}

    def test_span_marks_the_trace_once_per_stage_in_order(self):
        """The tracer's batch-granular marks come from the same call: a
        span marks its own name where it opens and ``then`` where it
        closes; a stage a closing span opened is not opened twice."""
        from realtime_fraud_detection_tpu.obs.tracing import TRACE_STAGES

        clock = [0.0]
        tracer = _vclock_tracer(clock)
        timer = SpanTimer(clock=lambda: clock[0],
                          annotation=_RecordingAnnotation)
        tb = tracer.batch([tracer.begin("a")], batch_size=1)
        costs = {"assemble": 3.0, "pack": 0.5, "dispatch": 0.5,
                 "device_wait": 5.0}
        for name, then in (("assemble", None), ("pack", None),
                           ("dispatch", "device_wait")):
            with timer.span(name, trace=tb, then=then):
                clock[0] += costs[name] / 1e3
        clock[0] += 0.004        # pipeline dwell: the tracer's device_wait
        with timer.span("device_wait", trace=tb, then="finalize"):
            clock[0] += 0.001
        with timer.span("finalize.responses"):       # no trace: no mark
            clock[0] += 0.002
        marked = [m for m, _ in tb.marks]
        assert marked == ["assemble", "pack", "dispatch", "device_wait",
                          "finalize"]
        assert marked == [s for s in TRACE_STAGES if s in marked]
        tracer.finish_batch(tb)
        stages = tracer.traces(terminal="scored")[0].stages
        assert stages["device_wait"] == pytest.approx(5.0)   # dwell + wait
        assert stages["finalize"] == pytest.approx(2.0)
        assert timer.stats("device_wait")["device_wait"]["total_s"] \
            == pytest.approx(0.001)                  # the span: the wait

    def test_spans_annotate_with_the_batch_id_of_their_root(self):
        _RecordingAnnotation.seen.clear()
        timer = SpanTimer(annotation=_RecordingAnnotation)
        with timer.span("job.complete_batch", batch=41):
            with timer.span("device_wait", replica=2):
                pass
            with timer.span("job.fan_out"):
                pass
        assert _RecordingAnnotation.seen == [
            ("rtfd:job.complete_batch", {"batch": 41}),
            ("rtfd:device_wait", {"batch": 41, "replica": 2}),
            ("rtfd:job.fan_out", {"batch": 41})]

    def test_spans_are_profiler_annotations_on_the_cpu_backend(
            self, tmp_path):
        """The default annotation is ``jax.profiler.TraceAnnotation``: a
        short profiler session records each span as ``rtfd:<name>`` with
        ``batch=`` among its arguments."""
        import glob

        import jax

        timer = SpanTimer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with timer.span("job.dispatch_batch", batch=7):
                with timer.span("assemble"):
                    jax.numpy.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
        seen = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("rtfd:"):
                        seen[ev.name] = dict(ev.stats)
        assert seen["rtfd:job.dispatch_batch"]["batch"] == 7
        assert seen["rtfd:assemble"]["batch"] == 7

    def test_threads_keep_their_own_stacks_and_totals_merge(self):
        import threading

        timer = SpanTimer(annotation=_RecordingAnnotation)
        barrier = threading.Barrier(8, timeout=30)

        def work():
            barrier.wait()
            for _ in range(2_000):
                with timer.span("outer"):
                    with timer.span("inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
        st = timer.stats()
        assert st["outer"]["count"] == st["inner"]["count"] == 16_000
        assert st["inner"]["parent"] == "outer"
        assert st["outer"]["parent"] == ""

    def test_collections_become_annotations_and_counts(self):
        import gc

        from realtime_fraud_detection_tpu.obs import GcSpans

        _RecordingAnnotation.seen.clear()
        watch = GcSpans(annotation=_RecordingAnnotation)
        before = len(gc.callbacks)
        with watch:
            gc.collect(0)
            gc.collect(2)
        assert len(gc.callbacks) == before           # the hook is gone
        gc.collect()
        assert watch.snapshot()["count"] == 2
        assert watch.snapshot()["seconds"] >= watch.longest_s > 0.0
        assert _RecordingAnnotation.seen == [
            ("rtfd:host.gc", {"generation": 0}),
            ("rtfd:host.gc", {"generation": 2})]


# ---- the compile ledger (ISSUE 36) ------------------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def _fresh_jit(tag, inner=None):
    """A function no test has jitted, named ``ledger_<tag>``."""
    import jax

    def f(x):
        return (inner(x) + inner(x + 1.0) if inner is not None else x) * 3.0

    f.__name__ = f.__qualname__ = f"ledger_{tag}"
    return jax.jit(f)


def _records_of(tag, since=0.0):
    from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

    return [r for r in compile_ledger().records()
            if f"ledger_{tag}" in r["program"] and r["start"] >= since]


def _feed(ledger, event, program, start, end, inside=()):
    """One phase as JAX reports it: the scalar where it opens, whatever
    closes inside it, the time span where it closes."""
    ledger.on_open(event, start, fun_name=program)
    for args in inside:
        _feed(ledger, *args)
    ledger.on_close(event, start, end, fun_name=program)


class TestCompileLedger:
    def test_a_fresh_jit_leaves_a_record_a_phase_under_the_open_span(self):
        import jax.numpy as jnp

        timer = SpanTimer(annotation=_RecordingAnnotation)
        f = _fresh_jit("spanned")
        with timer.span("job.dispatch_batch", batch=7):
            with timer.span("pack"):
                f(jnp.ones((3,)))
        records = _records_of("spanned")
        assert [r["phase"] for r in records] == ["trace", "lower", "compile"]
        assert [r["program"] for r in records] == [
            "ledger_spanned", "jit(ledger_spanned)", "jit(ledger_spanned)"]
        for a, b in zip(records, records[1:]):
            assert a["start"] <= a["end"] <= b["start"] <= b["end"]
        assert {r["caused_by"] for r in records} == {"pack batch=7"}
        # the same shape again runs the compiled program: nothing is told
        before = timer.compile_stats(newest=0)
        f(jnp.ones((3,)))
        assert timer.compile_stats(newest=0) == before
        assert len(_records_of("spanned")) == 3

    def test_outside_any_span_nothing_is_named_as_the_cause(self):
        import jax.numpy as jnp

        timer = SpanTimer(annotation=_RecordingAnnotation)
        with timer.span("pack", batch=3):
            pass                                   # closed: not the cause
        _fresh_jit("bare")(jnp.ones((3,)))
        assert [r["caused_by"] for r in _records_of("bare")] == ["", "", ""]

    def test_a_jit_inside_a_jit_is_counted_under_its_root_and_timed_once(
            self):
        import jax.numpy as jnp

        from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

        inner = _fresh_jit("inner")
        outer = _fresh_jit("outer", inner=inner)
        before = compile_ledger().totals()
        outer(jnp.ones((3,)))
        after = compile_ledger().totals()
        trace, lower, compile_ = _records_of("outer")
        # entered twice inside the root and each time told of (the second
        # answered from the trace cache: its seconds say so)
        assert trace["nested"]["ledger_inner"][0] == 2
        assert 0.0 < trace["nested"]["ledger_inner"][1] \
            <= trace["end"] - trace["start"]
        assert _records_of("inner") == []          # no program of its own
        assert after["compile_n"] - before["compile_n"] == 1
        assert after["trace_n"] - before["trace_n"] == 1
        # the inner traces lie inside the root's interval: counted once
        assert after["trace_s"] - before["trace_s"] == pytest.approx(
            trace["end"] - trace["start"])
        spent = sum(after[p + "_s"] - before[p + "_s"]
                    for p in ("trace", "lower", "compile"))
        assert spent <= compile_["end"] - trace["start"]

    def test_a_compilation_inside_a_trace_keeps_its_record_and_its_seconds(
            self):
        """An eager operation on a constant, met while tracing: a program
        like any other, and its seconds are not the root's too."""
        from realtime_fraud_detection_tpu.obs.profiling import CompileLedger

        ledger = CompileLedger()
        _feed(ledger, _TRACE, "root", 0.0, 10.0, inside=[
            (_TRACE, "kernel", 1.0, 2.0, [(_TRACE, "add", 1.2, 1.4)]),
            (_TRACE, "kernel", 2.0, 2.5),
            (_TRACE, "add", 3.0, 3.1),
            (_LOWER, "jit(add)", 3.1, 3.6),
            (_COMPILE, "jit(add)", 3.6, 5.6)])
        _feed(ledger, _LOWER, "jit(root)", 10.0, 11.0)
        ledger.on_cache("/jax/compilation_cache/cache_misses")   # stale
        ledger.on_open(_COMPILE, 11.0, fun_name="jit(root)")
        ledger.on_cache("/jax/compilation_cache/cache_hits")
        ledger.on_close(_COMPILE, 11.0, 11.5, fun_name="jit(root)")
        assert [(r["program"], r["phase"], r["cache"])
                for r in ledger.records()] == [
            ("jit(add)", "lower", None), ("jit(add)", "compile", None),
            ("root", "trace", None), ("jit(root)", "lower", None),
            ("jit(root)", "compile", "hit")]
        root = ledger.records()[2]
        assert root["nested"] == {"kernel": [2, pytest.approx(1.5)],
                                  "add": [2, pytest.approx(0.3)]}
        totals = ledger.totals()
        assert (totals["trace_n"], totals["lower_n"],
                totals["compile_n"]) == (1, 2, 2)
        assert totals["trace_s"] == pytest.approx(10.0 - 0.5 - 2.0)
        assert totals["lower_s"] == pytest.approx(1.5)
        assert totals["compile_s"] == pytest.approx(2.5)
        assert (totals["hits"], totals["misses"]) == (1, 0)
        # the union of every interval, nothing twice
        assert sum(totals[p + "_s"] for p in ("trace", "lower",
                                              "compile")) == pytest.approx(11.5)

    def test_the_persistent_cache_says_hit_or_miss(self):
        import time

        import jax
        import jax.numpy as jnp

        salt = float(time.time_ns() % 1_000_003)   # this run's own program

        def build():
            def f(x):
                return x * salt

            f.__name__ = f.__qualname__ = "ledger_cached"
            return jax.jit(f)

        def compiles():
            return [r["cache"] for r in _records_of("cached", since=t0)
                    if r["phase"] == "compile"]

        name = "jax_persistent_cache_min_entry_size_bytes"
        size = getattr(jax.config, name)
        jax.config.update(name, -1)        # a program this small is kept
        t0 = time.time()
        try:
            build()(jnp.ones((3,)))
            if compiles() != ["miss"]:
                pytest.skip("this backend's programs are not written to "
                            f"the persistent cache: {compiles()}")
            build()(jnp.ones((3,)))        # the same module, built anew
        finally:
            jax.config.update(name, size)
        assert compiles() == ["miss", "hit"]

    def test_reset_moves_nothing_out_of_the_totals_since_start(self):
        import jax.numpy as jnp

        timer = SpanTimer(annotation=_RecordingAnnotation)
        _fresh_jit("before_reset")(jnp.ones((3,)))
        before = timer.compile_stats()
        assert before["programs"] >= 1
        timer.reset()
        after = timer.compile_stats()
        since = after.pop("since_reset")
        before.pop("since_reset")
        assert after == before
        assert since["programs"] == 0 and since["phases"]["trace"] == {
            "count": 0, "seconds": 0.0}
        _fresh_jit("after_reset")(jnp.ones((3,)))
        now = timer.compile_stats()
        assert now["since_reset"]["programs"] == 1
        assert now["programs"] == before["programs"] + 1
        assert now["records"][-1]["program"] == "jit(ledger_after_reset)"

    def test_fifty_timers_leave_one_listener(self):
        from jax._src import monitoring

        from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

        timers = [SpanTimer(annotation=_RecordingAnnotation)
                  for _ in range(50)]
        ledger = compile_ledger()
        assert len(timers) == 50
        for listeners, ours in (
                (monitoring.get_scalar_listeners(), ledger.on_open),
                (monitoring.get_event_listeners(), ledger.on_cache),
                (monitoring.get_event_time_span_listeners(),
                 ledger.on_close)):
            assert sum(1 for cb in listeners if cb == ours) == 1
            assert sum(1 for cb in listeners if getattr(
                cb, "__self__", None).__class__ is type(ledger)) == 1
        # and none on the one event list a listener of ours is not for
        assert not any(getattr(cb, "__self__", None) is ledger
                       for cb in monitoring.get_event_duration_listeners())

    def test_the_record_cap_keeps_the_totals_exact(self):
        from realtime_fraud_detection_tpu.obs.profiling import CompileLedger

        ledger = CompileLedger(max_records=8)
        for i in range(100):
            _feed(ledger, _COMPILE, f"jit(p{i})", float(i), i + 0.25)
        totals = ledger.totals()
        assert totals["compile_n"] == 100
        assert totals["compile_s"] == pytest.approx(25.0)
        assert totals["dropped"] == 92
        assert [r["program"] for r in ledger.records()] == [
            f"jit(p{i})" for i in range(92, 100)]

    @pytest.mark.parametrize("how", ["reset", "job.complete_batch"])
    def test_a_compilation_under_traffic_is_logged_once_at_warning(
            self, how, caplog):
        import jax.numpy as jnp

        timer = SpanTimer(annotation=_RecordingAnnotation)
        where = "realtime_fraud_detection_tpu.obs.profiling"
        with caplog.at_level(logging.WARNING, logger=where):
            with timer.span("pack", batch=1):          # still warming up
                _fresh_jit(f"warm_{how[:3]}")(jnp.ones((3,)))
            assert not [r for r in caplog.records if r.name == where]
            if how == "reset":
                timer.reset()
            else:
                with timer.span("job.complete_batch", batch=1):
                    pass
            assert timer.under_traffic()
            with timer.span("job.dispatch_batch", batch=2):
                with timer.span("dispatch"):
                    _fresh_jit(f"live_{how[:3]}")(jnp.ones((3,)))
        lines = [r.getMessage() for r in caplog.records if r.name == where]
        assert len(lines) == 1, lines
        assert f"jit(ledger_live_{how[:3]})" in lines[0]
        assert "caused by dispatch batch=2" in lines[0]
        for phase in ("trace", "lower", "compile"):
            assert f"{phase} 0." in lines[0]
        assert "persistent cache" in lines[0]


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: same call shape."""

    seen: list = []

    def __init__(self, name, **ids):
        self.seen.append((name, ids))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


class TestTracer:
    def _scored_batch(self, tracer, clock, txn_ids, stage_costs_ms,
                      ingest_lag_s=0.0):
        """Drive one batch through the mark protocol on a virtual clock."""
        ctxs = [tracer.begin(t, ingest_lag_s=ingest_lag_s)
                for t in txn_ids]
        tb = tracer.batch(ctxs, batch_size=len(txn_ids))
        for stage in ("assemble", "pack", "dispatch", "device_wait",
                      "finalize"):
            tb.mark(stage)
            clock[0] += stage_costs_ms.get(stage, 0.0) / 1e3
        tracer.finish_batch(tb)
        return tb

    def test_stages_additive_and_recorded(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        costs = {"assemble": 3.0, "pack": 0.5, "dispatch": 0.5,
                 "device_wait": 5.0, "finalize": 1.0}
        self._scored_batch(tracer, clock, ["a", "b"], costs,
                           ingest_lag_s=0.002)
        traces = tracer.traces(terminal="scored")
        assert len(traces) == 2
        for t in traces:
            # consecutive-mark stages partition e2e exactly
            assert sum(t.stages.values()) == pytest.approx(t.e2e_ms)
            assert t.stages["ingest"] == pytest.approx(2.0)
            for stage, ms in costs.items():
                assert t.stages[stage] == pytest.approx(ms)
        assert tracer.counters["completed"] == 2

    def test_disabled_is_noop(self):
        tracer = Tracer(TracingSettings(enabled=False))
        assert tracer.begin("x") is None
        assert tracer.batch([None]) is None
        tracer.finish_batch(None)                 # must not raise
        tracer.finish_terminal(None, "shed")
        assert tracer.traces() == []

    def test_shed_terminal_recorded(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        tracer.finish_terminal(tracer.begin("s1"), "shed",
                               reason="no_tokens")
        traces = tracer.traces(terminal="shed")
        assert len(traces) == 1
        assert traces[0].meta["reason"] == "no_tokens"
        assert tracer.counters["shed"] == 1
        # shed traces never pollute the scored attribution or the SLO
        assert tracer.breakdown()["n"] == 0
        assert tracer.slo.observations_total == 0

    def test_slowest_survive_ring_eviction(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock, ring_size=16, slowest_n=2)
        # one slow outlier, then enough fast traces to evict it from the
        # ring — the exemplar store must still hold it verbatim
        self._scored_batch(tracer, clock, ["slow"],
                           {"device_wait": 500.0})
        for i in range(40):
            self._scored_batch(tracer, clock, [f"f{i}"],
                               {"device_wait": 1.0})
        ring_ids = {t.txn_id for t in tracer.traces()}
        assert "slow" not in ring_ids                 # evicted from ring
        slowest = tracer.slowest()
        assert slowest[0].txn_id == "slow"            # kept verbatim
        assert slowest[0].e2e_ms == pytest.approx(500.0)

    def test_breakdown_names_dominant_stage(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        for i in range(20):
            self._scored_batch(tracer, clock, [f"t{i}"],
                               {"assemble": 1.0, "device_wait": 12.0,
                                "finalize": 0.5})
        bd = tracer.breakdown()
        assert bd["n"] == 20
        for q in ("p50", "p95", "p99"):
            assert bd["quantiles"][q]["dominant_stage"] == "device_wait"
            stage_ms = bd["quantiles"][q]["stage_ms"]
            assert sum(stage_ms.values()) == pytest.approx(
                bd["quantiles"][q]["e2e_ms"], rel=0.05)
        assert bd["exemplars"]

    def test_chrome_export_structure(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        self._scored_batch(tracer, clock, ["c1", "c2"],
                           {"assemble": 2.0, "device_wait": 3.0})
        payload = tracer.export_chrome_trace()
        events = payload["traceEvents"]
        assert len(events) == 2 * 6        # 2 txns x 6 recorded stages
        assert {e["ph"] for e in events} == {"X"}
        names = {e["name"] for e in events}
        assert {"queue", "assemble", "device_wait"} <= names
        args = events[0]["args"]
        assert args["trace_id"] and args["txn_id"]
        json.dumps(payload)                # must be JSON-serializable

    def test_reset_clears_window_not_counters(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        self._scored_batch(tracer, clock, ["r1"], {"assemble": 1.0})
        tracer.reset()
        assert tracer.traces() == []
        assert tracer.counters["completed"] == 1


class TestSloTracker:
    def test_burn_rate_math(self):
        clock = [0.0]
        slo = SloTracker(objective_ms=20.0, objective_frac=0.99,
                         fast_window_s=1.0, slow_window_s=4.0,
                         bucket_s=0.05, clock=lambda: clock[0])
        for i in range(100):
            slo.record(5.0, now=clock[0])         # within objective
        slo.record(50.0, now=clock[0])            # one violation
        # violation frac 1/101 over a 1% budget -> burn ~0.99
        assert slo.burn_rate(1.0, now=clock[0]) == pytest.approx(
            (1 / 101) / 0.01, rel=1e-6)
        snap = slo.snapshot(now=clock[0])
        assert snap["windows"]["fast"]["violations"] == 1
        assert snap["violations_total"] == 1

    def test_window_ages_out(self):
        clock = [0.0]
        slo = SloTracker(objective_ms=20.0, objective_frac=0.99,
                         fast_window_s=1.0, slow_window_s=4.0,
                         bucket_s=0.05, clock=lambda: clock[0])
        for _ in range(50):
            slo.record(100.0, now=clock[0])       # all violations
        assert slo.burn_rate(1.0, now=clock[0]) == pytest.approx(100.0)
        clock[0] += 2.0                           # past the fast window
        assert slo.burn_rate(1.0, now=clock[0]) == 0.0
        # the slow window still sees them
        assert slo.burn_rate(4.0, now=clock[0]) == pytest.approx(100.0)


class TestSyncTracing:
    def _snapshot_with_traffic(self, clock, tracer):
        ctxs = [tracer.begin(f"m{i}") for i in range(4)]
        tb = tracer.batch(ctxs, batch_size=4)
        for stage in ("assemble", "pack", "dispatch", "device_wait",
                      "finalize"):
            tb.mark(stage)
            clock[0] += 0.003
        tracer.finish_batch(tb)
        return tracer.snapshot()

    def test_counter_delta_mirror(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        snap = self._snapshot_with_traffic(clock, tracer)
        mc = MetricsCollector()
        mc.sync_tracing(snap)
        assert mc.trace_completed.value(terminal="scored") == 4
        assert mc.trace_stage_ms.count(stage="assemble") == 4
        assert mc.trace_stage_ms.sum(stage="assemble") == pytest.approx(
            4 * 3.0, rel=0.01)
        # honest deltas: an unchanged snapshot mirrors as +0
        mc.sync_tracing(snap)
        assert mc.trace_completed.value(terminal="scored") == 4
        assert mc.trace_stage_ms.count(stage="assemble") == 4
        # more traffic mirrors only the increment
        snap2 = self._snapshot_with_traffic(clock, tracer)
        mc.sync_tracing(snap2)
        assert mc.trace_completed.value(terminal="scored") == 8
        assert mc.trace_stage_ms.count(stage="assemble") == 8

    def test_identical_series_from_two_collectors(self):
        """Satellite: stream-job and serving mirror the SAME snapshot into
        independent collectors — the rendered trace_* series must match."""
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        snap = self._snapshot_with_traffic(clock, tracer)
        a, b = MetricsCollector(), MetricsCollector()
        a.sync_tracing(snap)
        b.sync_tracing(snap)

        def trace_lines(mc):
            return [ln for ln in mc.render_prometheus().splitlines()
                    if ln.startswith("trace_")]

        assert trace_lines(a) == trace_lines(b)

    def test_exemplar_rendered_with_trace_id(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock)
        snap = self._snapshot_with_traffic(clock, tracer)
        mc = MetricsCollector()
        mc.sync_tracing(snap)
        text = mc.render_prometheus()
        ex_lines = [ln for ln in text.splitlines()
                    if ln.startswith("# exemplar trace_stage_ms_bucket")]
        assert ex_lines, "exemplar trace_ids must render as comment lines"
        assert 'trace_id="' in ex_lines[0]
        assert "trace_slo_burn_rate" in text
        # classic text format (version=0.0.4): no sample line may carry
        # trailing content — a trailing '#' would fail the WHOLE scrape
        for ln in text.splitlines():
            if ln and not ln.startswith("#"):
                assert "#" not in ln, f"exemplar leaked onto sample: {ln}"

    def test_slo_violation_counter_mirrors(self):
        clock = [0.0]
        tracer = _vclock_tracer(clock, slo_objective_ms=1.0)
        self._snapshot_with_traffic(clock, tracer)   # e2e 15ms > 1ms
        mc = MetricsCollector()
        mc.sync_tracing(tracer.snapshot())
        assert mc.trace_slo_violations.total() == 4
        mc.sync_tracing(tracer.snapshot())
        assert mc.trace_slo_violations.total() == 4


class TestStreamJobTracing:
    """Trace-context propagation through the REAL stream path."""

    def _run_job(self, qos=None, n=96, batch=32):
        from realtime_fraud_detection_tpu.obs.trace_drill import (
            TraceDrillConfig,
            TraceDrillScorer,
        )
        from realtime_fraud_detection_tpu.stream import (
            InMemoryBroker,
            JobConfig,
            StreamJob,
        )
        from realtime_fraud_detection_tpu.stream import topics as T

        clock = [0.0]
        tracer = _vclock_tracer(clock, ring_size=1024)
        scorer = TraceDrillScorer(clock, TraceDrillConfig(max_batch=batch))
        broker = InMemoryBroker()
        job = StreamJob(broker, scorer, JobConfig(
            max_batch=batch, emit_features=False, emit_enriched=False,
            qos=qos, tracing=tracer))
        txns = [{"transaction_id": f"j{i}", "user_id": f"u{i % 7}",
                 "merchant_id": "m1", "amount": 5.0 if i % 2 else 900.0,
                 "timestamp": "0.0"}
                for i in range(n)]
        broker.produce_batch(T.TRANSACTIONS, txns,
                             key_fn=lambda r: r["user_id"])
        job.run_until_drained(now=0.0)
        return tracer, job, txns

    def test_every_scored_txn_has_one_trace(self):
        tracer, job, txns = self._run_job()
        scored = tracer.traces(terminal="scored")
        assert len(scored) == len(txns)
        assert {t.txn_id for t in scored} == \
            {t["transaction_id"] for t in txns}
        for t in scored:
            assert {"queue", "assemble", "pack", "dispatch",
                    "device_wait", "finalize"} <= set(t.stages)
            assert t.meta["batch_size"] >= 1
            assert t.meta["close_reason"] in (
                "size", "deadline", "budget", "timeout", "flush")

    def test_shed_txns_carry_terminal_shed_stage(self):
        from realtime_fraud_detection_tpu.qos import QosPlane
        from realtime_fraud_detection_tpu.utils.config import QosSettings

        qos = QosPlane(QosSettings(enabled=True, admission_rate=1.0,
                                   admission_burst=8.0))
        tracer, job, txns = self._run_job(qos=qos)
        assert job.counters["shed"] > 0
        shed = tracer.traces(terminal="shed")
        assert len(shed) == job.counters["shed"]
        for t in shed:
            assert t.terminal == "shed"
            assert t.meta["reason"]
        # shed + scored partition the admitted stream
        assert len(shed) + len(tracer.traces(terminal="scored")) \
            == len(txns)


def test_trace_drill_fast_smoke(capsys):
    """The `rtfd trace-drill --fast` acceptance path runs un-slow-marked
    on every tier-1 pass — through the CLI entry, pinning attribution,
    SLO reaction + recovery, FIFO/shed equality, and the overhead bound
    (final stdout line: the compact <2 KB verdict)."""
    from realtime_fraud_detection_tpu import cli

    rc = cli.main(["trace-drill", "--fast"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    compact = json.loads(out[-1])
    assert len(out[-1].encode()) < 2048
    assert compact["passed"] is True
    assert compact["dominant"] == {"slow_assembly": "assemble",
                                   "slow_device": "device_wait"}
    assert compact["burn"]["slow_device_peak"] > compact["burn"]["threshold"]
    full = json.loads(out[-2])
    assert full["checks"]["noop_under_bound"]


def test_tracing_overhead_guard_real_scorer():
    """Tier-1 CI overhead guard: a fixed fake-Kafka workload on the REAL
    scorer, tracing off vs on — the job thread's per-txn host cost must
    stay under the pinned ratio (the plane is admissible on the hot path,
    not just in the virtual drill). The cost is the thread's CPU time, not
    the wall: the job runs on the calling thread and the tracer's work with
    it, while the suite's other xdist workers take the cores away for
    whole scheduler slices at a time — a wall-clock ratio of two ~1 s soaks
    failed under six workers and passed alone. Batch 16 reuses the bucket
    other tier-1 suites already compiled in-process, so the guard costs
    seconds."""
    import time

    from realtime_fraud_detection_tpu.obs.tracing import Tracer as _Tracer
    from realtime_fraud_detection_tpu.scoring import (
        FraudScorer,
        ScorerConfig,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )
    from realtime_fraud_detection_tpu.stream import topics as T

    batch, n = 16, 256

    def soak(traced: bool) -> float:
        gen = TransactionGenerator(num_users=500, num_merchants=100,
                                   seed=13)
        broker = InMemoryBroker()
        s = FraudScorer(scorer_config=ScorerConfig())
        s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        tracer = (_Tracer(TracingSettings(enabled=True))
                  if traced else None)
        job = StreamJob(broker, s, JobConfig(
            max_batch=batch, emit_features=False, tracing=tracer))
        broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(n),
                             key_fn=lambda r: str(r["user_id"]))
        s.score_batch(gen.generate_batch(batch))     # compile outside
        t0 = time.thread_time()
        job.run_until_drained(now=1000.0)
        assert job.counters["scored"] == n
        return time.thread_time() - t0

    # interleaved, the least of three per arm: what is left of the noise
    # (cache and frequency effects) only ever adds. The bound is
    # deliberately generous (tracing measures ~1.01x) so only a real
    # hot-path regression trips it
    runs = [(soak(False), soak(True)) for _ in range(3)]
    off = min(run[0] for run in runs)
    on = min(run[1] for run in runs)
    assert off > 0.01, f"the job thread's CPU time reads {off:.4f} s"
    assert on / off < 1.5, f"tracing overhead ratio {on / off:.3f} >= 1.5"


# ---------------------------------------------------------------------------
# distributed tracing + fleet aggregation (ISSUE 20)
# ---------------------------------------------------------------------------

class TestCarrier:
    """Cross-process trace carrier: wire roundtrip, transit attribution,
    redirect ledger, and loss accounting (fresh root, never a wedge)."""

    def test_roundtrip_and_sparse_wire_form(self):
        from realtime_fraud_detection_tpu.obs.tracing import (
            make_carrier,
            parse_carrier,
        )

        c = make_carrier("tingress-2a", origin="ingress", produced_ts=12.5,
                         priority="high", hops=2, redirect_s=0.003)
        # survives JSON framing (the broker wire) verbatim
        p = parse_carrier(json.loads(json.dumps(c)))
        assert p["tid"] == "tingress-2a" and p["org"] == "ingress"
        assert p["ts"] == 12.5 and p["rh"] == 2 and p["rs"] == 0.003
        # empty fields never ride the wire — the carrier stays tiny
        assert set(make_carrier("t1")) == {"v", "tid"}

    def test_parse_rejects_garbage(self):
        from realtime_fraud_detection_tpu.obs.tracing import parse_carrier

        for bad in (None, "x", 7, [], {}, {"tid": ""}, {"tid": 3}):
            assert parse_carrier(bad) is None

    def test_adopted_carrier_books_transit_additively(self):
        from realtime_fraud_detection_tpu.obs.tracing import make_carrier

        clock = [0.0]
        tracer = _vclock_tracer(clock)
        # produced at wall 10.0, consumed at wall 10.4; the record's own
        # event-time lag is 0.5 s — ingest must shrink by the transit so
        # the pre-admission segments never double-count one interval
        c = make_carrier("tingress-1", origin="ingress", produced_ts=10.0)
        ctx = tracer.begin("tx1", ingest_lag_s=0.5, carrier=c,
                           now_wall=10.4)
        tb = tracer.batch([ctx])
        tb.mark("device_wait")
        clock[0] += 0.010
        tracer.finish_batch(tb)
        (t,) = tracer.traces(terminal="scored")
        assert t.trace_id == "tingress-1" and t.origin == "ingress"
        assert t.stages["broker_transit"] == pytest.approx(400.0)
        assert t.stages["ingest"] == pytest.approx(100.0)
        assert sum(t.stages.values()) == pytest.approx(t.e2e_ms)
        assert t.to_dict()["origin"] == "ingress"
        assert tracer.counters["carrier_adopted"] == 1
        assert tracer.counters["carrier_lost"] == 0

    def test_redirect_ledger_is_a_stage(self):
        from realtime_fraud_detection_tpu.obs.tracing import make_carrier

        clock = [0.0]
        tracer = _vclock_tracer(clock)
        c = make_carrier("tserving-9", origin="serving", hops=1,
                         redirect_s=0.002)
        ctx = tracer.begin("tx2", carrier=c)
        tracer.finish_terminal(ctx, "shed", reason="no_tokens")
        (t,) = tracer.traces(terminal="shed")
        assert t.stages["redirect_hops"] == pytest.approx(2.0)

    def test_lost_carrier_degrades_to_fresh_local_root(self):
        clock = [0.0]
        tracer = Tracer(TracingSettings(enabled=True, ring_size=64,
                                        origin="w7"),
                        clock=lambda: clock[0])
        # expected-but-missing and present-but-garbled both count as loss
        lost1 = tracer.begin("tx3", expect_carrier=True)
        lost2 = tracer.begin("tx4", carrier={"v": 1})
        for ctx in (lost1, lost2):
            # fresh LOCAL root: minted id carries THIS process's origin
            # prefix, no adopted origin, no transit
            assert ctx.trace_id.startswith("tw7-")
            assert ctx.origin == "" and ctx.broker_transit_s == 0.0
            tracer.finish_terminal(ctx, "shed", reason="test")
        assert tracer.counters["carrier_lost"] == 2
        assert tracer.counters["carrier_adopted"] == 0
        # never a wedge: every started trace reached a terminal
        c = tracer.counters
        assert c["started"] == (c["completed"] + c["shed"] + c["errors"]
                                + c["cached"])


class TestLogTraceCorrelation:
    def test_json_formatter_stamps_active_trace_context(self):
        from realtime_fraud_detection_tpu.obs.tracing import (
            clear_log_context,
            set_log_context,
        )

        rec = logging.LogRecord("t", logging.INFO, __file__, 1, "in-batch",
                                (), None)
        set_log_context("tw2-0000002a", "w2")
        try:
            out = json.loads(JsonFormatter().format(rec))
        finally:
            clear_log_context()
        assert out["trace_id"] == "tw2-0000002a"
        assert out["worker"] == "w2"
        # context cleared -> no stamp (and explicit record fields win)
        rec2 = logging.LogRecord("t", logging.INFO, __file__, 1, "idle",
                                 (), None)
        out2 = json.loads(JsonFormatter().format(rec2))
        assert "trace_id" not in out2 and "worker" not in out2


class TestFleetMetrics:
    def _fm(self):
        from realtime_fraud_detection_tpu.obs.fleetmetrics import (
            FleetMetrics,
        )

        return FleetMetrics()

    def test_delta_fold_is_exact_and_dedupes_stale_seq(self):
        fm = self._fm()
        assert fm.ingest_delta({"worker": "w0", "seq": 1,
                                "counters": {"scored_total": 3.0,
                                             "shed": 0.0}})
        assert fm.ingest_delta({"worker": "w1", "seq": 1,
                                "counters": {"scored_total": 2.0}})
        assert fm.ingest_delta({"worker": "w0", "seq": 2,
                                "counters": {"scored_total": 4.0,
                                             "shed": 1.0}})
        # replayed/stale event is dropped, not double-counted
        assert not fm.ingest_delta({"worker": "w0", "seq": 2,
                                    "counters": {"scored_total": 99.0}})
        fleet = fm.fleet_counters()
        assert fleet["scored_total"] == 9.0
        assert fleet["shed"] == 1.0
        assert fm.worker_counters()["w0"]["scored_total"] == 7.0
        snap = fm.snapshot()
        assert snap["events_applied"] == 3 and snap["events_stale"] == 1
        assert snap["seq"] == {"w0": 2, "w1": 1}

    def test_render_prometheus_hygiene(self):
        fm = self._fm()
        fm.ingest_cumulative("w0", {"scored_total": 3, "shed": 1})
        fm.ingest_cumulative("w1", {"scored_total": 2})
        fm.set_worker_info("w0", pid="123", version="0.1.0")
        text = fm.render(version="0.1.0")
        lines = text.splitlines()
        # exactly one HELP/TYPE pair per family, HELP immediately
        # followed by TYPE
        helps = [ln.split()[2] for ln in lines if ln.startswith("# HELP")]
        types = [ln.split()[2] for ln in lines if ln.startswith("# TYPE")]
        assert helps == sorted(set(helps))
        assert types == helps
        # counter suffix normalization: never _total_total, and keys
        # without the suffix gain it exactly once
        assert "_total_total" not in text
        assert 'rtfd_worker_shed_total{worker="w0"} 1' in lines
        # the unlabeled fleet sum equals the per-worker sum
        assert "rtfd_fleet_scored_total 5" in lines
        # identity gauges
        assert any(ln.startswith("rtfd_build_info{")
                   and 'version="0.1.0"' in ln and ln.endswith(" 1")
                   for ln in lines)
        assert any(ln.startswith("fleet_worker_info{")
                   and 'pid="123"' in ln and 'worker="w0"' in ln
                   for ln in lines)


def _trace_row(tid, txn, worker_s, t_start, stages, origin="",
               terminal="scored", spans=None):
    e2e = sum(stages.values())
    meta = {"spans": spans} if spans else {}
    row = {"trace_id": tid, "txn_id": txn, "t_start": t_start,
           "e2e_ms": e2e, "stages": dict(stages), "meta": meta,
           "terminal": terminal, "priority": ""}
    if origin:
        row["origin"] = origin
    return row


class TestFleetTraceStore:
    def _store(self, **kw):
        from realtime_fraud_detection_tpu.obs.fleetmetrics import (
            FleetTraceStore,
        )

        return FleetTraceStore(**kw)

    def test_stitch_stats_crossed_fresh_and_remote(self):
        st = self._store()
        st.ingest("w0", [
            _trace_row("tingress-1", "a", "w0", 1.0,
                       {"ingest": 1.0, "broker_transit": 4.0,
                        "device_wait": 2.0}, origin="ingress"),
            _trace_row("tw0-1", "b", "w0", 1.1, {"device_wait": 2.0}),
        ], pid=41)
        st.ingest("w1", [
            _trace_row("tingress-2", "c", "w1", 1.2,
                       {"ingest": 0.5, "broker_transit": 8.0,
                        "device_wait": 2.0,
                        "remote_fetch": 1.5}, origin="ingress",
                       spans=[{"name": "remote_fetch", "ms": 1.5}]),
        ], pid=42)
        s = st.stitch_stats()
        assert s["total"] == 3
        assert s["crossed_process"] == 2
        assert s["fresh_roots"] == 1
        assert s["with_remote_span"] == 1
        assert s["stitch_rate"] == pytest.approx(2 / 3, abs=1e-3)
        assert s["broker_transit_ms"]["n"] == 2
        assert s["broker_transit_ms"]["max"] == pytest.approx(8.0)

    def test_breakdown_attributes_dominant_worker(self):
        st = self._store()
        # w0 fast, w1 the slow worker: device_wait owns w1's traces and
        # w1 owns the fleet tail
        st.ingest("w0", [
            _trace_row(f"tw0-{i}", f"f{i}", "w0", 1.0 + i * 0.01,
                       {"assemble": 1.0, "device_wait": 2.0})
            for i in range(10)])
        st.ingest("w1", [
            _trace_row(f"tw1-{i}", f"s{i}", "w1", 1.0 + i * 0.01,
                       {"assemble": 1.0, "device_wait": 90.0 + i})
            for i in range(10)])
        bd = st.breakdown()
        assert bd["n"] == 20
        for q in ("p50", "p95", "p99"):
            assert bd["quantiles"][q]["dominant_worker"] == "w1"
            assert bd["quantiles"][q]["dominant_stage"] == "device_wait"
        assert bd["per_worker"]["w1"]["dominant_stage"] == "device_wait"
        assert bd["exemplars"][0]["worker"] == "w1"

    def test_export_draws_flow_arrows_across_the_broker_hop(self):
        st = self._store()
        st.ingest("w0", [
            _trace_row("tingress-1", "a", "w0", 1.0,
                       {"ingest": 1.0, "broker_transit": 4.0,
                        "device_wait": 2.0}, origin="ingress"),
            _trace_row("tw0-1", "b", "w0", 1.1, {"device_wait": 2.0}),
        ], pid=41)
        payload = st.export_chrome_trace()
        ev = payload["traceEvents"]
        track_names = {e["args"]["name"] for e in ev if e["ph"] == "M"}
        assert "worker w0 (pid 41)" in track_names
        assert "ingress ingress" in track_names
        starts = [e for e in ev if e["ph"] == "s"]
        ends = [e for e in ev if e["ph"] == "f"]
        assert len(starts) == len(ends) == 1      # one crossed trace
        assert starts[0]["pid"] != ends[0]["pid"]  # arrow crosses tracks
        # the stitched trace's transit slice draws on the ORIGIN track
        transit = [e for e in ev if e["ph"] == "X"
                   and e["name"] == "broker_transit"]
        assert transit[0]["pid"] == starts[0]["pid"]
        json.dumps(payload)

    def test_merge_chrome_traces_folds_ring_dumps(self):
        from realtime_fraud_detection_tpu.obs.fleetmetrics import (
            merge_chrome_traces,
        )

        dumps = [
            {"worker": "w0", "pid": 41, "traces": [
                _trace_row("tingress-1", "a", "w0", 1.0,
                           {"ingest": 1.0, "broker_transit": 4.0,
                            "device_wait": 2.0}, origin="ingress")]},
            {"worker": "w1", "pid": 42, "traces": [
                _trace_row("tw1-1", "b", "w1", 1.1,
                           {"device_wait": 2.0})]},
        ]
        merged = merge_chrome_traces(dumps)
        tracks = merged["metadata"]["tracks"]
        assert {"w0", "w1", "ingress"} <= set(tracks)
        assert merged["metadata"]["n_traces"] == 2
        assert any(e["ph"] == "s" for e in merged["traceEvents"])


def test_obs_drill_fast_smoke(capsys):
    """The `rtfd obs-drill --fast --no-replay` acceptance path runs
    un-slow-marked on every tier-1 pass — ≥2 real OS worker processes,
    producer-stamped carriers over the TCP netbroker, the netfault
    carrier-strip window, fleet-metric exactness, and the compact <2 KB
    verdict as the final stdout line. One retry absorbs a wall-clock
    scheduling stall on oversubscribed CI hosts (the drill's overhead
    ratio and p99 attribution are real-time measurements over real OS
    processes — the `_dryrun_multihost` retry discipline); a retried
    pass still proves the plane, a double failure fails the gate."""
    from realtime_fraud_detection_tpu import cli

    rc = cli.main(["obs-drill", "--fast", "--no-replay"])
    if rc != 0:
        capsys.readouterr()                       # drop the failed pass
        rc = cli.main(["obs-drill", "--fast", "--no-replay"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    compact = json.loads(out[-1])
    assert len(out[-1].encode()) < 2048
    assert compact["passed"] is True
    assert compact["crossed"] > 0
    carriers = compact["carriers"]
    assert carriers["lost_total"] == carriers["stripped"]
    # "carried" counts every record that kept its carrier (redirect
    # records included) — adoption must match it exactly
    assert carriers["adopted_total"] == carriers["carried"]
    full = json.loads(out[-2])
    assert full["checks"]["fleet_counters_exact"]
    assert full["checks"]["no_cross_attachment"]
    assert full["checks"]["broker_transit_nonzero"]
