"""The metrics PR 36 added, on hand-made runs: the three under ``setup_s``
read the program's compile ledger and tell set-up from window by its
stamps; two scopes and two counters get readers that were there.

Fixture-free but for ``monkeypatch``: ``tests/test_bench_seam.py`` imports
these functions, so tier-1 runs them too."""

import types

import pytest

from benchmarks.harness import spec

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
SETUP_METRICS = ("compile_setup_s", "trace_lower_setup_s", "setup_programs")


def _metric(name, run):
    return spec.reader_for(name, "per_layer")(run)


def _phase(ledger, event, program, start, end, inside=()):
    """One phase as JAX tells a listener of it: where it opens, whatever
    closes inside it, where it closes."""
    ledger.on_open(event, start, fun_name=program)
    for args in inside:
        _phase(ledger, *args)
    ledger.on_close(event, start, end, fun_name=program)


def _ledger(monkeypatch, max_records=4096):
    """A ledger of the program's class in the place of the process's."""
    from realtime_fraud_detection_tpu.obs import profiling

    ledger = profiling.CompileLedger(max_records=max_records)
    monkeypatch.setattr(profiling, "compile_ledger", lambda: ledger)
    return ledger


def test_setup_records_are_counted_and_the_windows_are_not(monkeypatch):
    ledger = _ledger(monkeypatch)
    # set-up: one program, and inside its trace an eager operation that
    # compiled a program of its own
    _phase(ledger, TRACE, "score", 100.0, 104.0, inside=[
        (TRACE, "kernel", 100.1, 100.4),
        (LOWER, "jit(add)", 100.5, 101.0),
        (COMPILE, "jit(add)", 101.0, 102.0)])
    _phase(ledger, LOWER, "jit(score)", 104.0, 105.0)
    _phase(ledger, COMPILE, "jit(score)", 105.0, 108.0)
    # open when the window opens: it ended inside the window
    _phase(ledger, COMPILE, "jit(late)", 109.5, 110.5)
    # the window's own (a bucket nobody warmed)
    _phase(ledger, TRACE, "score", 111.0, 112.0)
    _phase(ledger, LOWER, "jit(score)", 112.0, 112.5)
    _phase(ledger, COMPILE, "jit(score)", 112.5, 114.0)
    run = types.SimpleNamespace(t_open=110.0)
    assert _metric("compile_setup_s", run) == pytest.approx(1.0 + 3.0)
    # [100, 105] less the second the nested program was being compiled
    assert _metric("trace_lower_setup_s", run) == pytest.approx(5.0 - 1.0)
    assert _metric("setup_programs", run) == 2
    # time that passed once: under any set-up that held these records
    assert _metric("compile_setup_s", run) \
        + _metric("trace_lower_setup_s", run) <= 110.0 - 100.0
    # another window over the same ledger
    early = types.SimpleNamespace(t_open=103.0)
    assert _metric("compile_setup_s", early) == pytest.approx(1.0)
    assert _metric("trace_lower_setup_s", early) == pytest.approx(0.5)
    assert _metric("setup_programs", early) == 1


def test_two_threads_compiling_at_once_are_counted_once(monkeypatch):
    import threading

    ledger = _ledger(monkeypatch)
    _phase(ledger, COMPILE, "jit(a)", 10.0, 14.0)
    other = threading.Thread(
        target=_phase, args=(ledger, COMPILE, "jit(b)", 12.0, 15.0))
    other.start()
    other.join()
    run = types.SimpleNamespace(t_open=20.0)
    assert _metric("compile_setup_s", run) == pytest.approx(5.0)
    assert _metric("setup_programs", run) == 2


def test_a_program_without_the_ledger_reads_none(monkeypatch):
    """The parent of PR 36: the import fails, the metric is left out."""
    from realtime_fraud_detection_tpu.obs import profiling

    monkeypatch.delattr(profiling, "compile_ledger")
    run = types.SimpleNamespace(t_open=110.0)
    for name in SETUP_METRICS:
        assert _metric(name, run) is None, name


def test_a_ledger_that_let_records_go_reads_none(monkeypatch):
    ledger = _ledger(monkeypatch, max_records=2)
    for i in range(3):
        _phase(ledger, COMPILE, f"jit(p{i})", float(i), i + 0.5)
    run = types.SimpleNamespace(t_open=110.0)
    for name in SETUP_METRICS:
        assert _metric(name, run) is None, name


def test_the_scope_and_counter_metrics_on_a_hand_made_run():
    layers = 6
    scope_s = {"text": 0.5}
    for i in range(layers):
        scope_s.update({f"text/layer{i}/attn_proj": 0.004,
                        f"text/layer{i}/ln": 0.001,
                        f"text/layer{i}/ffn": 0.02})
    counters = {"batches": 40, "scored": 40 * 256, "split_batches": 38,
                "compact_batches": 10}
    run = types.SimpleNamespace(
        trace={"window_s": 1.0}, counters=counters,
        counters_slice={"batches": 4},
        extra={"scope_trace": {"busy_s": 1.0, "scoped": True,
                               "scope_s": scope_s}})
    assert _metric("attn_proj_ms_per_batch", run) == pytest.approx(6.0)
    assert _metric("ln_ms_per_batch", run) == pytest.approx(1.5)
    assert _metric("split_batches_pct", run) == pytest.approx(95.0)
    assert _metric("compact_batches_pct", run) == pytest.approx(25.0)
    # a program from before the scopes and the counters
    old = types.SimpleNamespace(
        trace={"window_s": 1.0}, counters={"batches": 40},
        counters_slice={"batches": 4},
        extra={"scope_trace": {"busy_s": 1.0, "scoped": True,
                               "scope_s": {"text": 0.5}}})
    for name in ("attn_proj_ms_per_batch", "ln_ms_per_batch",
                 "split_batches_pct", "compact_batches_pct"):
        assert _metric(name, old) is None, name
