"""Seconds of set-up the program spent in the named phases of its own
compilations, from the program's compile ledger
(``realtime_fraud_detection_tpu/obs/profiling.CompileLedger``: one record a
phase — ``trace``, ``lower``, ``compile`` — of every compilation in the
process, with JAX's own ``time.time()`` stamps).

Set-up is told from window by the stamps: a record counts if it ended
before ``Run.t_open`` (``time.time()`` where the window opens), so the
``SpanTimer.reset()`` there and what ``Run.stages`` holds play no part. The
value is the union of the ``phases`` records' intervals, less what the
``less`` records cover of it: a program compiled while another was traced
(an eager operation on a constant) is compile time and not tracing time, so
``compile_setup_s`` + ``trace_lower_setup_s`` is time that passed once and
stays under ``setup_s``. The ledger keeps nested traces (a jitted function
traced inside another) as a count under their root, not as records: the
root's interval holds them.

``None`` where the program keeps no ledger (a parent from before it) or the
ledger's cap has let records go."""


def _union_s(intervals):
    total, edge = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > edge:
            total += end - max(start, edge)
            edge = end
    return total


def read(run, phases, less=()):
    try:
        from realtime_fraud_detection_tpu.obs.profiling import compile_ledger
    except ImportError:
        return None
    ledger = compile_ledger()
    if ledger.totals()["dropped"]:
        return None
    records = [r for r in ledger.records() if r["end"] <= run.t_open]

    def spans(which):
        return [(r["start"], r["end"]) for r in records
                if r["phase"] in which]

    return _union_s(spans(set(phases) | set(less))) - _union_s(spans(less))
