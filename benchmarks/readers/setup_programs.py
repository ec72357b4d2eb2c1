"""Programs the process compiled, or loaded from the persistent cache,
before the window opened: the ``compile`` records of the program's compile
ledger (``realtime_fraud_detection_tpu/obs/profiling.CompileLedger``) that
ended before ``Run.t_open``, told from the window's by JAX's own
``time.time()`` stamps, as ``setup_phase_time`` does. ``None`` where the program keeps no
ledger (a parent from before it) or the ledger's cap has let records go."""


def read(run):
    try:
        from realtime_fraud_detection_tpu.obs.profiling import compile_ledger
    except ImportError:
        return None
    ledger = compile_ledger()
    if ledger.totals()["dropped"]:
        return None
    return sum(1 for r in ledger.records()
               if r["phase"] == "compile" and r["end"] <= run.t_open)
