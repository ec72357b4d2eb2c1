"""``total_s`` of one ``SpanTimer`` span minus those of the spans in
``minus`` (``FraudScorer.host_stats()["stages"]``, reset at window open),
per scored transaction, in microseconds."""


def read(run, span, minus):
    scored = run.counters.get("scored", 0)
    if not scored or any(s not in run.stages for s in [span, *minus]):
        return None
    rest = sum(run.stages[s]["total_s"] for s in minus)
    return 1e6 * (run.stages[span]["total_s"] - rest) / scored
