"""Sum of the ``total_s`` of the named ``SpanTimer`` spans
(``FraudScorer.host_stats()["stages"]``, reset at window open) per scored
transaction, in microseconds."""


def read(run, spans):
    scored = run.counters.get("scored", 0)
    if not scored or any(s not in run.stages for s in spans):
        return None
    return 1e6 * sum(run.stages[s]["total_s"] for s in spans) / scored
