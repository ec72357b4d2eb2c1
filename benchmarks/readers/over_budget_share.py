"""Percent of attempted transactions not emitted within the traffic
file's latency budget of their due time (a failed one is over)."""

from benchmarks.harness import latency
from benchmarks.readers.latency_percentile import samples


def read(run):
    return latency.share_over(samples(run), run.budget_ms)
