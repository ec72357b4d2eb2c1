"""Due -> ``Record.timestamp`` on the transactions topic, in ms: the
gateway's ring, its sender and the produce, read by a consumer group of
the benchmark's own after the window."""

import numpy as np

from benchmarks.harness import latency


def read(run, q):
    w = run.in_window() & np.isfinite(run.ingested)
    return latency.percentile((run.ingested[w] - run.due[w]) * 1e3, q)
