"""How late the load generator ran: actual submit - due, in ms, over the
events due inside the window (never-submitted ones left out: they are
failures, counted elsewhere)."""

import numpy as np

from benchmarks.harness import latency


def read(run, q):
    w = run.in_window() & np.isfinite(run.submitted)
    late = (run.submitted[w] - run.due[w]) * 1e3
    return latency.percentile(late, q)
