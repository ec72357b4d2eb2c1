"""Completions per second between the first and the last completion
inside the counted part of the window: predictions stamped by the broker on
the predictions topic after the first batch of the window, over the time
from that first batch to the last. (A plain count over the window's length
moves in steps of one batch — 1% of a cell that completes five batches a
second — with where the window's edges happen to fall.)"""

import numpy as np

FIRST_BATCH_S = 0.002     # records of one fan-out are stamped within this


def read(run):
    ts = np.sort(run.emitted[run.in_window()])
    if len(ts) < 2 or ts[-1] - ts[0] <= 0:
        return None
    after_first = int((ts > ts[0] + FIRST_BATCH_S).sum())
    return after_first / float(ts[-1] - ts[0])
