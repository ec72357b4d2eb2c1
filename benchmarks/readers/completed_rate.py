"""Transactions due inside the counted part that were emitted without a
failure marker, per second: below the offered rate only when behind."""


def read(run):
    w = run.in_window()
    return float((w & ~run.failed()).sum()) / run.counted_s
