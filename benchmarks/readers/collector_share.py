"""Percent of the counted part of the window that the job's process spent
inside the cyclic garbage collector (the benchmark's ``gc.callbacks``
clock; every thread of the process stands still meanwhile)."""


def read(run):
    c = run.extra.get("collector")
    if not c or run.counted_s <= 0:
        return None
    return 100.0 * c["seconds"] / run.counted_s
