"""Host time of completion per scored transaction, in microseconds: the
benchmark's span around ``job.complete_batch`` minus the program's
``device_wait`` span inside it (response build, state write-back,
fan-out to the output topics, commit)."""


def read(run):
    scored = run.counters.get("scored", 0)
    span = run.bench_spans.get("job.complete_batch")
    if not scored or span is None or "device_wait" not in run.stages:
        return None
    return 1e6 * (span[1] - run.stages["device_wait"]["total_s"]) / scored
