"""Due -> emitted (``Record.timestamp`` on the predictions topic), over
every transaction due inside the window; a failed one enters as the longest
latency the run could have measured. None without ten samples beyond."""

from benchmarks.harness import latency


def samples(run):
    w = run.in_window()
    miss_ms = (run.seconds + run.grace_s) * 1e3
    return latency.latencies_ms(run.due[w], run.emitted[w],
                                run.failed()[w], miss_ms)


def read(run, q):
    lat = samples(run)
    run.extra["latency_samples"] = len(lat)
    return latency.percentile(lat, q)
