"""``StreamJob.counters[num] / counters[den]`` over the counted part."""


def read(run, num, den):
    if not run.counters.get(den):
        return None
    return run.counters[num] / run.counters[den]
