"""``100 * StreamJob.counters[num] / counters[den]`` over the counted part,
in percent; ``None`` where the program counts neither (a program from before
the counters)."""


def read(run, num, den):
    if not run.counters.get(den) or num not in run.counters:
        return None
    return 100.0 * run.counters[num] / run.counters[den]
