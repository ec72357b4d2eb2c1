"""``harness.runner.memory_peak_bytes`` of the fullest chip after the
window, in GB (1e9 bytes)."""


def read(run):
    return run.extra["memory_peak_bytes"] / 1e9
