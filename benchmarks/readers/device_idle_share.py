"""1 - device busy (union of operations) / traced slice, in percent; the
mean over chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
