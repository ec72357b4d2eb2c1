"""Device-busy time in the traced slice (summed over chips) per batch
completed in it, in ms."""


def read(run):
    batches = run.counters_slice.get("batches", 0)
    if run.trace is None or not batches:
        return None
    return 1e3 * sum(run.trace["per_device"].values()) / batches
