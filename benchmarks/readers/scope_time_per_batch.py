"""Device time in the traced slice under the program's named scopes
(``harness/scopes.py``; union of each scope's operations, summed over the
listed scopes and over chips) per batch completed in the slice, in ms.

``scopes`` must each have an operation: one that has none is named on the
log and the metric is left out, never reported as 0. ``optional`` scopes
(glue the compiler may fuse into a neighbour) count where they exist."""

from benchmarks.harness import scopes as scopes_mod


def read(run, scopes, optional=()):
    batches = run.counters_slice.get("batches", 0)
    red = scopes_mod.for_run(run)
    if red is None or not batches:
        return None
    total = 0.0
    for pattern in scopes:
        s = scopes_mod.scope_seconds(run, pattern)
        if s is None:
            return None
        total += s
    for pattern in optional:
        total += scopes_mod.matching(red["scope_s"], pattern) or 0.0
    return 1e3 * total / batches
