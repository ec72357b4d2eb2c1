"""Percent of device-busy time in the traced slice whose operations carry
none of the program's scope names (``harness/scopes.py``): how much of the
device the per-branch attribution cannot see."""

from benchmarks.harness import scopes


def read(run):
    red = scopes.for_run(run)
    if red is None or not red["busy_s"]:
        return None
    return 100.0 * red["scope_s"].get(scopes.UNSCOPED, 0.0) / red["busy_s"]
