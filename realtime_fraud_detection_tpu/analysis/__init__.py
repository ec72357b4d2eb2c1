"""Invariant guard plane: repo-native static checks + dynamic lock watcher.

The system's correctness invariants — virtual-clock determinism, no
device-to-host pull on the dispatch path, honest counter-delta Prometheus
mirrors, score-lock discipline around param swaps — lived only in
docstrings until this package. ``rtfd lint`` (analysis/lint.py) machine-
checks them over the AST; ``analysis/lockwatch.py`` watches real lock
acquisition order while the deterministic drills run. Both are enforced
in tier-1 (tests/test_analysis.py), so a new wall-clock read in a
virtual-clock subsystem or a d2h pull in a pre-pull-safe module fails
the suite with a pointed message instead of silently corrupting a drill
replay three PRs later.
"""

from realtime_fraud_detection_tpu.analysis.lint import (
    Finding,
    RULES,
    format_findings,
    lint_paths,
    lint_source,
    run_lint,
)
from realtime_fraud_detection_tpu.analysis.lockwatch import (
    LockWatcher,
    watch_locks,
)

__all__ = [
    "Finding",
    "RULES",
    "format_findings",
    "lint_paths",
    "lint_source",
    "run_lint",
    "LockWatcher",
    "watch_locks",
]
