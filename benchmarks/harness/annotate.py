"""Spans at the layer boundaries, written from the benchmark's side.

The program's scoring path carries no ``jax.profiler`` annotation, so in
the traced run the benchmark wraps the bound methods at each boundary —
on the instances of this run only, never the classes. Each call is a
``TraceAnnotation`` named ``bench:<name>`` (a host span on the profiler's
own clock, beside the device's operations) and is summed on
``perf_counter`` under the same name, for the metrics that need a host
time but no trace.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

# (object attribute of the run, method) at each boundary, outermost first
BOUNDARIES: Tuple[Tuple[str, str], ...] = (
    ("job", "dispatch_batch"),
    ("job", "complete_batch"),
    ("scorer", "assemble"),
    ("scorer", "dispatch_assembled"),
    ("scorer", "finalize"),
)


class Spans:
    """``totals[name] = [calls, seconds]``; ``reset()`` at a window edge."""

    def __init__(self) -> None:
        self.totals: Dict[str, List[float]] = {}

    def reset(self) -> None:
        self.totals = {}

    def wrap(self, owner: Any, owner_name: str, method: str) -> None:
        import jax

        inner = getattr(owner, method)
        name = f"{owner_name}.{method}"
        label = f"bench:{name}"
        annotation = jax.profiler.TraceAnnotation
        spans = self

        def wrapped(*args: Any, **kwargs: Any) -> Any:
            t0 = time.perf_counter()
            try:
                with annotation(label):
                    return inner(*args, **kwargs)
            finally:
                tot = spans.totals.setdefault(name, [0, 0.0])
                tot[0] += 1
                tot[1] += time.perf_counter() - t0

        setattr(owner, method, wrapped)   # instance attribute: this run only


def install(job: Any, scorer: Any) -> Spans:
    spans = Spans()
    owners = {"job": job, "scorer": scorer}
    for owner_name, method in BOUNDARIES:
        spans.wrap(owners[owner_name], owner_name, method)
    return spans
