"""Device time in the traced slice of ONE part of a named scope, per batch
completed in the slice, in ms.

The program cuts four of its layer scopes once more, by a ``named_scope``
nested inside the scope (``realtime_fraud_detection_tpu/obs/scopes.py``
``SCOPE_PARTS``): an operation's path is then ``text/layer3/ssm_proj/in_proj``
where a builder's ``VOCABULARY`` — and with it every reader of the parent —
stops at ``text/layer3/ssm_proj``. This reader deepens the run's vocabulary
by the parts that ``layer_metrics/*.json`` name for it (every file whose
``reader`` is this one: ``{"scope": "text/layer*/ssm_proj", "part":
"in_proj"}``), reads the trace again through ``harness/scopes.read_xplane``
and ``reduce`` — once a run, kept on ``run.extra`` — and returns the union
of the intervals of the operations under ``scope`` whose next component is
``part``, summed over layers and chips.

``None`` with a line on the log where no operation carries the part (a
program written before the parts: the parent commit), never 0.
"""

import copy
import json
from pathlib import Path

from benchmarks.harness import scopes as scopes_mod
from benchmarks.harness import trace as trace_mod

KEPT = "scope_part_trace"
METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"


def declared_parts():
    """``[(scope pattern, part)]`` of every metric file that names this
    reader."""
    out = []
    for path in sorted(METRICS.glob("*.json")):
        d = json.loads(path.read_text())
        if d.get("reader") == Path(__file__).stem:
            out.append((d["args"]["scope"], d["args"]["part"]))
    return out


def deepened(vocabulary, parts):
    """A copy of ``vocabulary`` in which each ``(scope, part)`` of ``parts``
    whose scope it names, component by component, knows ``part`` below it."""
    out = copy.deepcopy(dict(vocabulary))
    for scope, part in parts:
        level = out
        for name in scope.split("/"):
            level = level.get(name)
            if level is None:
                break
        else:
            level.setdefault(part, {})
    return out


def part_seconds(run):
    """``{path: seconds}`` of this run's trace under the deepened
    vocabulary, made once; ``None`` where the run's own reduction is."""
    if KEPT not in run.extra:
        out = None
        if scopes_mod.for_run(run) is not None:
            from benchmarks.harness import spec

            vocabulary = deepened(
                run.extra.get("vocabulary", scopes_mod.ENSEMBLE_VOCABULARY),
                declared_parts())
            path = trace_mod.newest_xplane(str(spec.ROOT / ".bench_trace"))
            out = scopes_mod.reduce(
                scopes_mod.read_xplane(path, vocabulary))["scope_s"]
        run.extra[KEPT] = out
    return run.extra[KEPT]


def read(run, scope, part):
    batches = run.counters_slice.get("batches", 0)
    scope_s = part_seconds(run)
    if scope_s is None or not batches:
        return None
    s = scopes_mod.matching(scope_s, f"{scope}/{part}")
    if s is None:
        print(f"[bench] scopes: no device operation under part {part!r} of "
              f"scope {scope!r}", flush=True)
        return None
    return 1e3 * s / batches
