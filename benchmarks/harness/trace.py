"""From a profiler trace to numbers: device busy, idle, gaps, top ops.

Two steps, so the arithmetic can be checked without a chip. ``read_xplane``
flattens the ``.xplane.pb`` the JAX profiler wrote into plain events
``(plane, line, name, start_ns, duration_ns)``; ``reduce`` works on such
events only, and is checked against the small recorded list in
``tests/fixtures/``.

What counts as what:

- a device is a plane named ``/device:TPU:<n>`` (any ``/device:`` plane);
  its operations are the events of its ``XLA Ops`` line — where a plane has
  no such line, every event of the plane;
- device busy time is the length of the UNION of those events' intervals
  clipped to the window: operations that overlap count once;
- the window is the span of the ``bench:slice`` annotation the harness
  writes around the traced part of the run (or given by the caller), so
  idle time at the window's edges counts;
- an idle gap is a maximal interval of the window in which no operation
  ran on that device. Each gap is attributed to the host annotation
  (``bench:<name>``, written by ``annotate.py`` on the trace's own clock)
  that covers most of it, the innermost first; a gap no annotation covers
  is ``(no annotation: poll / wait for input)``. Gaps shorter than
  ``MIN_GAP_NS`` (the device between two operations of one program) are
  idle time too, summed under one name of their own.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur
ANNOTATION_PREFIX = "bench:"
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
UNCOVERED = "(no annotation: poll / wait for input)"
WINDOW_ANNOTATION = "slice"
MIN_GAP_NS = 20_000.0
BETWEEN_OPS = "(gaps under 20 us between device operations)"


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO line. Keep
    what identifies it to a reader: the result name, the result shape, the
    operation, and the first model parameter among its operands (which
    says which layer's matmul a fusion is)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    shape, _, rest = rhs.partition(" ")
    if shape.startswith("("):           # tuple result: up to its close
        end = rhs.find(") ")
        shape, rest = rhs[:end + 1], rhs[end + 2:]
    op = rest.split("(", 1)[0]
    hint = re.search(r"%(models_[A-Za-z0-9_]+?)(?:\.\d+)?[,)]", rest)
    label = f"{lhs} {op} {shape[:60]}"
    if hint:
        label += f" {hint.group(1)}"
    return label[:160]


def read_xplane(path: str) -> List[Event]:
    """Device planes whole (operation names shortened by ``short_name``),
    host planes only the ``bench:`` annotations."""
    import jax

    out: List[Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if device or name.startswith(ANNOTATION_PREFIX):
                    out.append((plane.name, line.name, short_name(name),
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _overlap(a: float, b: float, spans: Sequence[Tuple[float, float]]
             ) -> float:
    """Length of [a, b] covered by ``spans``: sorted by start, and spans of
    one name do not nest (each is one call of one method on one thread)."""
    i = max(0, bisect.bisect_right(spans, (a, float("inf"))) - 1)
    total = 0.0
    while i < len(spans) and spans[i][0] < b:
        total += max(0.0, min(b, spans[i][1]) - max(a, spans[i][0]))
        i += 1
    return total


def reduce(events: Sequence[Event],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict[str, object]:
    """See the module docstring. Times in the result are seconds.

    Returns ``window_s``, ``busy_s`` (mean over devices), ``per_device``
    ``{plane: busy_s}``, ``idle_share``, ``device_ops`` and ``idle_gaps``
    (``[[name, seconds], ...]``, summed over devices, longest first, at
    most ``top``) and ``annotations`` ``{name: [count, seconds]}`` inside
    the window.
    """
    ann: Dict[str, List[Tuple[float, float]]] = {}
    dev: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = {}
    for plane, line, name, start, dur in events:
        if name.startswith(ANNOTATION_PREFIX):
            ann.setdefault(name[len(ANNOTATION_PREFIX):], []).append(
                (start, start + dur))
        elif plane.startswith(DEVICE_PLANE_PREFIX):
            dev.setdefault(plane, {}).setdefault(line, []).append(
                (name, start, start + dur))
    marker = ann.pop(WINDOW_ANNOTATION, None)
    if window is None:
        if not marker:
            raise ValueError("no window given and no bench:slice "
                             "annotation to take one from")
        window = marker[0]
    for spans in ann.values():
        spans.sort()
    w0, w1 = window
    if not w1 > w0:
        raise ValueError(f"empty window {window}")
    # innermost first: the annotation with the shorter mean span wins ties
    order = sorted(ann, key=lambda n: sum(b - a for a, b in ann[n])
                   / len(ann[n]))

    per_device: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    gap_time: Dict[str, float] = {}
    for plane, lines in sorted(dev.items()):
        ops = lines.get(OPS_LINE)
        if ops is None:
            ops = [e for evs in lines.values() for e in evs]
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if b > w0 and a < w1]
        for n, a, b in clipped:
            op_time[n] = op_time.get(n, 0.0) + (b - a)
        busy = _union((a, b) for _, a, b in clipped)
        per_device[plane] = sum(b - a for a, b in busy) / 1e9
        cursor = w0
        for a, b in busy + [(w1, w1)]:
            if a > cursor:
                best = BETWEEN_OPS if a - cursor < MIN_GAP_NS else UNCOVERED
                if best is UNCOVERED:
                    for name in order:
                        if _overlap(cursor, a, ann[name]) \
                                >= 0.5 * (a - cursor):
                            best = name
                            break
                gap_time[best] = gap_time.get(best, 0.0) + (a - cursor)
            cursor = max(cursor, b)
    if not per_device:
        raise ValueError("the trace has no device plane")
    window_s = (w1 - w0) / 1e9
    busy_s = sum(per_device.values()) / len(per_device)

    def ranked(d: Dict[str, float]) -> List[List[object]]:
        return [[n, s / 1e9] for n, s in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "per_device": per_device,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gap_time),
        "annotations": {
            n: [sum(1 for a, b in spans if b > w0 and a < w1),
                _overlap(w0, w1, spans) / 1e9]
            for n, spans in ann.items()},
    }
