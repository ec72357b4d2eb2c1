"""From a profiler trace to the device program's branches and kernels,
and to the program's own host spans.

The program names its work itself: ``jax.named_scope`` on every branch of
the fused scoring program and on the text branch's kernels, and a
``TraceAnnotation`` named ``rtfd:<span>`` around every host stage of a
microbatch (``realtime_fraud_detection_tpu/obs/scopes.py`` holds both lists).
Which device scopes a configuration's program writes is its builder's
``VOCABULARY`` (``configs/<builder>.py``): a nested mapping, branch ->
parts -> their parts, to whatever depth the program nests them, in which a
``*`` in a name stands for one component's digits (``layer*``). The strings
are written again on this side so that this file also runs against a
program that has none of them — it then finds nothing, says so, and every
reader built on it returns ``None``. ``BRANCHES`` ... ``LAYER_PARTS`` below
are the five-branch ensemble's, which ``ENSEMBLE_VOCABULARY`` nests and the
default builder names.

Where the names are in a v5e trace under JAX 0.9.0 (looked at by hand, PR
23): each event of a device plane's ``XLA Ops`` line is named by its whole
HLO line, and the HLO ``op_name`` — ``jit(_score_fused_packed_impl)/text/
layer0/ffn/dot_general`` — is the ``tf_op`` stat of the event's METADATA,
which ``jax.profiler.ProfileData`` does not hand out (it gives the event's
own stats: offset, duration). There is no name-scope line. So the times
come from ``ProfileData`` and the ``op_name`` of each event name from the
file itself, read as protobuf wire format (``XSpace.planes[].event_metadata
[].stats[]``; the four field numbers used are those of
``tsl/profiler/protobuf/xplane.proto``). A fusion carries one ``op_name``,
its root's: work of another scope fused into it is counted with the root.

``reduce(events, ...)`` works on plain tuples and is checked on the CPU
against ``tests/fixtures/scope_events.json``:

- device seconds per scope path: the UNION of the intervals of the scope's
  operations (``XLA Ops`` line) clipped to the window, summed over device
  planes. A path's time includes its children's (``text`` holds
  ``text/layer0/ffn``); ``unscoped`` is the operations whose ``op_name``
  starts with no branch of the configuration's vocabulary;
- the window is the ``bench:slice`` annotation the harness writes;
- host spans: every ``rtfd:`` annotation inside the window, with its self
  time (duration minus what the spans nested in it on the same thread
  cover);
- idle gaps (maximal intervals of the window with no operation on a
  device, as ``harness/trace.py`` cuts them) each go to the INNERMOST
  ``rtfd:`` span covering at least half of the gap — a collection
  (``rtfd:host.gc``, which stops every thread) before any span of the
  thread it interrupted.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict, Iterable, Iterator, List, Mapping, Optional, \
    Sequence, Tuple

from benchmarks.harness import trace as trace_mod

# the five-branch ensemble's scope names, as the program writes them
# (obs/scopes.py)
BRANCHES: Tuple[str, ...] = ("trees", "lstm", "text", "gnn", "iforest",
                             "rules", "blend", "unpack", "repack")
TEXT = "text"
LAYER = "layer*"
TEXT_PARTS: Tuple[str, ...] = ("embed", "head")
LAYER_PARTS: Tuple[str, ...] = ("attn_proj", "attn_core", "ffn", "ln")
UNSCOPED = "unscoped"

PREFIX = "rtfd:"
GC_SPAN = "host.gc"
SCOPE_STAT = "tf_op"
WINDOW = trace_mod.ANNOTATION_PREFIX + trace_mod.WINDOW_ANNOTATION

# (plane, line, name, start_ns, duration_ns, scope path or "")
Event = Tuple[str, str, str, float, float, str]
# {scope name: {the scope names written directly under it: {...}}}
Vocabulary = Mapping[str, "Vocabulary"]


@functools.lru_cache(maxsize=None)
def digits_re(pattern: str) -> "re.Pattern[str]":
    """``text/layer*/ffn`` -> a whole-string match in which ``*`` is one
    component's digits."""
    return re.compile(
        "^" + re.escape(pattern).replace(r"\*", r"\d+") + "$")


# tier-1 tests/test_scopes.py holds it to the program's ``layer<i>``
LAYER_RE = digits_re(LAYER)

ENSEMBLE_VOCABULARY: Vocabulary = {
    **{branch: {} for branch in BRANCHES},
    TEXT: {**{part: {} for part in TEXT_PARTS},
           LAYER: {part: {} for part in LAYER_PARTS}},
}


def _below(level: Vocabulary, name: str) -> Optional[Vocabulary]:
    """The vocabulary under ``name`` at this level; ``None`` where the
    level does not know the name."""
    if name in level:
        return level[name]
    for key, sub in level.items():
        if "*" in key and digits_re(key).match(name):
            return sub
    return None


def scope_path(op_name: str,
               vocabulary: Vocabulary = ENSEMBLE_VOCABULARY) -> str:
    """``jit(f)/text/layer0/ffn/dot_general:`` -> ``text/layer0/ffn``: every
    leading component that ``vocabulary`` knows at its depth, to whatever
    depth it goes; "" where the name starts with no branch of it.
    ``jit(...)`` components (the program's own name, and inner jitted
    functions) are skipped."""
    path: List[str] = []
    level: Optional[Vocabulary] = vocabulary
    for part in op_name.rstrip(":").split("/"):
        if not part or part.startswith("jit("):
            continue
        level = _below(level, part)
        if level is None:
            break
        path.append(part)
    return "/".join(path)


# ---- the file itself: protobuf wire format, the few fields needed

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed-width value, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield field, wire, int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")


def _map_value(entry: memoryview) -> Optional[memoryview]:
    for field, wire, val in _fields(entry):
        if field == 2 and wire == 2:
            return val
    return None


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: op_name}}`` from the ``tf_op`` stat of
    each event's metadata. XPlane: name 2, event_metadata 4, stat_metadata
    5; XEventMetadata: name 2, stats 5; XStatMetadata: id 1, name 2; XStat:
    metadata_id 1, str_value 5, ref_value 7 (a stat_metadata id whose name
    is the value)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for pf, pw, val in _fields(plane):
            if pf == 2 and pw == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pf == 4 and pw == 2:
                events.append(val)
            elif pf == 5 and pw == 2:
                meta = _map_value(val)
                if meta is None:
                    continue
                sid, sname = 0, ""
                for sf, sw, sv in _fields(meta):
                    if sf == 1 and sw == 0:
                        sid = sv
                    elif sf == 2 and sw == 2:
                        sname = bytes(sv).decode("utf-8", "replace")
                stat_names[sid] = sname
        if not name.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            continue
        wanted = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        names = out.setdefault(name, {})
        for entry in events:
            meta = _map_value(entry)
            if meta is None:
                continue
            ev_name, op = "", None
            for mf, mw, mv in _fields(meta):
                if mf == 2 and mw == 2:
                    ev_name = bytes(mv).decode("utf-8", "replace")
                elif mf == 5 and mw == 2:
                    sid, sval = 0, None
                    for sf, sw, sv in _fields(mv):
                        if sf == 1 and sw == 0:
                            sid = sv
                        elif sf == 5 and sw == 2:
                            sval = bytes(sv).decode("utf-8", "replace")
                        elif sf == 7 and sw == 0:
                            sval = stat_names.get(sv, "")
                    if sid in wanted and sval is not None:
                        op = sval
            if op is not None:
                names[ev_name] = op
    return out


def read_xplane(path: str, vocabulary: Vocabulary = ENSEMBLE_VOCABULARY
                ) -> List[Event]:
    """Device planes' ``XLA Ops`` events with their scope path under
    ``vocabulary``, and the host planes' ``rtfd:`` annotations and
    ``bench:slice``."""
    import jax

    ops = {plane: {name: scope_path(op, vocabulary)
                   for name, op in names.items()}
           for plane, names in op_names(path).items()}
    out: List[Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        paths = ops.get(plane.name, {})
        for line in plane.lines:
            if device and line.name != trace_mod.OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if device:
                    # named by the instruction alone: the scope says the rest
                    out.append((plane.name, line.name, name.split(" ", 1)[0],
                                float(ev.start_ns), float(ev.duration_ns),
                                paths.get(name, "")))
                elif name.startswith(PREFIX) or name == WINDOW:
                    out.append((plane.name, line.name, name,
                                float(ev.start_ns), float(ev.duration_ns),
                                ""))
    return out


# ---- the arithmetic

def _length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in trace_mod._union(intervals))


def _self_times(spans: List[Tuple[float, float, str]]
                ) -> List[Tuple[float, float, str, int, float]]:
    """Spans of ONE thread, ``(start, end, name)`` -> the same with nesting
    depth and self time: duration minus the spans directly inside."""
    out = []
    stack: List[List[Any]] = []       # [start, end, name, depth, children]
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and a >= stack[-1][1]:
            s = stack.pop()
            out.append((s[0], s[1], s[2], s[3], (s[1] - s[0]) - s[4]))
        if stack:
            stack[-1][4] += min(b, stack[-1][1]) - a
        stack.append([a, b, name, len(stack), 0.0])
    while stack:
        s = stack.pop()
        out.append((s[0], s[1], s[2], s[3], (s[1] - s[0]) - s[4]))
    return out


def reduce(events: Sequence[Event],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Optional[Dict[str, Any]]:
    """See the module docstring; seconds throughout. ``None`` where the
    trace has no device operation at all (a CPU rehearsal).

    ``scope_s`` ``{path: seconds}`` with ``unscoped``; ``busy_s`` (summed
    over devices); ``scoped`` (whether any operation carried a scope);
    ``host_spans`` ``{name: {count, total_s, self_s}}``; ``idle_gaps``
    ``[[span name, seconds], ...]`` longest first, at most ``top``;
    ``gap_s`` and ``gap_uncovered_s`` (gaps of at least
    ``trace.MIN_GAP_NS``)."""
    dev: Dict[str, List[Tuple[float, float, str]]] = {}
    host: Dict[Tuple[str, str], List[Tuple[float, float, str]]] = {}
    marker = None
    for plane, line, name, start, dur, path in events:
        if plane.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            dev.setdefault(plane, []).append((start, start + dur, path))
        elif name == WINDOW:
            marker = (start, start + dur)
        elif name.startswith(PREFIX):
            host.setdefault((plane, line), []).append(
                (start, start + dur, name[len(PREFIX):]))
    if not dev:
        return None
    if window is None:
        if marker is None:
            raise ValueError("no window given and no bench:slice "
                             "annotation to take one from")
        window = marker
    w0, w1 = window

    scope_iv: Dict[str, List[Tuple[float, float]]] = {}
    busy_iv: Dict[str, List[Tuple[float, float]]] = {}
    for plane, ops in dev.items():
        for a, b, path in ops:
            if b <= w0 or a >= w1:
                continue
            iv = (max(a, w0), min(b, w1))
            busy_iv.setdefault(plane, []).append(iv)
            parts = path.split("/") if path else [UNSCOPED]
            for depth in range(1, len(parts) + 1):
                key = plane, "/".join(parts[:depth])
                scope_iv.setdefault(key, []).append(iv)
    scope_s: Dict[str, float] = {}
    for (plane, path), ivs in scope_iv.items():
        scope_s[path] = scope_s.get(path, 0.0) + _length(ivs) / 1e9
    busy = {plane: trace_mod._union(ivs) for plane, ivs in busy_iv.items()}

    spans: List[Tuple[float, float, str, int, float]] = []
    for thread_spans in host.values():
        spans += [s for s in _self_times(thread_spans)
                  if s[1] > w0 and s[0] < w1]
    host_spans: Dict[str, Dict[str, float]] = {}
    for a, b, name, _depth, self_ns in spans:
        h = host_spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0})
        h["count"] += 1
        h["total_s"] += (b - a) / 1e9
        h["self_s"] += self_ns / 1e9
    # a collection first, then the deepest span, then the shortest
    by_rank = sorted(spans, key=lambda s: (s[2] != GC_SPAN, -s[3],
                                           s[1] - s[0]))
    gap_time: Dict[str, float] = {}
    gap_s = uncovered_s = 0.0
    for plane in dev:
        cursor = w0
        for a, b in busy.get(plane, []) + [(w1, w1)]:
            if a - cursor >= trace_mod.MIN_GAP_NS:
                gap, best = a - cursor, trace_mod.UNCOVERED
                for s0, s1, name, _d, _s in by_rank:
                    if min(a, s1) - max(cursor, s0) >= 0.5 * gap:
                        best = PREFIX + name
                        break
                gap_time[best] = gap_time.get(best, 0.0) + gap
                gap_s += gap
                if best is trace_mod.UNCOVERED:
                    uncovered_s += gap
            cursor = max(cursor, b)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(b - a for ivs in busy.values() for a, b in ivs) / 1e9,
        "scoped": any(p != UNSCOPED for p in scope_s),
        "scope_s": scope_s,
        "host_spans": host_spans,
        "idle_gaps": [[n, s / 1e9] for n, s in sorted(
            gap_time.items(), key=lambda kv: -kv[1])[:top]],
        "gap_s": gap_s / 1e9,
        "gap_uncovered_s": uncovered_s / 1e9,
    }


def matching(scope_s: Dict[str, float], pattern: str) -> Optional[float]:
    """Seconds under the scope paths matching ``pattern`` (``*`` stands for
    one component's digits, as in ``text/layer*/ffn``); ``None`` where no
    operation carried such a path."""
    rx = digits_re(pattern)
    found = [s for p, s in scope_s.items() if rx.match(p)]
    return sum(found) if found else None


# ---- one reduction per run, shared by the readers

def for_run(run: Any) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace, made once and kept on
    ``run.extra``; ``None`` in an untraced run, where the trace has no
    device operation, or where no operation carries a scope (a program
    without the named scopes) — each said once on the run's log."""
    if "scope_trace" in run.extra:
        return run.extra["scope_trace"]
    out = None
    if run.trace is not None:
        from benchmarks.harness import spec

        path = trace_mod.newest_xplane(str(spec.ROOT / ".bench_trace"))
        out = reduce(read_xplane(path, run.extra.get(
            "vocabulary", ENSEMBLE_VOCABULARY)))
        if out is None:
            print("[bench] scopes: the trace has no device operation; the "
                  "device_trace readers of the named scopes are left out",
                  flush=True)
        else:
            _log(out)
            if not out["scoped"]:
                print("[bench] scopes: no device operation carries a named "
                      "scope (a program without them); their readers are "
                      "left out", flush=True)
                out = None
    run.extra["scope_trace"] = out
    return out


def _log(out: Dict[str, Any]) -> None:
    ms = {p: round(1e3 * s, 3) for p, s in sorted(out["scope_s"].items())
          if p.count("/") < 2}
    print(f"[bench] scopes: device seconds in the slice by branch (ms): {ms}; "
          f"busy {out['busy_s']:.4f} s", flush=True)
    print(f"[bench] scopes: idle gaps by the program's own spans: "
          f"{out['idle_gaps']}; {out['gap_uncovered_s']:.4f} s of "
          f"{out['gap_s']:.4f} s under no rtfd: span", flush=True)
    print(f"[bench] scopes: rtfd: spans in the slice (count, total s, self "
          f"s): " + str({n: [h['count'], round(h['total_s'], 4),
                             round(h['self_s'], 4)]
                         for n, h in sorted(out['host_spans'].items())}),
          flush=True)


def scope_seconds(run: Any, pattern: str) -> Optional[float]:
    """Device seconds in the traced slice under ``pattern``; ``None`` with a
    line on the log naming the scope that had no operation."""
    red = for_run(run)
    if red is None:
        return None
    s = matching(red["scope_s"], pattern)
    if s is None:
        print(f"[bench] scopes: no device operation under scope "
              f"{pattern!r}", flush=True)
    return s
