"""Profiling: the program's one span primitive.

Replaces the reference's coarse timing-threaded-through-results approach
(SURVEY.md §5.1: per-request processing_time_ms at main.py:160-169, per-model
timing at ensemble_predictor.py:185-215).

``SpanTimer.span`` is the one way the program marks a host stage. One
``with`` does three things: it adds the span to the timer's aggregates
(count / total / self time / parent / p50 / p99 — ``host_stats()``), it is a
``jax.profiler.TraceAnnotation`` named ``rtfd:<name>`` (a host span on the
profiler's own clock, beside the device's operations, whenever a profiler
session is live and a no-op otherwise), and it puts the batch-granular
stage marks on a ``TraceBatch`` where one is passed (obs/tracing.py).
``GcSpans`` does the same for the cyclic collector's runs.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from realtime_fraud_detection_tpu.obs.scopes import ANNOTATION_PREFIX, HOST_GC

__all__ = ["SpanTimer", "GcSpans", "interpolated_percentile"]


def interpolated_percentile(xs_sorted, q: float) -> float:
    """Linear-interpolated percentile over a SORTED sample (numpy's
    default convention), unit-agnostic. The one implementation shared by
    SpanTimer.stats and the tracing plane's breakdown — raw index
    selection made small-n tails dishonest (p99 on n<100 was simply the
    max)."""
    pos = q * (len(xs_sorted) - 1)
    lo = int(pos)
    frac = pos - lo
    if lo + 1 >= len(xs_sorted):
        return float(xs_sorted[-1])
    return float(xs_sorted[lo] + (xs_sorted[lo + 1] - xs_sorted[lo]) * frac)


def _trace_annotation():
    import jax

    return jax.profiler.TraceAnnotation


class _Agg:
    """One span name's running totals and its newest samples, as one
    thread wrote them."""

    __slots__ = ("count", "total_s", "self_s", "parent", "samples")

    def __init__(self, max_samples: int):
        self.count = 0
        self.total_s = 0.0
        self.self_s = 0.0
        self.parent = ""
        self.samples: deque = deque(maxlen=max_samples)

    def add(self, seconds: float, self_s: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.self_s += self_s
        self.samples.append(seconds)


class _ThreadState:
    """What one thread owns of a timer: its stack of open spans and the
    aggregates it alone writes (so closing a span takes no lock)."""

    __slots__ = ("stack", "aggs")

    def __init__(self) -> None:
        self.stack: List["_Span"] = []
        self.aggs: Dict[str, _Agg] = {}


class _Span:
    """One open span: a plain context manager, one per ``with``."""

    __slots__ = ("_timer", "name", "ids", "_trace", "_then", "_ann", "_t0",
                 "_children_s", "_parent", "_state")

    def __init__(self, timer: "SpanTimer", name: str, trace: Any,
                 then: Optional[str], ids: Dict[str, Any]):
        self._timer = timer
        self.name = name
        self.ids = ids
        self._trace = trace
        self._then = then
        self._children_s = 0.0

    def __enter__(self) -> "_Span":
        timer = self._timer
        state = self._state = timer._state()
        stack = state.stack
        parent = self._parent = stack[-1] if stack else None
        if parent is not None and parent.ids:
            # the spans of one microbatch share its identifiers: a child
            # opened without any takes its parent's
            self.ids = {**parent.ids, **self.ids} if self.ids \
                else parent.ids
        trace = self._trace
        if trace is not None and (not trace.marks
                                  or trace.marks[-1][0] != self.name):
            # a stage a closing span already opened (``then``) stays open
            trace.mark(self.name)
        stack.append(self)
        ann = self._ann = timer._annotation(
            ANNOTATION_PREFIX + self.name, **self.ids)
        ann.__enter__()
        self._t0 = timer._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        timer = self._timer
        dt = timer._clock() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        state = self._state
        state.stack.pop()
        parent = self._parent
        if self._then is not None and self._trace is not None:
            self._trace.mark(self._then)
        name = self.name
        agg = state.aggs.get(name)
        if agg is None:
            agg = state.aggs[name] = _Agg(timer._max)
        agg.add(dt, dt - self._children_s)
        if parent is not None:
            parent._children_s += dt
            agg.parent = parent.name


class SpanTimer:
    """The program's host spans: aggregates, profiler annotations and
    ``TraceBatch`` marks from one call (module docstring).

    A span states its parent: the timer keeps the stack of open spans per
    thread, and reports for each name ``self_s`` (its duration minus the
    part its children cover) beside ``total_s``, and the name of the span
    that caused it. ``count`` / ``total_s`` / ``self_s`` are running totals
    since ``reset()``; the percentiles are over the newest ``max_samples``.
    Each thread writes aggregates of its own and ``stats()`` merges them,
    so the hot path takes no lock.

    ``annotation`` is the class opened around each span, called as
    ``annotation("rtfd:<name>", **ids)``; the default is
    ``jax.profiler.TraceAnnotation``, which costs well under a microsecond
    while no profiler session is live, so there is no switch.
    """

    def __init__(self, clock=time.perf_counter, max_samples: int = 10_000,
                 annotation=None):
        self._clock = clock
        self._lock = threading.Lock()    # guards _states, not the hot path
        self._max = max_samples      # per-span cap: hot-path safe, O(1) memory
        self._annotation = annotation if annotation is not None \
            else _trace_annotation()
        self._local = threading.local()
        self._states: List[_ThreadState] = []

    def _state(self) -> _ThreadState:
        try:
            return self._local.state
        except AttributeError:
            state = self._local.state = _ThreadState()
            with self._lock:
                self._states.append(state)
            return state

    def span(self, name: str, trace: Any = None, then: Optional[str] = None,
             **ids: Any) -> _Span:
        """``with timer.span("assemble", batch=7): ...``

        ``trace`` (an ``obs.tracing.TraceBatch`` or None) gets the stage
        mark ``name`` when the span opens and, with ``then``, the mark of
        the stage that begins where this span ends (the tracer's
        ``device_wait`` begins where ``dispatch`` returns). ``ids`` become
        the annotation's arguments (``batch=``, ``replica=``); a span
        opened inside another takes that one's too.
        """
        return _Span(self, name, trace, then, ids)

    def record(self, name: str, seconds: float) -> None:
        """A duration measured elsewhere, as a span with no parent."""
        aggs = self._state().aggs
        agg = aggs.get(name)
        if agg is None:
            agg = aggs[name] = _Agg(self._max)
        agg.add(seconds, seconds)

    def stats(self, name: Optional[str] = None) -> Dict[str, Dict[str, Any]]:
        # the sort and the percentile math run on copies: a stats() reader
        # never stalls a thread that is closing spans
        with self._lock:
            states = list(self._states)
        merged: Dict[str, list] = {}
        for state in states:
            for n, a in list(state.aggs.items()):
                if name is not None and n != name:
                    continue
                m = merged.setdefault(n, [0, 0.0, 0.0, "", []])
                m[0] += a.count
                m[1] += a.total_s
                m[2] += a.self_s
                m[3] = a.parent or m[3]
                m[4].extend(a.samples)
        out: Dict[str, Dict[str, Any]] = {}
        for n, (count, total_s, self_s, parent, xs) in merged.items():
            if not xs:
                continue
            xs.sort()
            out[n] = {
                "count": count,
                "total_s": total_s,
                "self_s": self_s,
                "parent": parent,
                "mean_ms": 1e3 * total_s / count,
                "p50_ms": 1e3 * interpolated_percentile(xs, 0.50),
                "p99_ms": 1e3 * interpolated_percentile(xs, 0.99),
                "max_ms": 1e3 * xs[-1],
            }
        return out

    def reset(self) -> None:
        with self._lock:
            states = list(self._states)
        for state in states:
            state.aggs.clear()


class GcSpans:
    """The cyclic collector's runs as ``rtfd:host.gc`` annotations with
    ``generation=``, and as count / seconds / longest pause. Live inside a
    ``with`` (``gc.callbacks``); every thread of the process stands still
    during a collection, so an idle gap under one belongs to it and not to
    whatever span it interrupted."""

    def __init__(self, clock=time.perf_counter, annotation=None):
        self._clock = clock
        self._annotation = annotation if annotation is not None \
            else _trace_annotation()
        self._label = ANNOTATION_PREFIX + HOST_GC
        self._open: Optional[Any] = None
        self._t0 = 0.0
        self.count = 0
        self.seconds = 0.0
        self.longest_s = 0.0

    def _on(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._open = self._annotation(
                self._label, generation=info.get("generation", -1))
            self._open.__enter__()
            self._t0 = self._clock()
        elif self._open is not None:
            took = self._clock() - self._t0
            self._open.__exit__(None, None, None)
            self._open = None
            self.count += 1
            self.seconds += took
            self.longest_s = max(self.longest_s, took)

    def __enter__(self) -> "GcSpans":
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on)

    def snapshot(self) -> Dict[str, Any]:
        return {"count": self.count, "seconds": self.seconds,
                "longest_ms": 1e3 * self.longest_s}
