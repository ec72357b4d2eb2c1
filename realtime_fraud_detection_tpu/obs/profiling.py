"""Profiling: the program's one span primitive, and its compile ledger.

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

``CompileLedger`` keeps what JAX reports of its own compilations
(``jax.monitoring``): one record a phase (``trace``, ``lower``,
``compile``) with the program's name, the phase's wall-clock start and end,
whether the persistent cache held the program, and the span that was open
on the compiling thread (``caused_by``: ``"pack batch=7"``). There is one
a process, ``compile_ledger()``; it finds a ``SpanTimer``'s open spans
through the per-thread stacks the timers keep anyway, and no listener it
registers is on an event a cached ``jit`` call fires, so it costs nothing
while nothing compiles.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from realtime_fraud_detection_tpu.obs.scopes import (
    ANNOTATION_PREFIX,
    HOST_GC,
    JOB_COMPLETE,
)

__all__ = ["SpanTimer", "GcSpans", "CompileLedger", "compile_ledger",
           "interpolated_percentile"]

log = logging.getLogger(__name__)


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

    __slots__ = ("stack", "aggs", "timer", "__weakref__")

    def __init__(self, timer: "SpanTimer") -> None:
        self.stack: List["_Span"] = []
        self.aggs: Dict[str, _Agg] = {}
        self.timer = weakref.ref(timer)


# every live timer's state on this thread, oldest first: how the ledger
# finds the span a compilation ran under without a word on a span's path
_on_this_thread = threading.local()


def _innermost_open_span() -> Tuple[Optional["SpanTimer"], Optional["_Span"]]:
    """The innermost open span of the calling thread and its timer. Where
    two timers hold open spans on one thread, the one built later is the
    inner (a timer made inside another's span)."""
    for ref in reversed(getattr(_on_this_thread, "states", ())):
        state = ref()
        if state is not None and state.stack:
            return state.timer(), state.stack[-1]
    return None, None


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
        self._was_reset = False
        self._compile_mark: Dict[str, float] = {}
        compile_ledger()             # listening, once a process

    def _state(self) -> _ThreadState:
        try:
            return self._local.state
        except AttributeError:
            state = self._local.state = _ThreadState(self)
            with self._lock:
                self._states.append(state)
            refs = [r for r in getattr(_on_this_thread, "states", ())
                    if r() is not None]      # dead timers leave here
            refs.append(weakref.ref(state))
            _on_this_thread.states = refs
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
        # the ledger is the process's and keeps everything: a reset only
        # moves this timer's mark, and says its warm-up is over
        self._compile_mark = compile_ledger().totals()
        self._was_reset = True

    def under_traffic(self) -> bool:
        """Past its warm-up: ``reset()`` has been called (a benchmark's
        window opened) or a ``StreamJob`` has completed a batch. What
        compiles from then on stalls live traffic, and the ledger says so
        at WARNING."""
        if self._was_reset:
            return True
        with self._lock:
            states = list(self._states)
        return any(JOB_COMPLETE in state.aggs for state in states)

    def compile_stats(self, newest: int = 16) -> Dict[str, Any]:
        """The process's compile ledger as this timer sees it: totals by
        phase since the process started, the same ``since_reset`` (since
        start until the first ``reset()``), and the newest records."""
        ledger = compile_ledger()
        now = ledger.totals()
        mark = self._compile_mark
        return dict(
            _render_totals(now),
            since_reset=_render_totals(
                {k: v - mark.get(k, 0) for k, v in now.items()}),
            records=ledger.records()[-newest:])


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


# ---- the compile ledger -----------------------------------------------------
# what JAX 0.9 reports of a compilation (jax/_src/dispatch.py
# ``LogElapsedTimeContextManager``: a scalar with the start time where a
# phase opens, a time span where it closes; jax/_src/compiler.py and
# compilation_cache.py: an event for a hit and for a written miss, inside
# the backend-compile phase). Nothing else in JAX fires these listeners.
_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_OF = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
PHASES: Tuple[str, ...] = ("trace", "lower", "compile")


def _render_totals(flat: Dict[str, float]) -> Dict[str, Any]:
    return {
        "phases": {p: {"count": int(flat[p + "_n"]),
                       "seconds": flat[p + "_s"]} for p in PHASES},
        "programs": int(flat["compile_n"]),
        "cache_hits": int(flat["hits"]),
        "cache_misses": int(flat["misses"]),
        "dropped_records": int(flat["dropped"]),
    }


class _Phase:
    """One open phase on one thread."""

    __slots__ = ("phase", "program", "other_s", "nested")

    def __init__(self, phase: str, program: str):
        self.phase = phase
        self.program = program
        self.other_s = 0.0       # records closed inside: counted there
        self.nested: Dict[str, List[float]] = {}


class CompileLedger:
    """Every phase of every compilation the listeners are told of.

    A record: ``{"program", "phase", "start", "end", "cache", "caused_by"}``
    (``start`` / ``end`` are JAX's own ``time.time()`` stamps; ``cache`` is
    ``"hit"`` / ``"miss"`` on a ``compile`` record whose program the
    persistent cache held or took, else None), and where jitted functions
    were entered INSIDE it ``nested``: ``[times, seconds]`` by name. Every
    ``jax.numpy`` call of a traced body is such a function (thousands a
    program), so a nested trace is a count under its root and not a record
    of its own. JAX tells of each entry, traced anew or answered from its
    trace cache: a jitted kernel called by 24 layers reads ``[24, 0.5]``
    where its body is traced once, and 24 times its first entry's seconds
    where every layer traces it again. A ``lower`` or ``compile`` inside a
    trace (an eager operation on a constant) is a compilation like any
    other and keeps its record.

    ``seconds`` of a phase is self time, as a span's: a record's duration
    less the records that closed inside it, so the phases sum to the union
    of their intervals on a thread and nothing is counted twice. Totals are
    running and exact; the records are the newest ``max_records``.
    """

    def __init__(self, max_records: int = 4096):
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=max_records)
        self._totals: Dict[str, float] = dict(
            {p + "_n": 0 for p in PHASES}, **{p + "_s": 0.0 for p in PHASES},
            hits=0, misses=0, dropped=0)
        self._local = threading.local()

    # ---- the three listeners
    def on_open(self, event: str, start: float, fun_name: str = "",
                **_: Any) -> None:
        phase = _PHASE_OF.get(event)
        if phase is not None:
            self._open().append(_Phase(phase, fun_name))
            if phase == "compile":
                self._local.cache = None

    def on_cache(self, event: str, **_: Any) -> None:
        cache = _CACHE_OF.get(event)
        if cache is not None:
            self._local.cache = cache    # the open compile phase takes it

    def on_close(self, event: str, start: float, end: float,
                 fun_name: str = "", **_: Any) -> None:
        phase = _PHASE_OF.get(event)
        if phase is None:
            return
        stack = self._open()
        if stack and stack[-1].phase == phase \
                and stack[-1].program == fun_name:
            this = stack.pop()
        else:                    # opened before the ledger listened
            this = _Phase(phase, fun_name)
        seconds = end - start
        if stack and phase == "trace":
            # a jitted function traced inside another phase: a count
            # under the root, and the root's own time
            stack[-1].other_s += this.other_s
            tally = stack[0].nested.setdefault(fun_name, [0, 0.0])
            tally[0] += 1
            tally[1] += seconds
            return
        if stack:
            stack[-1].other_s += seconds
        self._record(this, start, end, seconds - this.other_s)

    def _open(self) -> List[_Phase]:
        try:
            return self._local.open
        except AttributeError:
            stack = self._local.open = []
            return stack

    def _record(self, this: _Phase, start: float, end: float,
                self_s: float) -> None:
        local = self._local
        phase = this.phase
        timer, span = _innermost_open_span()
        caused_by = "" if span is None else span.name + "".join(
            f" {k}={v}" for k, v in span.ids.items())
        record = {"program": this.program, "phase": phase, "start": start,
                  "end": end, "cache": None, "caused_by": caused_by}
        if this.nested:
            record["nested"] = this.nested
        # what this thread has traced and lowered since its last program
        spent = getattr(local, "spent", None)
        if spent is None:
            spent = local.spent = {}
        spent[phase] = spent.get(phase, 0.0) + self_s
        if phase == "compile":
            record["cache"] = getattr(local, "cache", None)
            local.spent = None
        with self._lock:
            totals, records = self._totals, self._records
            totals["dropped"] += len(records) == records.maxlen
            records.append(record)
            totals[phase + "_n"] += 1
            totals[phase + "_s"] += self_s
            if record["cache"] is not None:
                totals["hits" if record["cache"] == "hit"
                       else "misses"] += 1
        if phase == "compile" and timer is not None \
                and timer.under_traffic():
            log.warning(
                "compiled under traffic: %s (%s; persistent cache %s) "
                "caused by %s: warm the buckets the deployment will see",
                this.program,
                ", ".join(f"{p} {spent[p]:.3f} s" for p in PHASES
                          if p in spent),
                record["cache"] or "not used", caused_by)

    # ---- what it shows
    def totals(self) -> Dict[str, float]:
        """Running totals since the process started, flat: ``<phase>_n``
        records and ``<phase>_s`` self seconds of each phase, cache
        ``hits`` / ``misses``, and ``dropped`` (records the cap let go)."""
        with self._lock:
            return dict(self._totals)

    def records(self) -> List[Dict[str, Any]]:
        """The newest ``max_records`` records, oldest first."""
        with self._lock:
            return list(self._records)


_ledger: Optional[CompileLedger] = None
_ledger_lock = threading.Lock()


def compile_ledger() -> CompileLedger:
    """The process's one ledger; the first call registers its listeners
    with ``jax.monitoring``, whose lists are global and never shrink."""
    global _ledger
    if _ledger is None:
        with _ledger_lock:
            if _ledger is None:
                from jax import monitoring

                ledger = CompileLedger()
                monitoring.register_scalar_listener(ledger.on_open)
                monitoring.register_event_listener(ledger.on_cache)
                monitoring.register_event_time_span_listener(
                    ledger.on_close)
                _ledger = ledger
    return _ledger
