"""Offer the load and keep the books: the two ways a cell is driven.

``open_loop``: a producer wakes every millisecond and submits the
pre-built events now due through ``IngressGateway`` while the main thread
sits in ``job.run_for``; it never waits for the job. It runs in a process
of its own (``producer_main.py``; ``RemoteProducer`` here is its handle)
and reaches the job's broker over ``stream/netbroker.py``. How late it ran
is recorded per event and reported as a metric.

``backlog``: every event is produced to the broker before the job starts
(gateway bypassed); the job drains it for the whole window.

Both leave a ``Run``: per-event due / submitted / ingested / emitted
times joined on ``transaction_id``, counter deltas, the program's host
spans and, in the traced run, the trace summary. Readers take metrics from
it; nothing here knows a metric's name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmarks.harness import correct
from benchmarks.harness.events import seq_of

TRACE_SLICE_S = 3.0     # the profiler runs over the window's last seconds


@dataclasses.dataclass
class Run:
    """What one run measured. Times are ``time.time()`` seconds; per-event
    arrays are indexed by the event's position in the stream."""

    mode: str
    seconds: float                  # --seconds
    counted_s: float                # part of the window the counts cover
    t_open: float
    t_count_end: float              # t_open + counted_s
    t_count_snap: float             # when its counters were read
    grace_s: float
    budget_ms: float
    due: np.ndarray
    submitted: np.ndarray           # NaN where not submitted (backlog: due)
    ingested: np.ndarray            # Record.timestamp on the input topic
    emitted: np.ndarray             # Record.timestamp on predictions; NaN
    marked_failed: np.ndarray       # emitted with an error or shed marker
    bad_outputs: int                # emitted, not finite / off the ladder
    duplicates: int                 # second records of one transaction_id
    gateway_dropped: int
    counters: Dict[str, int]        # delta over the counted part
    counters_slice: Dict[str, int]  # delta over the traced slice
    close_reasons: Dict[str, int]
    lag_start: int
    lag_end: int
    stages: Dict[str, Dict[str, float]]   # host_stats()["stages"], counted
    bench_spans: Dict[str, List[float]]   # annotate.Spans.totals, counted
    pool_completed: Optional[List[int]]   # per device, counted part
    token_cache: Dict[str, int]
    tracer: Any = None
    trace: Optional[Dict[str, Any]] = None    # harness.trace.reduce(...)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # ---- views every latency reader shares
    def in_window(self) -> np.ndarray:
        """Events attempted in the counted part: due inside it (open loop),
        or emitted inside it / never emitted though polled (backlog)."""
        if self.mode == "open_loop":
            return (self.due >= self.t_open) & (self.due < self.t_count_end)
        return (self.emitted >= self.t_open) & (self.emitted
                                                < self.t_count_end)

    def failed(self) -> np.ndarray:
        """Failed among ``in_window()``: error- or shed-marked, or (open
        loop) not emitted within the grace after the window."""
        w = self.in_window()
        late = ~(self.emitted <= self.t_open + self.seconds + self.grace_s)
        if self.mode != "open_loop":
            late = np.zeros_like(w)
        return w & (self.marked_failed | late)


class OpenLoopProducer(threading.Thread):
    """Submits ``events[i]`` at ``due[i]`` (wall clock), in order; on a full
    ring it tries again at the next wake and the lateness shows."""

    def __init__(self, submit: Callable[[Dict[str, Any]], bool],
                 events: Sequence[Dict[str, Any]], due: Sequence[float],
                 wake_s: float = 0.001):
        super().__init__(name="bench-open-loop", daemon=True)
        self._submit = submit
        self._events = events
        self._due = [float(t) for t in due]
        self._wake_s = wake_s
        self._halt = threading.Event()
        self.submitted = np.full(len(self._due), np.nan)

    def run(self) -> None:
        i, n = 0, len(self._due)
        due, events, submit, stamp = (self._due, self._events, self._submit,
                                      self.submitted)
        while i < n and not self._halt.is_set():
            now = time.time()
            while i < n and due[i] <= now:
                if not submit(events[i]):
                    break
                stamp[i] = time.time()
                i += 1
            time.sleep(self._wake_s)

    def stop(self) -> None:
        self._halt.set()


class RemoteProducer:
    """The producer process and the ``BrokerServer`` it sends to.

    The server's ``InMemoryBroker`` (``.server.broker``) is the broker the
    job is built over and reads directly; only the generator's produces
    cross the socket. Started early: the child builds the same stream from
    the same seed while the parent sets the scorer up."""

    def __init__(self, workload: str, seed: int, seconds: float):
        from realtime_fraud_detection_tpu.stream import topics as T
        from realtime_fraud_detection_tpu.stream.netbroker import BrokerServer

        self.server = BrokerServer(port=0).start()
        self._tmp = tempfile.mkdtemp(prefix="bench_producer_")
        self._out = os.path.join(self._tmp, "submitted.npz")
        self.proc: Optional[subprocess.Popen] = None
        try:
            self.proc = subprocess.Popen(
                [sys.executable, str(Path(__file__).with_name(
                    "producer_main.py")),
                 "--workload", workload, "--seed", str(seed), "--seconds",
                 str(seconds), "--port", str(self.server.port), "--topic",
                 T.TRANSACTIONS, "--out", self._out],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        except BaseException:
            self.close()
            raise

    def start_at(self, t_base: float, timeout_s: float = 120.0) -> str:
        """Wait until the child is built and connected, then give it the
        stream's start time. Returns its ``ready`` line."""
        line: List[str] = []
        reader = threading.Thread(
            target=lambda: line.append(self.proc.stdout.readline()),
            daemon=True)
        reader.start()
        reader.join(timeout=timeout_s)
        if not line or not line[0].startswith("ready"):
            raise RuntimeError(
                f"the producer process did not become ready within "
                f"{timeout_s} s (exit code {self.proc.poll()}, said "
                f"{line!r})")
        self.proc.stdin.write(f"{t_base!r}\n")
        self.proc.stdin.flush()
        return line[0].strip()

    def finish(self, timeout_s: float) -> tuple:
        """Wait for the child to end; ``(submitted[n], dropped)``."""
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"the producer process had not finished its schedule "
                f"{timeout_s:.1f} s after the window") from None
        if rc != 0:
            raise RuntimeError(f"the producer process exited with {rc}")
        with np.load(self._out) as data:
            return data["submitted"], int(data["dropped"])

    def close(self) -> None:
        """Stop what is still running and wait until it has ended."""
        if self.proc is not None:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait(timeout=30)
            for pipe in (self.proc.stdin, self.proc.stdout):
                if pipe is not None:
                    pipe.close()
        self.server.stop()
        shutil.rmtree(self._tmp, ignore_errors=True)


@contextlib.contextmanager
def collector_off():
    """Collect once, set what is live aside (``gc.freeze``), and keep the
    cyclic collector off inside the block. For the load generator's own
    process only: the job's process keeps its collector on (``drive``)."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


class GcClock:
    """Times the cyclic collector's runs in this process (``gc.callbacks``):
    seconds, runs per generation and the longest pause since ``reset``, and
    for each full collection (generation 2: a dozen in a window) the
    ``time.time()`` it ended at and the seconds it took."""

    def __init__(self) -> None:
        self._t0 = 0.0
        self.reset()
        gc.callbacks.append(self._on)

    def reset(self) -> None:
        self.seconds, self.longest_s, self.runs = 0.0, 0.0, [0, 0, 0]
        self.full: List[tuple] = []

    def _on(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        took = time.perf_counter() - self._t0
        self.seconds += took
        self.longest_s = max(self.longest_s, took)
        self.runs[info["generation"]] += 1
        if info["generation"] == 2:
            self.full.append((time.time(), took))

    def read(self) -> Dict[str, Any]:
        return {"seconds": self.seconds, "longest_s": self.longest_s,
                "runs": list(self.runs), "full": list(self.full)}

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def _snapshot(job) -> Dict[str, int]:
    return dict(job.counters)


def _delta(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}


def _pool_completed(job) -> Optional[List[int]]:
    if job.pool is None:
        return None
    return [d["completed"] for d in job.pool.stats()["devices"]]


def _read_topic(broker, topic: str, group: str, n: int):
    """Every record of ``topic`` through a consumer group of the
    benchmark's own: ``(seq, Record.timestamp, value)`` per record made by
    this run's generator."""
    out = []
    consumer = broker.consumer([topic], group)
    while True:
        recs = consumer.poll(65536)
        if not recs:
            break
        for r in recs:
            v = r.value
            seq = seq_of(str(v.get("transaction_id", ""))) \
                if isinstance(v, dict) else -1
            if 0 <= seq < n:
                out.append((seq, r.timestamp, v))
    return out


def drive(*, mode: str, job, scorer, broker,
          events: Optional[Sequence[Dict[str, Any]]],
          remote: Optional[RemoteProducer], due_offsets: np.ndarray,
          seconds: float, warmup_s: float, grace_s: float, budget_ms: float,
          traced: bool, spans,
          trace_dir: Optional[str], on_open: Callable[[], None],
          compiles, log: Callable[[str], None]) -> Run:
    """Warm-up, window (with the profiler over its last seconds when
    ``traced``), grace, then the join. ``on_open`` runs when the window
    opens (set-up ends there)."""
    import jax

    n = len(due_offsets)
    cfg = job.config
    slice_s = min(TRACE_SLICE_S, seconds / 3.0) if traced else 0.0
    counted_s = seconds - slice_s
    if mode != "open_loop":
        for ev in events:
            broker.produce(cfg.transactions_topic, ev,
                           key=str(ev["user_id"]))
    # The collector is ON in the job's process from here to the end of the
    # run: `rtfd run-job` runs with it on, over the same in-memory broker
    # that keeps every record of every topic in the job's heap, so what
    # collection costs is the program's and is measured. Only what is live
    # NOW leaves its view, once (``gc.freeze``): the harness's pre-built
    # stream and prefilled backlog (millions of objects a deployment would
    # hold in Kafka, not in the job's heap) and, with them, the start-up
    # state a long-lived job would long since have promoted to the oldest
    # generation. (Set-up ran with the collector off, ``runner.run_cell``:
    # all it could have walked is what is set aside here.)
    gc.freeze()
    gc.enable()
    gc_clock = GcClock()
    if mode == "open_loop":
        t_base = time.time() + 0.25
        log(f"producer process: {remote.start_at(t_base)}")
        due = t_base + due_offsets
        t_open = t_base + warmup_s
        job.run_for(max(0.0, t_open - time.time()))
    else:
        due = np.full(n, time.time())
        job.run_for(warmup_s)
        t_open = time.time()

    # ---- the window opens
    on_open()
    compiles0 = compiles.count
    gc_clock.reset()
    scorer.spans.reset()
    if spans is not None:
        spans.reset()
    c0, pool0 = _snapshot(job), _pool_completed(job)
    reasons0 = dict(job.assembler.close_reasons)
    lag_start = job.consumer.lag()
    job.run_for(counted_s - (time.time() - t_open))
    c1, pool1 = _snapshot(job), _pool_completed(job)
    t_count_snap = time.time()
    collector = gc_clock.read()
    stages = scorer.host_stats()["stages"]
    bench_spans = {k: list(v) for k, v in spans.totals.items()} \
        if spans is not None else {}
    reasons1 = dict(job.assembler.close_reasons)
    c2 = c1
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)    # keep one
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # annotations, no frames
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench:slice"):
                job.run_for(slice_s)
        finally:
            jax.profiler.stop_trace()
        c2 = _snapshot(job)
    lag_end = job.consumer.lag()
    compiled_in_window = compiles.count - compiles0

    # ---- grace: what was due inside the window may still complete
    submitted, dropped = due.copy(), 0
    if mode == "open_loop":
        # the producer's list ends with the window; a late one finishes it
        # (its lateness is in every latency) while the job keeps running
        t_limit = t_open + seconds + grace_s
        while time.time() < t_limit and (remote.proc.poll() is None
                                         or job.consumer.lag() > 0):
            job.run_for(min(0.1, max(0.01, t_limit - time.time())))
        submitted, dropped = remote.finish(timeout_s=5.0)
    gc_clock.close()

    # ---- the join, on transaction_id
    emitted = np.full(n, np.nan)
    ingested = np.full(n, np.nan)
    marked = np.zeros(n, bool)
    bad = dups = 0
    for seq, ts, value in _read_topic(broker, cfg.predictions_topic,
                                      "bench-predictions-reader", n):
        if np.isnan(emitted[seq]):
            emitted[seq] = ts
        else:
            dups += 1
        if correct.failed_marker(value):
            marked[seq] = True
        elif not correct.prediction_ok(value):
            bad += 1
    for seq, ts, _ in _read_topic(broker, cfg.transactions_topic,
                                  "bench-ingest-reader", n):
        ingested[seq] = ts
    cache = getattr(scorer.tokenizer, "cache_stats", dict)()
    run = Run(
        mode=mode, seconds=seconds, counted_s=counted_s, t_open=t_open,
        t_count_end=t_open + counted_s, t_count_snap=t_count_snap,
        grace_s=grace_s, budget_ms=budget_ms,
        due=np.asarray(due, np.float64), submitted=submitted,
        ingested=ingested, emitted=emitted, marked_failed=marked,
        bad_outputs=bad, duplicates=dups,
        gateway_dropped=dropped,
        counters=_delta(c1, c0),
        counters_slice=_delta(c2, c1),
        close_reasons={k: v - reasons0.get(k, 0)
                       for k, v in reasons1.items()},
        lag_start=lag_start, lag_end=lag_end, stages=stages,
        bench_spans=bench_spans,
        pool_completed=[b - a for a, b in zip(pool0, pool1)]
        if pool0 is not None else None,
        token_cache=dict(cache), tracer=job.tracer,
        extra={"compiled_in_window": compiled_in_window,
               "collector": collector})
    if traced:
        from benchmarks.harness import trace as trace_mod

        t0 = time.perf_counter()
        trace_events = trace_mod.read_xplane(
            trace_mod.newest_xplane(trace_dir))
        run.trace = trace_mod.reduce(trace_events)
        log(f"trace: {len(trace_events)} events reduced in "
            f"{time.perf_counter() - t0:.1f} s")
    return run
