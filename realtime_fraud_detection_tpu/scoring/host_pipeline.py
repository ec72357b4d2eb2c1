"""Overlapped host-assembly stage: 2-stage software pipelining of the seam.

The fused device program made the TPU side of a microbatch one call; what
remained serial was the HOST side — ``FraudScorer.assemble`` (state joins,
encode, tokenize) ran on the same thread that then blocked in
``finalize``'s device wait, so assembly and device compute took turns
instead of overlapping. This module is the software-pipelining half of the
host-assembly plane (the input-pipeline lever of tf.data, arXiv:2101.12127):

    stage 1 (background thread): assemble + pad/pack + launch batch N+1
    stage 2 (caller's thread):   block on batch N's result, write back

``AssemblerStage`` owns one daemon thread and a bounded queue. ``submit``
enqueues a record batch and returns an ``AssembledHandle`` immediately; the
thread runs ``scorer.assemble`` + ``scorer.dispatch_assembled`` in FIFO
order, so while the caller waits out batch N's device time in
``finalize``, batch N+1's host assembly is already running. The queue bound
is the pipeline depth — a slow device backpressures ``submit`` instead of
growing an unbounded backlog.

Ordering and state-consistency contract:

- Batches dispatch in submit order (single stage thread, FIFO queue) —
  the overlap never reorders scoring, fan-out, or offset commits.
- ``lock`` serializes the scorer's host-state mutation: the stage holds it
  across assemble+dispatch; callers pass the same lock to
  ``scorer.finalize`` so the state write-back never interleaves with an
  assembly. The device wait itself happens outside the lock — that is the
  window the overlap lives in.
- Velocity/history staleness is the SAME tradeoff the pipelined run loops
  already document (stream/job.JobConfig.pipeline_depth): batch N+1 may
  assemble before batch N's write-back lands. With overlap the interleaving
  becomes timing-dependent rather than fixed, which is why the stream job
  keeps overlap opt-in (``JobConfig.overlap_assembly``).

QoS interaction: admission, dedupe and ladder observation stay on the
caller's thread BEFORE ``submit`` (stream/job.dispatch_batch), and batch
close deadlines remain the assembler's (stream/microbatch) — the overlap
stage neither drops nor reorders admission decisions; the virtual-clock
drill in tests/test_host_pipeline.py pins this.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, List, Mapping, Optional, Sequence

__all__ = ["AssembledHandle", "AssemblerStage"]


class AssembledHandle:
    """Future for one submitted batch: resolves to a PendingScore."""

    __slots__ = ("_event", "_pending", "_exc")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._pending: Any = None
        self._exc: Optional[BaseException] = None

    def _set(self, pending: Any) -> None:
        self._pending = pending
        self._event.set()

    def _set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> Any:
        """Block until the batch is assembled + dispatched; returns the
        PendingScore (or re-raises the stage's assembly error)."""
        if not self._event.wait(timeout):
            raise TimeoutError("assembled batch not ready")
        if self._exc is not None:
            raise self._exc
        return self._pending


class AssemblerStage:
    """Background assemble+dispatch stage over one FraudScorer.

    One daemon thread, one bounded FIFO queue: ``submit`` returns a handle
    immediately, ``handle.result()`` (usually via the caller's finalize
    path) joins the pipeline back up. ``lock`` is the stage's state lock —
    pass it to ``scorer.finalize(..., lock=stage.lock)`` so write-backs
    serialize against assemblies.
    """

    def __init__(self, scorer, depth: int = 2):
        self.scorer = scorer
        self.lock = threading.Lock()
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._thread: Optional[threading.Thread] = None
        self._closed = False

    # ------------------------------------------------------------ lifecycle
    def _ensure_started(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="host-assembler", daemon=True)
            self._thread.start()

    def close(self) -> None:
        """Drain and stop the stage thread (idempotent)."""
        if self._closed:
            return
        self._closed = True
        if self._thread is not None:
            self._q.put(None)
            self._thread.join(timeout=30.0)
            self._thread = None

    # --------------------------------------------------------------- submit
    def submit(self, records: Sequence[Mapping[str, Any]],
               now: Optional[float] = None,
               trace: Optional[Any] = None) -> AssembledHandle:
        """Enqueue one microbatch for background assembly + dispatch.

        Blocks when ``depth`` batches are already queued (backpressure);
        the returned handle resolves to the PendingScore in FIFO order.
        ``trace`` (obs.tracing.TraceBatch) rides the queue item so the
        stage thread's assemble/pack/dispatch marks land on the batch
        that is actually being assembled — trace↔batch attachment is by
        object identity, immune to thread interleaving.
        """
        if self._closed:
            raise RuntimeError("assembler stage is closed")
        self._ensure_started()
        handle = AssembledHandle()
        self._q.put((list(records), now, handle, trace))
        return handle

    def finalize(self, handle: AssembledHandle,
                 now: Optional[float] = None) -> List[dict]:
        """Resolve a handle and finalize under the stage lock — the
        convenience join for callers without their own completion path."""
        pending = handle.result()
        return self.scorer.finalize(pending, now=now, lock=self.lock)

    # ----------------------------------------------------------------- run
    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            records, now, handle, trace = item
            # rtfd-lint: allow[wall-clock] dispatch-time diagnostics (dispatch_ms), not control flow
            t0 = time.perf_counter()
            try:
                with self.lock:
                    # the trace kwarg only when tracing is live (drills
                    # drive this stage with duck-typed scorer stand-ins)
                    kw = {"trace": trace} if trace is not None else {}
                    batch = self.scorer.assemble(records, now, **kw)
                    pending = self.scorer.dispatch_assembled(
                        batch, records, t0=t0, **kw)
            except BaseException as e:  # noqa: BLE001 — surfaces at result()
                handle._set_exception(e)
            else:
                handle._set(pending)
