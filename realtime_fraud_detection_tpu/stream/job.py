"""The streaming scoring job: this framework's FraudDetectionJob.

Equivalent of the reference's Flink job graph (FraudDetectionJob.java:33-106)
*with the ML seam actually wired* (the reference never connects Flink to the
ML service — SURVEY.md §0.3):

    payment-transactions ──▶ microbatch assembler ──▶ FraudScorer (TPU)
        ├─▶ fraud-predictions   (every scored txn; §2.7 response schema)
        ├─▶ fraud-alerts        (fraud_score > alert threshold 0.7,
        │                        FraudDetectionJob.java:66-81)
        ├─▶ transaction-enriched (txn + score/decision fields)
        └─▶ transaction-features (the 64-wide §2.3 vector)

Offsets are committed only AFTER all produces + state write-back — crash
replays the uncommitted tail, and replayed transaction_ids are deduplicated
against the scorer's transaction cache (at-least-once delivery, effectively-
once scoring).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional

from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.obs.profiling import GcSpans, SpanTimer
from realtime_fraud_detection_tpu.scoring.scorer import (
    LAUNCH_COUNTERS,
    FraudScorer,
)
from realtime_fraud_detection_tpu.serving.validation import sanitize_for_stream
from realtime_fraud_detection_tpu.state.stores import _event_time_ms
from realtime_fraud_detection_tpu.stream import topics as T
from realtime_fraud_detection_tpu.stream.microbatch import MicrobatchAssembler
from realtime_fraud_detection_tpu.stream.transport import (
    FaultInjector,
    InMemoryBroker,
    Record,
)
from realtime_fraud_detection_tpu.stream.windows import WindowedAnalytics

log = logging.getLogger(__name__)


@dataclasses.dataclass
class JobConfig:
    """Streaming-job parameters (reference JobConfig.java:14-200 analog)."""

    group_id: str = "fraud-detection-job"
    max_batch: int = 256
    max_delay_ms: float = 5.0
    alert_threshold: float = 0.7      # FraudDetectionJob.java:66
    emit_features: bool = True
    emit_enriched: bool = True
    # attach the windowed-analytics stage (the reference built its
    # WindowProcessor but never wired it into the job graph — SURVEY.md §0.3)
    enable_analytics: bool = False
    # blend the 6-category feature score 60/40 into the enriched output
    # (FeatureEnrichmentProcessor semantics — also built-but-unwired in the
    # reference, FeatureEnrichmentProcessor.java:84-150)
    enable_enrichment: bool = False
    # how many dispatched microbatches may be in flight before the oldest is
    # completed. 2 overlaps host assembly with device compute; 3 additionally
    # overlaps the device->host result transfer with a full batch period
    # (what that buys is not measured on local hardware). Completion
    # stays in dispatch order; commit-after-fan-out semantics are unchanged.
    # TRADEOFF: state write-back (velocity/txn-cache) for a batch happens at
    # completion, so a batch is assembled while up to depth-1 earlier
    # batches' write-backs are pending — at depth D a user's transactions
    # landing in D consecutive microbatches see velocity counts missing up
    # to D-1 batches' updates (vs 1 at the default depth 2). Raise depth for
    # throughput soaks; keep 2 where freshest velocity features matter.
    pipeline_depth: int = 2
    # overlapped host assembly (scoring/host_pipeline.AssemblerStage): a
    # background thread runs assemble+dispatch for batch N+1 while this
    # thread waits out batch N's device time in finalize — 2-stage software
    # pipelining of the host→device seam. Admission/dedupe/ladder stay on
    # THIS thread (decisions are never reordered or dropped); the velocity-
    # staleness tradeoff is the same as pipeline_depth's, but the exact
    # interleaving of batch N's write-back with batch N+1's assembly
    # becomes timing-dependent — keep off where bit-reproducible replays
    # matter, on for throughput.
    overlap_assembly: bool = False
    # device-pool scoring plane (scoring/device_pool.py): replicate the
    # scorer's params onto every addressable device and dispatch whole
    # microbatches round-robin across per-device in-flight queues — the
    # multi-chip throughput lever (one chip idles seven on a v5e-8
    # otherwise). Scores stay bit-identical to single-device; completion
    # (fan-out + commit) stays FIFO. The run loops raise their in-flight
    # window to the pool's capacity (devices x inflight_depth) so every
    # replica receives work — the velocity-staleness tradeoff documented
    # at pipeline_depth scales with that window.
    device_pool: bool = False
    # per-replica in-flight depth (>= 2 keeps each device's compute
    # back-to-back while the next batch's H2D stages)
    inflight_depth: int = 2
    # deadline-aware QoS plane (qos/): admission control, per-transaction
    # latency budgets (the assembler closes batches early when the oldest
    # waiter's budget runs low), and the degradation ladder fed by the
    # backlog signal (consumer lag + pipelined in-flight records). None or
    # enabled=False = the plane is off, behavior unchanged.
    qos: Optional[Any] = None            # utils.config.QosSettings
    # continuous-learning plane (feedback/): a FeedbackPlane instance the
    # job feeds after every completed batch (emitted predictions +
    # assembled feature rows into the label join / drift monitor) and
    # whose labels topic it drains in the run loops. None = off.
    feedback: Optional[Any] = None       # feedback.FeedbackPlane
    # tracing plane (obs/tracing.py): a TracingSettings (or a live Tracer)
    # — every admitted transaction gets a trace context riding the batch
    # through dispatch/completion into the flight recorder; sheds get a
    # terminal `shed` trace. None or enabled=False = off, and the scoring
    # path pays one `is None` branch per batch (the measured no-op path).
    tracing: Optional[Any] = None        # utils.config.TracingSettings|Tracer
    # distributed tracing: when True, every consumed record is EXPECTED to
    # carry a producer-stamped trace carrier (obs.tracing.CARRIER_KEY in
    # the raw record value); a record without a parseable one opens a
    # fresh root trace counted in the tracer's carrier_lost — the
    # netfault-dropped-frame degradation contract. False (default) means
    # carriers are adopted opportunistically when present, never counted
    # as lost when absent (single-process deployments stay quiet).
    expect_carrier: bool = False
    # self-tuning host pipeline (tuning/): a TuningSettings (or a live
    # TuningPlane) — the assembler's close decisions move from the fixed
    # deadline to the arrival-aware just-in-time controller, and the
    # online tuner adjusts the max-wait bound / bucket set / in-flight
    # depth from completed-batch observations. None or enabled=False =
    # off, and batch-close decisions are BIT-IDENTICAL to the fixed-
    # deadline path (the assembler takes the controller branch only when
    # one is attached).
    autotune: Optional[Any] = None       # utils.config.TuningSettings|plane
    labels_topic: str = T.LABELS
    # topic names (reference JobConfig.java topic parameters); defaults are
    # the §2.5 contract (stream/topics.py) — overridable per deployment,
    # e.g. the reference's test-transactions topic for shadow traffic
    transactions_topic: str = T.TRANSACTIONS
    predictions_topic: str = T.PREDICTIONS
    alerts_topic: str = T.ALERTS
    enriched_topic: str = T.ENRICHED
    features_topic: str = T.FEATURES


@dataclasses.dataclass
class _BatchCtx:
    """A microbatch between dispatch and completion (device in flight)."""

    fresh: List[Record]
    ids: set
    pending: Any                      # scoring.scorer.PendingScore | None
    positions: Dict[tuple, int]       # offsets to commit at completion
    now: Optional[float]
    # records rejected by per-record ingest sanitization; each gets its own
    # error result at completion — they never poison the rest of the batch
    invalid: List[tuple] = dataclasses.field(default_factory=list)
    # txn-cache duplicates: (record, cached result) pairs. State write-back
    # happens BEFORE fan-out (finalize order), so a crash between the two
    # leaves a record cached but its prediction never produced; on replay
    # the dedupe path re-emits the prediction from the cache instead of
    # silently swallowing it. Predictions are thereby at-least-once while
    # scoring + state stay effectively-once (consumers dedupe by txn id).
    cached_dups: List[tuple] = dataclasses.field(default_factory=list)
    # QoS admission sheds: (record, AdmissionDecision) pairs. Each gets an
    # explicit score-with-reason on the predictions topic at completion —
    # a shed is a recorded decision, never a silent drop.
    shed: List[tuple] = dataclasses.field(default_factory=list)
    # tracing plane: this batch's TraceBatch carrier (None = tracing off)
    trace: Optional[Any] = None
    # dispatch instant on the record-timestamp clock base (wall in
    # production, virtual in drills): the tuning plane's service-time
    # observation is completion minus this
    t_dispatch: float = 0.0
    # the job's batch sequence number: the ``batch=`` every span of this
    # microbatch carries (obs/profiling.SpanTimer)
    seq: int = 0


class StreamJob:
    """Consume → score → fan out → commit. One instance per process.

    The run loops keep up to ``JobConfig.pipeline_depth`` microbatches in
    flight: while the device computes batch N, the host polls + assembles +
    dispatches later batches, completing (fan-out + offset commit) strictly
    in dispatch order. Depth 2 overlaps host work with device compute;
    depth 3 additionally overlaps the result transfer with a full batch
    period (see JobConfig.pipeline_depth for the staleness tradeoff).
    """

    def __init__(
        self,
        broker: InMemoryBroker,
        scorer: FraudScorer,
        config: Optional[JobConfig] = None,
        faults: Optional[FaultInjector] = None,
    ):
        self.broker = broker
        self.scorer = scorer
        self.config = config or JobConfig()
        self.consumer = broker.consumer(
            [self.config.transactions_topic], self.config.group_id, faults
        )
        # QoS plane: admission + ladder + budget (qos/plane.py); the
        # assembler consults the budget so batches close early when the
        # oldest waiter's remaining deadline drops under the margin
        self.qos = None
        qs = self.config.qos
        if qs is not None and getattr(qs, "enabled", False):
            from realtime_fraud_detection_tpu.qos import QosPlane

            self.qos = qs if isinstance(qs, QosPlane) else QosPlane(qs)
        # self-tuning plane: the assembler consults its just-in-time
        # controller instead of the fixed deadline; the run loops re-read
        # its recommended in-flight depth each iteration
        self.tuning = None
        ts = self.config.autotune
        if ts is not None and getattr(ts, "enabled", False):
            from realtime_fraud_detection_tpu.tuning import TuningPlane

            self.tuning = ts if isinstance(ts, TuningPlane) \
                else TuningPlane(ts)
        self.assembler = MicrobatchAssembler(
            self.consumer,
            max_batch=self.config.max_batch,
            max_delay_ms=self.config.max_delay_ms,
            budget=self.qos.budget if self.qos is not None else None,
            controller=self.tuning,
        )
        self.analytics = (
            WindowedAnalytics(broker) if self.config.enable_analytics else None
        )
        # continuous-learning plane: its own consumer group on the labels
        # topic (labels are a separate stream with its own offsets — a
        # replayed label batch must not disturb transaction offsets)
        self.feedback = self.config.feedback
        self._labels_consumer = None
        if self.feedback is not None:
            self._labels_consumer = broker.consumer(
                [self.config.labels_topic],
                f"{self.config.group_id}-labels")
        # device pool: replicate params onto every addressable device; the
        # scorer's dispatch_assembled routes through it from here on. An
        # already-attached pool (caller-constructed) is respected. getattr:
        # drills drive this job with duck-typed scorer stand-ins
        self.pool = getattr(scorer, "pool", None)
        if self.config.device_pool and self.pool is None:
            from realtime_fraud_detection_tpu.scoring import DevicePool

            self.pool = DevicePool(
                scorer, inflight_depth=self.config.inflight_depth)
        # overlapped host assembly: scorer.dispatch moves to a background
        # stage thread; this thread keeps admission/dedupe/commit order
        self._stage = None
        if self.config.overlap_assembly:
            from realtime_fraud_detection_tpu.scoring.host_pipeline import (
                AssemblerStage,
            )

            self._stage = AssemblerStage(
                scorer, depth=max(1, self.config.pipeline_depth))
        # tracing plane: per-transaction flight recorder + SLO burn rate.
        # A live Tracer is adopted (the drills pass a virtual-clock one);
        # TracingSettings with enabled=True constructs one here.
        self.tracer = None
        tr = self.config.tracing
        if tr is not None:
            from realtime_fraud_detection_tpu.obs.tracing import Tracer

            if isinstance(tr, Tracer):
                self.tracer = tr if tr.enabled else None
            elif getattr(tr, "enabled", False):
                self.tracer = Tracer(tr)
        # host spans: one timer per scoring path, the scorer's (so
        # ``host_stats()["stages"]`` holds the job's spans too); a stand-in
        # scorer without one gets the job's own
        self.spans = getattr(scorer, "spans", None) or SpanTimer()
        # collections as ``rtfd:host.gc`` annotations while a run loop is
        # live, where tracing is on; their count and pauses ride the
        # tracer's snapshot
        self._host_gc = contextlib.nullcontext()
        if self.tracer is not None:
            self._host_gc = self.tracer.host_gc = GcSpans()
        # the job's own, then the counters of the text branch's launches,
        # summed where ``batches`` is from ``PendingScore.counters``
        # (models/text_encoder.py says what each counts): all of them read
        # 0 from the first batch, whichever the encoder fills
        self.counters: Dict[str, int] = dict.fromkeys(
            ("scored", "alerts", "batches", "duplicates_skipped", "errors",
             "shed") + LAUNCH_COUNTERS, 0)
        self._batch_seq = 0
        # transaction_ids dispatched but not yet written back: the pipelined
        # loop dedupes batch N+1 against these before batch N lands in the
        # txn cache (keeps effectively-once scoring under pipelining)
        self._inflight_ids: set = set()
        # graceful-shutdown seam (cli.py installs SIGTERM/SIGINT handlers
        # that set this): the run loops stop POLLING but still complete
        # every dispatched batch and commit its offsets — a signal drains
        # the in-flight tail instead of losing it to replay-on-restart
        self.stop_requested = False
        self._batch_error_logged = False

    def _log_batch_error(self, stage: str, n: int, exc: Exception) -> None:
        """The whole-batch degradation keeps the stream alive (every record
        of the batch is emitted as 0.5 / REVIEW / risk_level "ERROR"), but
        its cause must be readable from the output — a compile error on
        the device otherwise looks like a job that runs. First occurrence
        with its traceback, later ones one line each."""
        first = not self._batch_error_logged
        self._batch_error_logged = True
        log.error(
            "scoring %s failed for a batch of %d (%s: %s); emitting the "
            "ERROR marker for each record", stage, n, type(exc).__name__,
            exc, exc_info=exc if first else None)

    def request_stop(self) -> None:
        """Ask the run loops to drain in-flight microbatches, commit, and
        return (signal-handler safe: one attribute write)."""
        self.stop_requested = True

    def _inflight_depth(self) -> int:
        """Run-loop in-flight window: the configured pipeline depth, set
        to the device pool's capacity when one is attached — a window
        smaller than devices x depth would leave replicas starved, and a
        window LARGER than capacity would deadlock the single-threaded
        run loop (the executor's dispatch blocks for a slot that only
        this loop's own finalize can free; a 1-replica MeshExecutor at
        depth 2 under a configured depth 3 hit exactly this). With the
        tuning plane attached, its online-tuned depth replaces the
        configured one (re-read every loop iteration, so a tuner move
        takes effect one batch later); an attached pool's capacity still
        overrides — it IS the hardware window."""
        depth = max(1, self.config.pipeline_depth)
        if self.tuning is not None:
            depth = max(1, self.tuning.recommended_inflight_depth())
        if self.pool is not None:
            depth = self.pool.total_slots()
        return depth

    # ----------------------------------------------------------------- steps
    def process_batch(self, records: List[Record],
                      now: Optional[float] = None) -> List[Dict[str, Any]]:
        """Score one microbatch and fan results out to the output topics."""
        ctx = self.dispatch_batch(records, now=now)
        return self.complete_batch(ctx) if ctx is not None else []

    def dispatch_batch(self, records: List[Record],
                       now: Optional[float] = None) -> Optional["_BatchCtx"]:
        """Stage 1 of the pipelined step: dedupe + launch on device.

        Returns without blocking on the device — the caller overlaps the
        next batch's poll/assembly with this batch's compute and calls
        ``complete_batch`` (in dispatch order) to fan out + commit. Offsets
        are snapshotted HERE so a later poll can't advance what this
        batch's commit covers.
        """
        if not records:
            return None
        self._batch_seq += 1
        with self.spans.span(scopes.JOB_DISPATCH, batch=self._batch_seq):
            return self._dispatch_batch(records, now, self._batch_seq)

    def _dispatch_batch(self, records: List[Record], now: Optional[float],
                        seq: int) -> "_BatchCtx":
        with self.spans.span(scopes.JOB_ADMIT):
            admitted = self._admit(records, now)
        (fresh, invalid, cached_dups, shed, trace_ctxs, batch_ids,
         positions, t_adm) = admitted
        tracer = self.tracer
        if not fresh:
            return _BatchCtx([], set(), None, positions, now, invalid,
                             cached_dups, shed, seq=seq)
        trace = None
        if tracer is not None:
            trace = tracer.batch(
                trace_ctxs, batch_size=len(fresh),
                close_reason=self.assembler.last_close_reason)
        pending = None
        try:
            # the trace kwarg is passed ONLY when tracing is live: drills
            # and tests drive this job with duck-typed scorer stand-ins
            # whose dispatch() may not know the parameter, and an
            # unexpected-kwarg TypeError here would silently take the
            # whole-batch degradation path
            kw = {"trace": trace} if trace is not None else {}
            if self._stage is not None:
                # background assembly: the handle resolves to a
                # PendingScore at completion; errors surface there and take
                # the same whole-batch degradation path. The trace rides
                # the queue item, so the stage thread's marks land on the
                # batch they belong to (identity, not timing).
                pending = self._stage.submit([r.value for r in fresh],
                                             now=now, **kw)
            else:
                pending = self.scorer.dispatch([r.value for r in fresh],
                                               now=now, **kw)
        except Exception as e:  # noqa: BLE001 — boundary: keep streaming
            # whole-batch degradation fallback: score 0.5, REVIEW, keep the
            # stream alive; counted at completion
            self._log_batch_error("dispatch", len(fresh), e)
        self._inflight_ids |= batch_ids
        return _BatchCtx(fresh, batch_ids, pending, positions, now, invalid,
                         cached_dups, shed, trace, t_adm, seq)

    def _admit(self, records: List[Record], now: Optional[float]) -> tuple:
        """Sanitize, dedupe, QoS admission and trace begin for one polled
        batch (the ``job.admit`` span); offsets are snapshotted here."""
        fresh: List[Record] = []
        invalid: List[tuple] = []
        cached_dups: List[tuple] = []
        shed: List[tuple] = []
        trace_ctxs: List[Any] = []
        tracer = self.tracer
        batch_ids: set = set()
        # rtfd-lint: allow[wall-clock] production default time base; drills pass now
        t_adm = now if now is not None else time.time()

        def _ingest_lag(rec: Record) -> float:
            # upstream-of-admission lag: gateway ingest stamp when present
            # (IngressGateway stamp_ingest), else the broker produce
            # timestamp — wall-minus-wall (or virtual-minus-virtual in the
            # drills), never mixed with the tracer's monotonic base
            src = None
            if isinstance(rec.value, dict):
                src = rec.value.get("ingest_ts")
            if src is None:
                src = rec.timestamp
            try:
                return max(0.0, t_adm - float(src)) if src is not None \
                    else 0.0
            except (TypeError, ValueError):
                return 0.0

        expect_carrier = self.config.expect_carrier

        def _carrier(rec: Record) -> Any:
            # read from the RAW record value (the ingest_ts precedent):
            # sanitize strips unknown fields, so the carrier must be
            # lifted before the sanitized copy replaces the value
            return rec.value.get("trace_carrier") \
                if isinstance(rec.value, dict) else None

        for r in records:
            txn, errors = sanitize_for_stream(r.value)
            if errors:
                # per-record degradation (TransactionProcessor.java:83-91):
                # one poisoned record must not drag its batch-mates onto
                # the error path — it alone gets an error result
                invalid.append((r, errors))
                continue
            txn_id = txn["transaction_id"]  # sanitizer guarantees non-empty
            if txn_id in batch_ids or txn_id in self._inflight_ids:
                # first instance (this batch / a dispatched batch) will
                # emit the prediction itself — skip silently
                self.counters["duplicates_skipped"] += 1
                continue
            cached = self.scorer.txn_cache.get_transaction(txn_id, now=now)
            if cached is not None:
                # already scored + written back. Its prediction may never
                # have been produced (crash between write-back and
                # fan-out), so re-emit from the cache at completion —
                # at-least-once predictions, no re-scoring, no
                # double-counted velocity. batch_ids gets the id so a
                # second copy in this same poll re-emits only once.
                self.counters["duplicates_skipped"] += 1
                batch_ids.add(txn_id)
                cached_dups.append((r, cached))
                continue
            priority = ""
            if self.qos is not None:
                # admission AFTER dedupe (a replayed duplicate must not
                # burn tokens) and BEFORE dispatch: a shed is an explicit
                # decision recorded at completion, never a silent drop
                decision = self.qos.admit(txn, t_adm)
                priority = decision.priority
                if not decision.admitted:
                    self.counters["shed"] += 1
                    shed.append((dataclasses.replace(r, value=txn),
                                 decision))
                    if tracer is not None:
                        # a shed is a recorded terminal trace, not a gap
                        tracer.finish_terminal(
                            tracer.begin(txn_id,
                                         ingest_lag_s=_ingest_lag(r),
                                         priority=decision.priority,
                                         carrier=_carrier(r),
                                         now_wall=t_adm,
                                         expect_carrier=expect_carrier),
                            "shed", reason=decision.reason,
                            priority=decision.priority)
                    continue
            batch_ids.add(txn_id)
            fresh.append(dataclasses.replace(r, value=txn))
            if tracer is not None:
                trace_ctxs.append(
                    tracer.begin(txn_id, ingest_lag_s=_ingest_lag(r),
                                 priority=priority, carrier=_carrier(r),
                                 now_wall=t_adm,
                                 expect_carrier=expect_carrier))
        positions = self.consumer.snapshot_positions()
        if self.qos is not None:
            # backlog signal, one ladder observation per dispatched
            # microbatch: consumer lag counts everything not yet COMMITTED
            # — the unread topic backlog plus every pipelined in-flight
            # batch (commit happens at completion) — minus THIS batch,
            # which is being handled right now, not waiting
            self.qos.observe_backlog(
                max(0, self.consumer.lag() - len(records)))
            if self._stage is not None:
                # a ladder step writes the scorer's qos mask + rules_only
                # flag; the stage thread reads both at dispatch — take the
                # stage lock so one batch never sees a torn pair
                with self._stage.lock:
                    self.qos.apply_degradation(self.scorer)
            else:
                # rtfd-lint: allow[lock-order] stream job is single-writer: consume, score, QoS share one thread
                self.qos.apply_degradation(self.scorer)
        return (fresh, invalid, cached_dups, shed, trace_ctxs, batch_ids,
                positions, t_adm)

    def complete_batch(self, ctx: "_BatchCtx",
                       now: Optional[float] = None) -> List[Dict[str, Any]]:
        """Stage 2: block on the device result, fan out, commit offsets.

        ``now`` is the COMPLETION time (for QoS budget accounting on the
        drill's virtual clock); ``ctx.now`` remains the dispatch-time
        event clock for state TTLs. Default None = wall clock.
        """
        with self.spans.span(scopes.JOB_COMPLETE, batch=ctx.seq):
            return self._complete_batch(ctx, now)

    def _complete_batch(self, ctx: "_BatchCtx",
                        now: Optional[float]) -> List[Dict[str, Any]]:
        fresh = ctx.fresh
        t_done = now if now is not None else (
            # rtfd-lint: allow[wall-clock] production default time base; drills pass now
            ctx.now if ctx.now is not None else time.time())
        now = ctx.now
        if not fresh:
            invalid_results = self._emit_invalid(ctx)  # no ids at risk
            self._emit_shed(ctx)
            self._emit_cached_dups(ctx)
            self.consumer.commit(ctx.positions)
            return invalid_results

        scored_ok, results, feats = False, None, None
        if ctx.pending is not None:
            try:
                pending = ctx.pending
                if self._stage is not None and hasattr(pending, "result"):
                    # overlapped mode: join the background assembly; an
                    # assembly/dispatch error takes the same whole-batch
                    # degradation path as a finalize error
                    pending = pending.result()
                results = self.scorer.finalize(
                    pending, now=now,
                    lock=self._stage.lock if self._stage is not None
                    else None)
                feats = pending.features
                scored_ok = True
                # (nothing from a stand-in scorer's pending without them)
                for key, value in getattr(pending, "counters", {}).items():
                    self.counters[key] += value
            except Exception as e:  # noqa: BLE001 — boundary: keep streaming
                self._log_batch_error("finalize", len(fresh), e)
                results = None
        if results is None:
            self.counters["errors"] += len(fresh)
            results = [
                {
                    "transaction_id": str(r.value.get("transaction_id", "")),
                    "fraud_probability": 0.5,
                    "fraud_score": 0.5,
                    "risk_level": "ERROR",
                    "decision": "REVIEW",
                    "model_predictions": {},
                    "confidence": 0.0,
                    "processing_time_ms": 0.0,
                    "explanation": {"error": True},
                }
                for r in fresh
            ]

        if self.qos is not None:
            self.qos.record_scored(len(fresh))
            for r in fresh:
                # budget headroom at completion, from the record's ingest
                # timestamp (negative = deadline blown; explicit None
                # check — t=0.0 is a legitimate virtual-clock timestamp)
                self.qos.record_completion(
                    r.timestamp if r.timestamp is not None else t_done,
                    t_done)
        try:
            # inside the protective try: a produce failure here must release
            # the in-flight ids like any other fan-out failure
            invalid_results = self._emit_invalid(ctx)
            self._emit_shed(ctx)
            self._emit_cached_dups(ctx)
            out = invalid_results + self._fan_out(
                ctx, fresh, results, feats, scored_ok, now)
            burn = None
            if ctx.trace is not None and self.tracer is not None:
                # emit complete: close every trace in the batch (the
                # per-txn e2e/SLO observation happens here), then consult
                # the SLO burn gate — latency can burn the error budget
                # without the backlog signal ever tripping
                self.tracer.finish_batch(
                    ctx.trace, terminal="scored" if scored_ok else "error")
                # burn rate and trace completion share the tracer's
                # clock (virtual in the drills), so no ``now`` is
                # passed — one time base end to end. Computed once: the
                # QoS gate and the tuning plane both consume it.
                ts = self.tracer.settings
                burn = self.tracer.slo.burn_rate(ts.slo_fast_window_s)
                if self.qos is not None:
                    self.qos.observe_slo_burn(
                        burn,
                        threshold=ts.slo_burn_threshold,
                        patience=ts.slo_gate_patience,
                        up_patience=ts.slo_gate_up_patience)
            if self.tuning is not None:
                # close the tuning loop: the batch's dispatch→complete
                # duration feeds the controller's T(bucket) model, the
                # per-txn completion latencies feed the tuner's
                # admitted-p99 objective, and the SLO burn + ladder level
                # gate it (the tuner freezes during an emergency — it
                # never fights the QoS ladder)
                lat = [max(0.0, t_done - r.timestamp) * 1e3
                       for r in fresh if r.timestamp is not None]
                self.tuning.on_batch_complete(
                    len(fresh), max(0.0, t_done - ctx.t_dispatch), t_done,
                    latencies_ms=lat,
                    burn_rate=burn if burn is not None else 0.0,
                    ladder_level=(self.qos.effective_level()
                                  if self.qos is not None else 0))
            if self.feedback is not None and scored_ok:
                # feed the label join with exactly what was emitted, plus
                # the assembled feature rows (the retrain corpus), then
                # drain any due labels and run the cheap policy check —
                # the expensive retrain stays with the caller (react)
                self.feedback.on_predictions(
                    [r.value for r in fresh], results,
                    features=feats[:len(fresh)] if feats is not None
                    else None,
                    now=t_done)
                self.drain_labels()
                self.feedback.check_trigger(now=t_done)
            return out
        finally:
            # ALWAYS release, even when fan-out raises mid-way (broker down):
            # a leaked id makes the replayed record look like an in-flight
            # duplicate, so it would be skipped and the next commit would
            # advance past it — silent record loss (ADVICE r2). With the ids
            # released, an uncommitted batch replays and rescans normally
            # (txn-cache dedupe still guards the already-written-back case).
            self._inflight_ids -= ctx.ids

    def _emit_invalid(self, ctx: "_BatchCtx") -> List[Dict[str, Any]]:
        """Per-record error results for sanitization rejects: produced to
        the predictions topic so downstream sees a REVIEW decision, never a
        silent gap. Covered by this batch's offset commit."""
        results = []
        items = []
        for rec, errors in ctx.invalid:
            value = rec.value if isinstance(rec.value, dict) else {}
            res = {
                "transaction_id": str(value.get("transaction_id", "")),
                "fraud_probability": 0.5,
                "fraud_score": 0.5,
                "risk_level": "ERROR",
                "decision": "REVIEW",
                "model_predictions": {},
                "confidence": 0.0,
                "processing_time_ms": 0.0,
                "explanation": {"error": True, "validation_errors": errors},
            }
            self.counters["errors"] += 1
            items.append((str(value.get("user_id", "")), res))
            results.append(res)
        if items:
            self.broker.produce_batch_keyed(self.config.predictions_topic,
                                            items)
        return results

    def _emit_shed(self, ctx: "_BatchCtx") -> None:
        """Produce an explicit score-with-reason for every shed record
        (qos.QosPlane.shed_result): downstream sees a REVIEW with the shed
        reason and priority class in the explanation — load shedding is an
        auditable decision, not record loss. Covered by this batch's
        offset commit."""
        if not ctx.shed or self.qos is None:
            return
        items = []
        for rec, decision in ctx.shed:
            value = rec.value if isinstance(rec.value, dict) else {}
            items.append((str(value.get("user_id", "")),
                          self.qos.shed_result(value, decision)))
        self.broker.produce_batch_keyed(self.config.predictions_topic, items)

    def _emit_cached_dups(self, ctx: "_BatchCtx") -> None:
        """Re-emit predictions for txn-cache duplicates from their cached
        results. A record lands here only if it was scored AND written back
        previously; whether its prediction was actually produced before a
        crash is unknowable, so re-emitting is the at-least-once answer —
        downstream consumers dedupe by transaction_id."""
        items = []
        for rec, cached in ctx.cached_dups:
            value = rec.value if isinstance(rec.value, dict) else {}
            items.append((
                str(value.get("user_id", "")),
                {
                    "transaction_id": str(cached.get("transaction_id") or
                                          value.get("transaction_id", "")),
                    "fraud_probability": float(cached.get("fraud_score", 0.5)),
                    "fraud_score": float(cached.get("fraud_score", 0.5)),
                    "risk_level": str(cached.get("risk_level", "UNKNOWN")),
                    "decision": str(cached.get("decision", "REVIEW")),
                    "model_predictions": {},
                    "confidence": float(cached.get("confidence", 0.0)),
                    "processing_time_ms": 0.0,
                    "explanation": {"replayed_from_cache": True},
                },
            ))
        if items:
            self.broker.produce_batch_keyed(self.config.predictions_topic,
                                            items)

    def _fan_out(self, ctx: "_BatchCtx", fresh: List[Record],
                 results: List[Dict[str, Any]], feats, scored_ok: bool,
                 now: Optional[float]) -> List[Dict[str, Any]]:
        """Enrich + produce to output topics + commit (stage-2 tail)."""
        with self.spans.span(scopes.JOB_FAN_OUT):
            self._produce(ctx, fresh, results, feats, scored_ok, now)
        # commit AFTER fan-out + scorer write-back: at-least-once
        with self.spans.span(scopes.JOB_COMMIT):
            self.consumer.commit(ctx.positions)
        return results

    def _produce(self, ctx: "_BatchCtx", fresh: List[Record],
                 results: List[Dict[str, Any]], feats, scored_ok: bool,
                 now: Optional[float]) -> None:
        cfg = self.config
        enriched_scores = None
        wants_enriched = cfg.emit_enriched or self.analytics is not None
        if cfg.enable_enrichment and scored_ok and wants_enriched:
            import numpy as np

            from realtime_fraud_detection_tpu.core.batching import (
                pad_to_bucket,
            )
            from realtime_fraud_detection_tpu.features.rules import (
                DECISIONS as _DECISIONS,
                RISK_LEVEL_NAMES as _RISK,
                blend_enrichment,
            )

            n = len(results)
            prior = np.asarray([r["fraud_score"] for r in results], np.float32)
            # pad to the scoring buckets so blend_enrichment compiles once
            # per bucket, not once per tail-batch size
            (prior_p, feats_p), _, _ = pad_to_bucket(
                (prior, feats[:n]), n)
            blended, dec, risk = blend_enrichment(prior_p, feats_p)
            enriched_scores = (
                np.asarray(blended)[:n],
                [_DECISIONS[i] for i in np.asarray(dec)[:n]],
                [_RISK[i] for i in np.asarray(risk)[:n]],
            )

        # accumulate per topic and flush as ONE batched produce each: over
        # a networked broker, per-record produces cost a round trip apiece
        # (measured 8.6x slower on loopback at batch 256; worse over a
        # real network) — the fan-out is the job's per-record hot loop
        out_preds: List[tuple] = []
        out_alerts: List[tuple] = []
        out_enriched: List[tuple] = []
        out_features: List[tuple] = []
        for i, (rec, res) in enumerate(zip(fresh, results)):
            uid = str(rec.value.get("user_id", ""))
            out_preds.append((uid, res))
            if res["fraud_score"] > cfg.alert_threshold:
                out_alerts.append((uid, self._to_alert(rec.value, res)))
                self.counters["alerts"] += 1
            if cfg.emit_enriched or self.analytics is not None:
                enriched = dict(rec.value)
                enriched.update(
                    fraud_score=res["fraud_score"],
                    risk_level=res["risk_level"],
                    decision=res["decision"],
                )
                if enriched_scores is not None:
                    blended, decisions, risks = enriched_scores
                    enriched.update(
                        fraud_score=float(blended[i]),
                        risk_level=risks[i],
                        decision=decisions[i],
                        ensemble_score=res["fraud_score"],
                    )
                if cfg.emit_enriched:
                    out_enriched.append((uid, enriched))
                if self.analytics is not None:
                    self.analytics.process(
                        enriched, _event_time_ms(enriched, now) / 1000.0)
            # features exist only when scoring succeeded (the error fallback
            # never ran assemble, so there are no feature rows for the batch)
            if cfg.emit_features and scored_ok:
                out_features.append((uid, {
                    "transaction_id": res["transaction_id"],
                    "features": feats[i].tolist()}))
        self.broker.produce_batch_keyed(cfg.predictions_topic, out_preds)
        if out_alerts:
            self.broker.produce_batch_keyed(cfg.alerts_topic, out_alerts)
        if out_enriched:
            self.broker.produce_batch_keyed(cfg.enriched_topic, out_enriched)
        if out_features:
            self.broker.produce_batch_keyed(cfg.features_topic, out_features)
        self.counters["scored"] += len(fresh)
        self.counters["batches"] += 1

    @staticmethod
    def _to_alert(txn: Dict[str, Any], res: Dict[str, Any]) -> Dict[str, Any]:
        """Alert payload (Transaction.toFraudAlert analog, SURVEY.md §2.10)."""
        return {
            "alert_type": "FRAUD_DETECTED",
            "transaction_id": res["transaction_id"],
            "user_id": txn.get("user_id"),
            "merchant_id": txn.get("merchant_id"),
            "amount": txn.get("amount"),
            "fraud_score": res["fraud_score"],
            "risk_level": res["risk_level"],
            "decision": res["decision"],
            "timestamp": txn.get("timestamp"),
        }

    def drain_labels(self, max_records: int = 10_000) -> int:
        """Poll the labels topic into the feedback plane (no-op without
        one). Label offsets commit immediately after ingestion: the join +
        prequential state is process-local anyway, and a replayed label is
        deduplicated by the join."""
        if self.feedback is None or self._labels_consumer is None:
            return 0
        recs = self._labels_consumer.poll(max_records)
        if not recs:
            return 0
        matched = self.feedback.on_labels(
            [r.value for r in recs if isinstance(r.value, dict)])
        self._labels_consumer.commit()
        return matched

    # ------------------------------------------------------------------ run
    def run_until_drained(self, max_batches: int = 10_000,
                          now: Optional[float] = None) -> int:
        """Process until the input topic is fully consumed. Returns #scored."""
        with self._host_gc:
            return self._run_until_drained(max_batches, now)

    def _poll(self, **kw) -> List[Record]:
        """``assembler.next_batch`` as the ``job.poll`` span: the consumer
        poll and the wait for rows, up to the batch's close."""
        with self.spans.span(scopes.JOB_POLL):
            return self.assembler.next_batch(**kw)

    def _run_until_drained(self, max_batches: int,
                           now: Optional[float]) -> int:
        from collections import deque

        start_scored = self.counters["scored"]
        depth = self._inflight_depth()
        in_flight: deque = deque()
        for _ in range(max_batches):
            if self.stop_requested:
                # drain: dispatch the assembler's polled-but-unbatched
                # tail too — those records' offsets are past the last
                # commit snapshot, and leaving them unscored would replay
                # them on every restart (the satellite this seam exists
                # for: SIGTERM loses nothing, only SIGKILL replays)
                tail = self.assembler.flush()
                while tail:
                    in_flight.append(self.dispatch_batch(tail, now=now))
                    tail = self.assembler.flush()
                break
            batch = self._poll(block=False)
            if not batch:
                batch = self.assembler.flush()
            if not batch:
                if in_flight:
                    self.complete_batch(in_flight.popleft())
                    continue
                if self.consumer.lag() == 0:
                    break
                continue
            in_flight.append(self.dispatch_batch(batch, now=now))
            while len(in_flight) >= depth:
                self.complete_batch(in_flight.popleft())
            if self.feedback is not None \
                    and self.feedback.pending_trigger is not None:
                # retrain between batches (the job is a batch process; the
                # serving app instead hands this to a worker thread)
                self.feedback.react(now=now)
        while in_flight:
            self.complete_batch(in_flight.popleft())
        self.drain_labels()
        return self.counters["scored"] - start_scored

    def close(self) -> None:
        """Stop the background assembler stage (no-op without overlap)."""
        if self._stage is not None:
            self._stage.close()

    def run_for(self, duration_s: float) -> int:
        """Process the stream for a wall-clock window (soak-test entry)."""
        with self._host_gc:
            return self._run_for(duration_s)

    def _run_for(self, duration_s: float) -> int:
        from collections import deque

        # rtfd-lint: allow[wall-clock] consume-only slice duration is wall-bound by definition
        t_end = time.monotonic() + duration_s
        start = self.counters["scored"]
        depth = self._inflight_depth()
        in_flight: deque = deque()
        # rtfd-lint: allow[wall-clock] consume-only slice duration is wall-bound by definition
        while time.monotonic() < t_end and not self.stop_requested:
            batch = self._poll(block=True, timeout_s=0.05)
            if batch:
                in_flight.append(self.dispatch_batch(batch))
            if in_flight and (len(in_flight) >= depth or not batch):
                self.complete_batch(in_flight.popleft())
            if self.feedback is not None \
                    and self.feedback.pending_trigger is not None:
                self.feedback.react()
        if self.stop_requested:
            # same drain discipline as run_until_drained: the polled tail
            # is scored + committed, not abandoned to replay
            tail = self.assembler.flush()
            while tail:
                in_flight.append(self.dispatch_batch(tail))
                tail = self.assembler.flush()
        while in_flight:
            self.complete_batch(in_flight.popleft())
        self.drain_labels()
        return self.counters["scored"] - start
