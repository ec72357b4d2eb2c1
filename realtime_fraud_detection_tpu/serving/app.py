"""The scoring service: §2.7 API surface over the microbatched TPU scorer.

Endpoint parity with the reference FastAPI app (main.py:127-343):

    POST /predict             one transaction  -> FraudPrediction
    POST /batch-predict       list             -> {results, count, ...}
    GET  /health              liveness + model inventory
    GET  /metrics             JSON summary (throughput/latency/decisions)
    GET  /model-info          ensemble weights/strategy/mesh
    POST /reload-models       hot swap (from checkpoint dir or fresh init)
    GET  /metrics/prometheus  text exposition

plus capabilities the reference only promised:

    GET  /drift               feature drift report (config.py:110-116)
    POST /experiments         create an A/B experiment (ab_testing.py analog)
    GET  /experiments?name=   arm metrics + significance

The difference from the reference is the execution model: every concurrent
/predict coalesces through RequestMicrobatcher into ONE fused XLA program
call, instead of 5 asyncio tasks per request at batch=1
(ensemble_predictor.py:166-182), and /batch-predict scores the whole list in
bucketed dense batches instead of a sequential loop (main.py:235-248).
"""

from __future__ import annotations

import asyncio
import dataclasses
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from realtime_fraud_detection_tpu.checkpoint import CheckpointManager
from realtime_fraud_detection_tpu.obs import (
    DriftConfig,
    FeatureDriftMonitor,
    MetricsCollector,
)
from realtime_fraud_detection_tpu.scoring import init_scoring_models
from realtime_fraud_detection_tpu.scoring.scorer import FraudScorer
from realtime_fraud_detection_tpu.serving.batcher import RequestMicrobatcher
from realtime_fraud_detection_tpu.serving.httpd import HttpError, HttpServer
from realtime_fraud_detection_tpu.serving.validation import (
    validate_batch,
    validate_transaction,
)
from realtime_fraud_detection_tpu.testing import (
    ABTestManager,
    Variant,
    apply_weight_overrides,
)
from realtime_fraud_detection_tpu.utils.config import Config

__all__ = ["ServingApp"]


class ServingApp:
    """Wire scorer + batcher + obs + experiments behind the HTTP surface."""

    def __init__(self, config: Optional[Config] = None,
                 scorer: Optional[FraudScorer] = None,
                 host: Optional[str] = None, port: Optional[int] = None):
        self.config = config or Config()
        sc = self.config.serving
        self.scorer = scorer if scorer is not None else FraudScorer(self.config)
        self.metrics = MetricsCollector()
        self.drift = FeatureDriftMonitor(DriftConfig(
            num_features=self.scorer.sc.feature_dim))
        self.ab = ABTestManager()
        # deadline-aware QoS plane (qos/): always constructed so /qos can
        # enable it at runtime; admission/ladder only act when enabled.
        # Shares this app's MetricsCollector, so admitted/shed/ladder
        # series ride the existing Prometheus exposition.
        from realtime_fraud_detection_tpu.qos import QosPlane

        self.qos = QosPlane(self.config.qos, metrics=self.metrics)
        # continuous-learning plane (feedback/): always constructed so
        # /labels and /quality/live work out of the box; the join /
        # prequential / retrain machinery only runs when
        # config.feedback.enabled. Shares this app's drift monitor and
        # MetricsCollector; promotion goes through THIS app's score lock —
        # the same recipe /reload-models applies.
        from realtime_fraud_detection_tpu.feedback import FeedbackPlane
        from realtime_fraud_detection_tpu.feedback.plane import (
            promote_candidate,
        )

        self.feedback = FeedbackPlane(
            self.config.feedback, scorer=self.scorer, config=self.config,
            metrics=self.metrics, drift_monitor=self.drift,
            promote_fn=lambda cand: promote_candidate(
                self.scorer, self.config, cand, lock=self._score_lock))
        self._feedback_reacting = False
        # device-pool scoring (serving.device_pool): replicate the model
        # onto every addressable device; dispatches from the microbatcher
        # round-robin across per-device in-flight queues. Implies the
        # two-phase pipelined batcher (several batches must be in flight
        # for the replicas to see work) with its depth raised to the
        # pool's capacity.
        self.pool = getattr(self.scorer, "pool", None)
        if sc.device_pool and self.pool is None:
            from realtime_fraud_detection_tpu.scoring import DevicePool

            self.pool = DevicePool(self.scorer,
                                   inflight_depth=sc.inflight_depth)
        elif self.config.mesh.enabled and self.pool is None:
            # mesh-sharded branch execution (config.mesh / scoring/
            # mesh_executor.py): same dispatch/finalize seam as the pool
            # — the two-phase batcher, QoS masks and hot swap compose
            # unchanged — but each rotation slot is a data x model MESH
            # storing the configured branches sharded
            from realtime_fraud_detection_tpu.scoring import MeshExecutor

            mcfg = self.config.mesh
            self.pool = MeshExecutor(
                self.scorer, model_axis=mcfg.model,
                replicas=mcfg.replicas,
                inflight_depth=mcfg.inflight_depth,
                shard_branches=tuple(mcfg.shard_branches))
        # tracing plane (obs/tracing.py): per-transaction flight recorder
        # + /latency/breakdown + /slo. Constructed only when enabled —
        # the scoring path's no-op cost is one `is None` branch per batch.
        self.tracer = None
        if self.config.tracing.enabled:
            from realtime_fraud_detection_tpu.obs.tracing import Tracer

            self.tracer = Tracer(self.config.tracing)
        # fleet metrics aggregation (obs/fleetmetrics.py): per-worker
        # counter snapshots folded into one exposition at GET
        # /metrics/fleet — a ProcessFleet coordinator (or harness) feeds
        # worker snapshots in through this attribute; this process's own
        # tracer counters fold in at render time under its worker id
        from realtime_fraud_detection_tpu.obs.fleetmetrics import (
            FleetMetrics,
        )

        self.fleet_metrics = FleetMetrics()
        two_phase = sc.overlap_assembly or self.pool is not None
        # self-tuning host pipeline (serving.autotune / config.tuning):
        # the request microbatcher's close decisions move from the fixed
        # deadline to the arrival-aware just-in-time controller; the
        # online tuner reads the tracing plane's burn + the QoS ladder
        # through signals_fn so it freezes during emergencies
        self.tuning = None
        if sc.autotune or self.config.tuning.enabled:
            from realtime_fraud_detection_tpu.tuning import TuningPlane
            from realtime_fraud_detection_tpu.utils.config import (
                TuningSettings,
            )

            fields = {**dataclasses.asdict(self.config.tuning),
                      "enabled": True}
            if not two_phase or self.pool is not None:
                # pin the tuner's in-flight dimension where this path
                # cannot apply it: single-phase serving has no pipeline
                # depth, and with a device pool the depth IS the pool's
                # capacity — leaving the knob free would let the tuner
                # "trial" a no-op change and accept measurement noise as
                # an improvement
                depth = (self.pool.total_slots()
                         if self.pool is not None else 1)
                fields["inflight_min"] = fields["inflight_max"] = depth
            tset = TuningSettings(**fields)
            tset.validate(qos=self.config.qos)
            self.tuning = TuningPlane(tset)
            self.tuning.signals_fn = lambda: (
                (self.tracer.slo.burn_rate(
                    self.config.tracing.slo_fast_window_s)
                 if self.tracer is not None else 0.0),
                (self.qos.effective_level() if self.qos.enabled else 0))
        # consistent-hash shard router (cluster/hashring.py): with
        # config.cluster.enabled, /predict serves only users whose
        # partition the ring assigns to THIS worker_id; other keys get a
        # 421 naming the owning worker + address. Placement is a pure
        # function of (workers, n_partitions, virtual_nodes) — every
        # worker and every ingress computes the same answer with no
        # coordination traffic.
        # optional network-fault snapshot source (an object with
        # .snapshot(), e.g. chaos.netfaults.LinkFaultPlane): attached by
        # harnesses/drills that degrade this app's links; exposition
        # mirrors it through sync_netfaults so the serving plane renders
        # the same netfault_*/fenced_* series as a stream job would
        self.netfaults = None
        self.cluster_router = None
        cl = self.config.cluster
        if cl.enabled:
            from realtime_fraud_detection_tpu.cluster.hashring import (
                ShardRouter,
            )

            self.cluster_router = ShardRouter(
                cl.n_partitions, sorted(cl.workers),
                virtual_nodes=cl.virtual_nodes,
                addresses=dict(cl.workers))
        self.batcher = RequestMicrobatcher(
            self._score_batch_sync,
            max_batch=sc.microbatch_max_size,
            deadline_ms=sc.microbatch_deadline_ms,
            budget=self.qos.budget if self.config.qos.enabled else None,
            tracer=self.tracer,
            controller=self.tuning,
            # priority stamping mirrors the stream job: classes appear in
            # the queue-wait split only while the QoS plane is ENABLED
            # (it can be toggled at runtime via POST /qos) — without it,
            # traffic reports as "unclassified", never as classes that
            # no admission decision actually used
            classify_fn=lambda t: (self.qos.classify(t)
                                   if self.qos.enabled else ""),
            # two-phase pipelined scoring (serving.overlap_assembly): the
            # drain task dispatches batch N+1 (cache check + assembly +
            # device launch) while batch N still waits on the device in its
            # finalize task — per-waiter results keep arriving in order
            dispatch_fn=(self._dispatch_batch_sync if two_phase else None),
            finalize_fn=(self._finalize_batch_sync if two_phase else None),
            pipeline_depth=(self.pool.total_slots()
                            if self.pool is not None else 2),
        )
        self.http = HttpServer(host if host is not None else sc.host,
                               port if port is not None else sc.port)
        # dedicated Prometheus port (reference monitoring contract: metrics
        # on 8081 separate from the API; config.monitoring.enable_prometheus
        # + prometheus_port). 0 disables the extra listener — the main app
        # still serves /metrics/prometheus for annotation-based scraping.
        self.metrics_http: Optional[HttpServer] = None
        mon = self.config.monitoring
        if mon.enable_prometheus and mon.prometheus_port:
            self.metrics_http = HttpServer(
                host if host is not None else sc.host, mon.prometheus_port)
            self.metrics_http.route("GET", "/metrics",
                                    self._metrics_prometheus)
        self._reload_lock = asyncio.Lock()
        # prediction TTL cache (reference ensemble_predictor.py:437-471):
        # idempotent retries of a transaction_id serve the stored response
        from realtime_fraud_detection_tpu.serving.cache import PredictionCache

        self.prediction_cache = (
            PredictionCache(self.config.ensemble.cache_ttl_seconds,
                            self.config.ensemble.cache_max_entries)
            if sc.enable_prediction_cache else None
        )
        # FraudScorer and the drift monitor are single-writer; /predict's
        # microbatcher thread and /batch-predict's executor thread both call
        # _score_batch_sync, so serialize them (the device is serial anyway)
        self._score_lock = threading.Lock()
        # set by _predict (event loop) when the QoS served rung moved;
        # consumed by _dispatch_batch_sync (executor) under _score_lock.
        # Plain bool: single writer per side, torn reads impossible.
        self._qos_rung_dirty = False
        # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
        self._started = time.monotonic()
        # admission control (reference config.py:86 max_concurrent_
        # predictions, enforced): transactions admitted but not yet
        # answered. Beyond the cap, requests get an immediate 503 instead
        # of growing the microbatch queue without bound — load sheds at
        # the door, and the deadline batcher's latency contract holds for
        # everything admitted. Single event loop => plain counter.
        self._inflight_txns = 0
        self._register_routes()

    # --------------------------------------------------------------- scoring
    def _score_batch_sync(self, txns, trace=None) -> List[Dict[str, Any]]:
        """Runs in an executor thread: device call + obs write-back.

        The score lock is held for host-state mutation only (assembly at
        dispatch; write-back inside finalize) — NOT across the device wait,
        so a concurrent caller assembles its batch while this one's compute
        is in flight (the double-buffered serving path).
        """
        return self._finalize_batch_sync(self._dispatch_batch_sync(txns,
                                                                   trace))

    def _dispatch_batch_sync(self, txns, trace=None) -> tuple:
        """Pipeline stage 1 (executor thread): prediction-cache lookup +
        assemble + device launch, WITHOUT blocking on the result. The
        two-phase microbatcher (serving.overlap_assembly) calls this for
        batch N+1 while batch N's ``_finalize_batch_sync`` is still waiting
        on the device — host assembly overlaps device compute."""
        # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
        t0 = time.perf_counter()
        # serve idempotent retries from the prediction cache; only misses
        # go to the device (reference TTL-cache semantics)
        cache = self.prediction_cache
        cached: Dict[int, Dict[str, Any]] = {}
        to_score = txns
        if cache is not None:
            with self._score_lock:
                for i, txn in enumerate(txns):
                    hit = cache.get(str(txn.get("transaction_id", "")))
                    if hit is not None:
                        cached[i] = hit            # deep copy from the cache
            if cached:
                to_score = [t for i, t in enumerate(txns) if i not in cached]
        if trace is not None and cached:
            # cache hits never reach the device: close their traces with
            # the `cached` terminal and keep only the scored contexts on
            # the batch carrier (contexts align with txns by queue order)
            kept = []
            for i, c in enumerate(trace.contexts):
                if i in cached:
                    self.tracer.finish_terminal(c, "cached")
                else:
                    kept.append(c)
            trace.contexts = kept
        try:
            pending = None
            if to_score:
                with self._score_lock:
                    if self._qos_rung_dirty and self.qos.enabled:
                        # rung change flagged by _predict on the event
                        # loop; applied here under the lock this thread
                        # already holds for the dispatch
                        self._qos_rung_dirty = False
                        self.qos.apply_degradation(self.scorer)
                    pending = self.scorer.dispatch(to_score, trace=trace)
        except Exception:
            self.metrics.record_error("score")
            self._close_trace_error(trace)
            raise
        return (t0, txns, to_score, cached, pending, trace)

    def _close_trace_error(self, trace) -> None:
        """Close every open context on a failed batch with the `error`
        terminal — the waiters got the exception, but the flight
        recorder must still see the (worst-latency) failing
        transactions, exactly as the stream job records them. Never a
        silent gap."""
        if trace is None or self.tracer is None:
            return
        for c in trace.contexts:
            self.tracer.finish_terminal(c, "error")
        trace.contexts = []

    def _finalize_batch_sync(self, ctx: tuple) -> List[Dict[str, Any]]:
        """Pipeline stage 2 (executor thread): block on the device result,
        then run the obs/experiment/cache tail and reassemble request
        order."""
        t0, txns, to_score, cached, pending, trace = ctx
        cache = self.prediction_cache
        try:
            fresh = (self.scorer.finalize(pending, lock=self._score_lock)
                     if pending is not None else [])
        except Exception:
            self.metrics.record_error("score")
            self._close_trace_error(trace)
            raise
        # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
        dt = time.perf_counter() - t0
        # batch metrics count the same population as per-prediction metrics:
        # fresh results only — a cache hit costs ~0 and would deflate the
        # apparent batch latency per txn; an all-hit batch records nothing
        # (no device batch happened)
        if fresh:
            self.metrics.record_batch(len(fresh), dt)
        if self.config.monitoring.enable_drift_detection \
                and pending is not None \
                and not self.config.feedback.enabled:
            # with the feedback plane enabled, on_predictions below feeds
            # the same shared drift monitor — don't double-count the batch
            with self._score_lock:
                self.drift.update(pending.features)
        # experiments and per-prediction metrics run on FRESH results only:
        # a cache hit is a retry of an already-recorded transaction, and
        # re-recording it would feed correlated duplicate observations into
        # the A/B significance test and inflate decision metrics
        self._apply_experiments(to_score, fresh)
        if self.config.monitoring.enable_performance_tracking:
            per_txn = dt / max(len(fresh), 1)
            for r in fresh:
                self.metrics.record_prediction(
                    r["decision"], r["fraud_score"], per_txn,
                    r["model_predictions"])
        if cache is not None:
            # cache AFTER experiments: the stored response is exactly what
            # this request serves, so a retry is truly idempotent even when
            # a variant reweighted the score
            with self._score_lock:
                for r in fresh:
                    cache.put(r["transaction_id"], r)
        if self.config.feedback.enabled and fresh:
            # continuous-learning plane: register exactly what this batch
            # serves (post-experiment scores) with the label join + drift
            # monitor, then run the cheap trigger check; the expensive
            # retrain runs on a worker thread (_maybe_react)
            with self._score_lock:
                self.feedback.on_predictions(
                    to_score, fresh,
                    features=(pending.features if pending is not None
                              else None))
                self.feedback.check_trigger()
            self._maybe_react()
        if trace is not None and self.tracer is not None:
            # emit: the batch's waiters resolve right after this returns.
            # Closing here also feeds the SLO window; the burn gate is an
            # extra, hysteresis-guarded degradation signal on top of the
            # backlog ladder.
            self.tracer.finish_batch(trace)
            if self.qos.enabled:
                ts = self.config.tracing
                self.qos.observe_slo_burn(
                    self.tracer.slo.burn_rate(ts.slo_fast_window_s),
                    threshold=ts.slo_burn_threshold,
                    patience=ts.slo_gate_patience,
                    up_patience=ts.slo_gate_up_patience)
        # reassemble in request order
        if cached:
            results, it_fresh = [], iter(fresh)
            for i in range(len(txns)):
                results.append(cached[i] if i in cached else next(it_fresh))
        else:
            results = fresh
        return results

    def _apply_experiments(self, txns, results) -> None:
        """Route each txn through active experiments: treatment overrides
        re-weight the ensemble host-side (a weighted average over the 5
        returned model predictions — numerically identical to running the
        device combine with those weights), and every arm accumulates
        online metrics. Ground-truth labels, when the producer supplies
        them (simulator ``is_fraud``), feed the significance test."""
        alert_t = self.config.stream.alert_score_threshold
        base = self.config.normalized_weights()
        for txn, res in zip(txns, results):
            uid = str(txn.get("user_id", ""))
            for name in self.ab.active_experiments():
                variant = self.ab.assign(name, uid)
                if variant.overrides.get("weights"):
                    ens = self.config.ensemble
                    reweighted = apply_weight_overrides(
                        res["model_predictions"], base,
                        variant.overrides["weights"],
                        ens.confidence_threshold,
                        decline_threshold=ens.decline_threshold,
                        review_threshold=ens.review_threshold,
                        monitor_threshold=ens.monitor_threshold)
                    if reweighted is not None:
                        # decision + risk_level are recomputed with the new
                        # score so the served record stays consistent
                        res.update(reweighted)
                        res["fraud_score"] = reweighted["fraud_probability"]
                        res.setdefault("explanation", {})["experiment"] = {
                            "name": name, "variant": variant.name}
                actual = txn.get("is_fraud")
                self.ab.record_prediction(
                    name, variant.name, res["fraud_score"],
                    res["fraud_score"] > alert_t,
                    bool(actual) if actual is not None else None)

    def _maybe_react(self) -> None:
        """Kick the plane's retrain->gate->promote on a worker thread when
        a trigger is pending (never on the scoring path). One reaction in
        flight at a time; the promotion itself happens under the score
        lock inside promote_fn — the /reload-models recipe."""
        if self.feedback.pending_trigger is None or self._feedback_reacting:
            return
        self._feedback_reacting = True

        def _run() -> None:
            try:
                # O(n) shallow row snapshot under the ingest lock; the
                # expensive sort + stack and the training itself run
                # lock-free — the retrain must never block scoring
                with self._score_lock:
                    rows = self.feedback.buffer.snapshot_rows()
                arrays = self.feedback.buffer.arrays_from(
                    rows, self.feedback.buffer.store_history)
                self.feedback.react(arrays=arrays)
            finally:
                self._feedback_reacting = False

        threading.Thread(target=_run, name="feedback-retrain",
                         daemon=True).start()

    # ---------------------------------------------------------------- routes
    def _register_routes(self) -> None:
        r = self.http.route
        r("POST", "/predict", self._predict)
        r("POST", "/batch-predict", self._batch_predict)
        r("GET", "/health", self._health)
        r("GET", "/metrics", self._metrics)
        r("GET", "/model-info", self._model_info)
        r("POST", "/reload-models", self._reload_models)
        r("GET", "/metrics/prometheus", self._metrics_prometheus)
        r("GET", "/metrics/fleet", self._metrics_fleet)
        r("GET", "/drift", self._drift)
        r("POST", "/experiments", self._create_experiment)
        r("GET", "/experiments", self._experiment_results)
        r("GET", "/qos", self._qos_status)
        r("POST", "/qos", self._qos_configure)
        r("POST", "/labels", self._ingest_labels)
        r("GET", "/quality/live", self._quality_live)
        r("GET", "/latency/breakdown", self._latency_breakdown)
        r("GET", "/slo", self._slo_status)
        r("GET", "/autotune", self._autotune_status)
        r("GET", "/cluster", self._cluster_status)

    def _admit(self, n: int) -> None:
        limit = self.config.serving.max_concurrent_predictions
        if self._inflight_txns + n > limit:
            self.metrics.record_error("at_capacity")
            raise HttpError(
                503, f"at capacity ({self._inflight_txns} in flight, "
                     f"limit {limit})")
        self._inflight_txns += n

    def _release_on_done(self, fut: "asyncio.Future", n: int) -> None:
        """Free n admission slots when the batcher resolves ``fut`` — NOT
        when the HTTP waiter gives up. A timed-out request's transaction
        still sits in the microbatch queue and will be scored; releasing
        its slot early would let new admissions stack on top of abandoned
        work and grow the queue without bound."""
        def _done(f: "asyncio.Future") -> None:
            self._inflight_txns -= n
            if not f.cancelled():
                f.exception()        # consume, silencing "never retrieved"
        fut.add_done_callback(_done)

    async def _predict(self, body, query) -> Tuple[int, Any]:
        txn, errors = validate_transaction(body)
        if errors:
            raise HttpError(422, errors)
        if (self.cluster_router is not None
                and self.config.cluster.worker_id):
            # shard affinity ahead of admission: a wrong-shard request
            # must not burn this worker's QoS tokens or concurrency
            # slots. 421 Misdirected Request, with the owner's identity
            # and address so the caller (or the ingress) re-issues once.
            uid = str(txn.get("user_id", ""))
            owner = self.cluster_router.route(uid)
            if owner != self.config.cluster.worker_id:
                resp = {
                    "error": "wrong_shard",
                    "owner": owner,
                    "location": self.cluster_router.address_of(owner),
                    "partition": self.cluster_router.partition_of(uid),
                }
                carrier = txn.get("trace_carrier")
                if carrier is not None:
                    # redirect-aware carrier echo: bump the hop count so
                    # the eventual consumer books this bounce under the
                    # trace's redirect_hops stage; the caller copies the
                    # returned carrier onto the re-issued request
                    from realtime_fraud_detection_tpu.obs.tracing import (
                        make_carrier,
                        parse_carrier,
                    )

                    c = parse_carrier(carrier)
                    if c is not None:
                        resp["trace_carrier"] = make_carrier(
                            c["tid"], origin=c["org"],
                            produced_ts=c.get("ts"), priority=c["pr"],
                            fault=c["flt"], parent=c["sp"],
                            hops=int(c.get("rh", 0)) + 1,
                            redirect_s=float(c.get("rs", 0.0)))
                return 421, resp
        if self.qos.enabled:
            # QoS admission ahead of the concurrency gate: a shed is an
            # explicit score-with-reason (200, decision REVIEW, risk_level
            # SHED), so retriable overload is visible to the caller without
            # looking like record loss. The ladder observes the batcher
            # queue depth as its backlog signal.
            # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
            decision = self.qos.admit(txn, time.monotonic())
            if not decision.admitted:
                return 200, self.qos.shed_result(txn, decision)
            self.qos.observe_backlog(self.batcher.queue_depth)
            # A served-rung change is only FLAGGED here: the event loop
            # must never take _score_lock (an executor thread holds it
            # across multi-ms batch assembly — blocking here would freeze
            # every endpoint exactly when QoS is protecting latency). The
            # executor consumes the flag in _dispatch_batch_sync under
            # the lock it already holds, so set_degradation's mask +
            # rules_only writes can never race a dispatch into a torn
            # (mask from rung N, flag from rung N+1) pair — the
            # `rtfd lint` lock-order finding this path was rebuilt for.
            if self.qos.effective_level() != self.scorer.qos_level:
                self._qos_rung_dirty = True
        timeout = self.config.serving.prediction_timeout_seconds
        self._admit(1)
        try:
            fut = self.batcher.submit_nowait(txn)
        except (asyncio.QueueFull, RuntimeError):
            self._inflight_txns -= 1
            self.metrics.record_error("at_capacity")
            raise HttpError(503, "scoring queue full")
        self._release_on_done(fut, 1)
        # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
        t_enq = time.monotonic()
        try:
            # shield: the waiter's timeout must not cancel the scoring —
            # the batch containing this txn is already (or will be) on the
            # device; the slot frees via _release_on_done either way
            result = await asyncio.wait_for(asyncio.shield(fut),
                                            timeout=timeout)
        except asyncio.TimeoutError:
            self.metrics.record_error("timeout")
            raise HttpError(408, "prediction timed out")
        if self.qos.enabled:
            # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
            self.qos.record_completion(t_enq, time.monotonic())
        self.metrics.queue_depth.set(self.batcher.queue_depth)
        return 200, result

    async def _batch_predict(self, body, query) -> Tuple[int, Any]:
        txns, errors = validate_batch(
            body, self.config.serving.batch_size_limit)
        if errors:
            raise HttpError(422, errors)
        limit = self.config.serving.max_concurrent_predictions
        if len(txns) > limit:
            # oversize, not overload: no amount of retrying can ever fit
            # this batch under the concurrency cap, so reject it as
            # non-retryable instead of a transient 503
            raise HttpError(
                413, f"batch of {len(txns)} exceeds the concurrency "
                     f"capacity {limit}; split into smaller batches")
        # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
        t0 = time.perf_counter()
        self._admit(len(txns))
        try:
            loop = asyncio.get_running_loop()
            results = await loop.run_in_executor(
                None, self._score_batch_sync, txns)
        finally:
            self._inflight_txns -= len(txns)
        return 200, {
            "results": results,
            "count": len(results),
            # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
            "processing_time_ms": (time.perf_counter() - t0) * 1e3,
        }

    async def _health(self, body, query) -> Tuple[int, Any]:
        info = self.scorer.model_info()
        loaded = sum(1 for m in info["models"].values() if m["enabled"])
        payload = {
            "status": "healthy",
            "models_loaded": loaded,
            "num_models": info["num_models"],
            # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
            "uptime_seconds": time.monotonic() - self._started,
            "queue_depth": self.batcher.queue_depth,
        }
        if self.prediction_cache is not None:
            # lock-free by contract (cache.py): stats() reads only atomic
            # counters, and taking _score_lock here would stall the event
            # loop behind an executor thread's batch assembly
            payload["prediction_cache"] = self.prediction_cache.stats()
        return 200, payload

    async def _metrics(self, body, query) -> Tuple[int, Any]:
        payload = self.metrics.summary()
        payload["host_assembly"] = self.scorer.host_stats()
        if self.pool is not None:
            key = ("mesh" if hasattr(self.pool, "mesh_snapshot")
                   else "device_pool")
            payload[key] = self.pool.stats()
        return 200, payload

    async def _metrics_prometheus(self, body, query) -> Tuple[int, Any]:
        # mirror the scorer's host-assembly spans + cache counters and the
        # feedback plane's prequential/label/promotion series into the
        # registry at scrape time (cheap gauge sets + counter deltas)
        self.metrics.sync_host_stats(self.scorer.host_stats())
        self.metrics.sync_quant(self.scorer.quant_snapshot())
        self.metrics.sync_kernels(self.scorer.kernel_snapshot())
        self.metrics.sync_graph(self.scorer.graph_snapshot())
        self.metrics.sync_microbatch(self.batcher.close_reasons)
        if self.pool is not None:
            # a mesh executor mirrors through its own series (geometry,
            # placement, per-chip bytes); the replicated pool keeps the
            # device_pool_* family
            mesh_snap = getattr(self.pool, "mesh_snapshot", None)
            if mesh_snap is not None:
                self.metrics.sync_mesh(mesh_snap())
            else:
                self.metrics.sync_device_pool(self.pool.stats())
        if self.tracer is not None:
            self.metrics.sync_tracing(self.tracer.snapshot())
        if self.tuning is not None:
            self.metrics.sync_autotune(self.tuning.snapshot())
        if self.config.feedback.enabled:
            with self._score_lock:
                snap = self.feedback.snapshot()
            self.metrics.sync_feedback(snap)
        if self.cluster_router is not None:
            self.metrics.sync_cluster(self._cluster_snapshot())
        if self.netfaults is not None:
            self.metrics.sync_netfaults(self.netfaults.snapshot())
        return 200, self.metrics.render_prometheus()

    async def _metrics_fleet(self, body, query) -> Tuple[int, Any]:
        """Fleet-level Prometheus exposition: every worker's counters
        under a ``{worker=...}`` label plus honest unlabeled fleet sums,
        exactly one HELP/TYPE pair per family (obs/fleetmetrics.py).
        This process's own tracing counters fold in at render time under
        its cluster worker id, so a one-process deployment still renders
        an honest one-worker fleet."""
        from realtime_fraud_detection_tpu import __version__

        local_id = self.config.cluster.worker_id or "serving"
        if self.tracer is not None:
            self.fleet_metrics.ingest_cumulative(
                local_id,
                {f"trace_{k}": v
                 for k, v in self.tracer.counters.items()})
            self.fleet_metrics.set_worker_info(
                local_id, pid=os.getpid(), version=__version__)
        return 200, self.fleet_metrics.render(version=__version__)

    def _cluster_snapshot(self) -> Dict[str, Any]:
        """Serving-side cluster snapshot (router truth only — the stream
        fleet's snapshot additionally carries handoff/checkpoint ledgers;
        obs.metrics.sync_cluster accepts either shape)."""
        snap = self.cluster_router.snapshot()
        return {
            "workers_alive": len(snap["members"]),
            "workers": {
                m: {"partitions_owned": len(snap["assignment"].get(m, ()))}
                for m in snap["members"]
            },
            "router": snap,
        }

    async def _cluster_status(self, body, query) -> Tuple[int, Any]:
        """Shard-routing status: this worker's identity, the membership,
        the partition assignment, and the router's movement ledger."""
        if self.cluster_router is None:
            return 200, {"enabled": False}
        return 200, {
            "enabled": True,
            "worker_id": self.config.cluster.worker_id,
            **self.cluster_router.snapshot(),
        }

    async def _model_info(self, body, query) -> Tuple[int, Any]:
        return 200, self.scorer.model_info()

    async def _reload_models(self, body, query) -> Tuple[int, Any]:
        """Hot swap under a lock (reference main.py:291-305 +
        model_manager.py:348-380). Body options:
        {"checkpoint_dir": ..., "step": optional} — restore params (and host
        state if present) from a checkpoint; {"quality_artifact": path} —
        re-blend live from a quality-eval artifact (weights + validity are
        runtime tensors to the fused program, so a new measured blend
        deploys with ZERO recompiles; combinable with checkpoint_dir to
        swap params and blend together); {} — fresh re-init (dummy-model
        analog). The swap happens between batches: the scorer reads
        ``self.models`` once per score_batch call."""
        body = body or {}
        async with self._reload_lock:
            loop = asyncio.get_running_loop()
            source: Dict[str, Any] = {}
            blend_requested = "quality_artifact" in body
            if blend_requested:
                # VALIDATE the artifact up front (parse + schema + known
                # branch names) but apply it only AFTER the checkpoint
                # restore succeeds: a 404/409 restore must leave the live
                # blend untouched, and a half-applied update (new blend +
                # old params, or vice versa) must never serve.
                try:
                    weights = Config.load_selected_blend_weights(
                        str(body["quality_artifact"]))
                except FileNotFoundError as e:
                    raise HttpError(404, str(e))
                except (ValueError, OSError) as e:
                    raise HttpError(422, str(e))
                unknown = [n for n in weights
                           if n not in self.config.models]
                if unknown:
                    raise HttpError(
                        422, f"artifact names unknown model(s) {unknown}; "
                             f"configured: {sorted(self.config.models)}")
            if "checkpoint_dir" in body:
                step = body.get("step")
                if step is not None:
                    try:
                        step = int(step)
                    except (TypeError, ValueError):
                        raise HttpError(422, f"step must be an integer, "
                                             f"got {step!r}")
                if blend_requested:
                    # refuse to combine a checkpoint and a quality artifact
                    # that record DIFFERENT text-encoder architectures —
                    # the blend was measured with one model, the params are
                    # another; serving that pair silently mixes quality
                    # claims. Checked BEFORE the restore
                    # so a refusal leaves the live deployment untouched;
                    # {"allow_arch_mismatch": true} overrides explicitly.
                    art_tm = Config.load_artifact_text_model(
                        str(body["quality_artifact"]))
                    try:
                        ck_meta = (CheckpointManager(body["checkpoint_dir"])
                                   .manifest(step).get("metadata") or {})
                    except FileNotFoundError as e:
                        raise HttpError(404, str(e))
                    ck_tm = ck_meta.get("text_model")
                    if (art_tm is not None and ck_tm is not None
                            and dict(art_tm) != dict(ck_tm)
                            and not body.get("allow_arch_mismatch")):
                        raise HttpError(
                            409, f"text-encoder architecture mismatch: "
                                 f"artifact records {art_tm}, checkpoint "
                                 f"records {ck_tm}; pass "
                                 f"allow_arch_mismatch to combine anyway")

                def _restore():
                    # one shared recipe (checkpoint.restore_into_scorer):
                    # step resolved once, shape-aware template from the
                    # manifest, swap under the score lock. The same
                    # allow_arch_mismatch override also waives the
                    # quantization-mode stamp check — an int8 checkpoint
                    # never silently restores into an f32 scorer (409).
                    mgr = CheckpointManager(body["checkpoint_dir"])
                    return mgr.restore_into_scorer(
                        self.scorer, step=step, lock=self._score_lock,
                        allow_arch_mismatch=bool(
                            body.get("allow_arch_mismatch")))
                try:
                    ck = await loop.run_in_executor(None, _restore)
                except FileNotFoundError as e:
                    raise HttpError(404, str(e))
                except ValueError as e:
                    raise HttpError(409, str(e))   # config/shape mismatch
                source.update(checkpoint=body["checkpoint_dir"],
                              step=ck.step)
            elif blend_requested:
                pass                               # blend-only reload
            else:
                import jax

                seed = int(body.get("seed", 0))

                def _reinit():
                    fresh = init_scoring_models(
                        jax.random.PRNGKey(seed),
                        bert_config=self.scorer.bert_config,
                        feature_dim=self.scorer.sc.feature_dim,
                        node_dim=self.scorer.sc.node_dim)
                    with self._score_lock:
                        self.scorer.set_models(fresh)
                await loop.run_in_executor(None, _reinit)
                source["reinit_seed"] = seed
            if blend_requested:
                # params are in place; deploy the (pre-validated) blend.
                # Belt and suspenders: if the apply still fails, roll the
                # model table back and refresh, so the served blend is
                # either fully the old one or fully the new one.
                snapshot = {n: (mc.enabled, mc.weight)
                            for n, mc in self.config.models.items()}
                try:
                    applied = self.config.apply_quality_artifact(
                        str(body["quality_artifact"]))
                    with self._score_lock:
                        self.scorer.refresh_blend_from_config()
                except Exception:
                    for name, (was_enabled, was_weight) in snapshot.items():
                        mc = self.config.models[name]
                        mc.enabled = was_enabled
                        mc.weight = was_weight
                    with self._score_lock:
                        self.scorer.refresh_blend_from_config()
                    raise
                source["quality_artifact"] = {
                    "path": str(body["quality_artifact"]),
                    "weights": applied,
                }
            if self.prediction_cache is not None:
                # cached responses describe the replaced models; clear()
                # keeps the monotonic hit/miss counters /health exposes
                with self._score_lock:
                    self.prediction_cache.clear()
        return 200, {"status": "reloaded", "source": source}

    async def _qos_status(self, body, query) -> Tuple[int, Any]:
        """QoS plane status: ladder level, admission state, counters."""
        snap = self.qos.snapshot()
        snap["queue_depth"] = self.batcher.queue_depth
        return 200, snap

    async def _qos_configure(self, body, query) -> Tuple[int, Any]:
        """Update QoS knobs at runtime (all runtime tensors/host state —
        zero recompiles). Body: any subset of utils.config.QosSettings
        fields, e.g. {"enabled": true, "admission_rate": 20000,
        "budget_ms": 20}."""
        body = body or {}
        try:
            applied = self.qos.configure(body)
        except (TypeError, ValueError) as e:
            raise HttpError(422, str(e))
        # the budget only binds the batcher while the plane is enabled
        self.batcher.budget = (self.qos.budget
                               if self.config.qos.enabled else None)
        if not self.config.qos.enabled:
            # dropping back to a disabled plane also lifts any degradation
            with self._score_lock:
                self.scorer.set_degradation(None)
        return 200, {"status": "configured", "applied": applied,
                     "qos": self.qos.snapshot()}

    async def _ingest_labels(self, body, query) -> Tuple[int, Any]:
        """Ingest delayed ground-truth label events (the labels-topic
        seam over HTTP). Body: one event dict or a list of them; each
        needs ``transaction_id``, ``is_fraud`` and (optionally)
        ``label_ts``. Labels are joined to emitted predictions, feed the
        prequential metrics + labeled buffer, and can trigger a
        retrain."""
        if not self.config.feedback.enabled:
            raise HttpError(409, "feedback plane disabled "
                                 "(config.feedback.enabled)")
        events = body if isinstance(body, list) else [body]
        cleaned = []
        for ev in events:
            if not isinstance(ev, dict) or not ev.get("transaction_id") \
                    or "is_fraud" not in ev:
                raise HttpError(
                    422, "each label event needs transaction_id + is_fraud")
            ev = dict(ev)
            # rtfd-lint: allow[wall-clock] HTTP serving plane is real-time (no virtual-clock mode)
            ev.setdefault("label_ts", time.time())
            cleaned.append(ev)
        with self._score_lock:
            matched = self.feedback.on_labels(cleaned)
            self.feedback.check_trigger()
        self._maybe_react()
        return 200, {"ingested": len(cleaned), "matched": matched,
                     "join": self.feedback.join.stats()}

    async def _quality_live(self, body, query) -> Tuple[int, Any]:
        """Live model quality under delayed ground truth: prequential
        sliding/fading AUC + precision/recall at the pinned operating
        point, calibration error, per-branch drop-one attribution,
        label-join health, buffer occupancy, and the retrain/gate/
        promotion audit tail. Snapshotted under the score lock — the
        executor thread mutates the plane's windows under the same lock."""
        with self._score_lock:
            return 200, self.feedback.snapshot()

    async def _latency_breakdown(self, body, query) -> Tuple[int, Any]:
        """Critical-path decomposition of the captured trace window:
        additive per-stage contributions to the p50/p95/p99 end-to-end
        latency with the dominant stage flagged, plus the slowest-N
        exemplar trace ids (obs/tracing.py breakdown)."""
        if self.tracer is None:
            return 200, {"enabled": False, "n": 0,
                         "hint": "start with --trace or "
                                 "config.tracing.enabled"}
        return 200, self.tracer.breakdown()

    async def _slo_status(self, body, query) -> Tuple[int, Any]:
        """SLO burn-rate status: objective, fast/slow-window violation
        fractions + burn rates, and the QoS gate the burn signal feeds."""
        if self.tracer is None:
            return 200, {"enabled": False}
        payload = self.tracer.slo.snapshot()
        payload["enabled"] = True
        payload["qos_gate"] = {
            "engaged": self.qos.slo_engaged,
            "threshold": self.config.tracing.slo_burn_threshold,
        }
        return 200, payload

    async def _autotune_status(self, body, query) -> Tuple[int, Any]:
        """Self-tuning plane state: the forecast, the JIT controller's
        decision mix + live knob values, and the tuner's trial/freeze
        counters (tuning/plane.py snapshot)."""
        if self.tuning is None:
            return 200, {"enabled": False,
                         "hint": "start with --autotune or "
                                 "config.tuning.enabled"}
        return 200, self.tuning.snapshot()

    async def _drift(self, body, query) -> Tuple[int, Any]:
        rep = self.drift.report()
        return 200, {
            "drifted": rep.drifted,
            "max_psi": rep.max_psi,
            "top_features": rep.top_features[:10],
            "psi": [float(x) for x in rep.psi],
            "rows_seen": rep.rows_seen,
            "baseline_frozen": rep.baseline_frozen,
        }

    async def _create_experiment(self, body, query) -> Tuple[int, Any]:
        body = body or {}
        try:
            name = body["name"]
            if "from_quality_artifact" in body:
                # canary a measured blend: control = production weights,
                # treatment = the artifact's selected blend at `traffic`.
                # Every artifact branch must be ENABLED in the live scorer:
                # host-side re-weighting can only use predictions the fused
                # program returned (a disabled branch's weight would be
                # silently renormalized away — a control-vs-wrong-thing
                # experiment). Enable first via /reload-models.
                from realtime_fraud_detection_tpu.scoring import MODEL_NAMES
                from realtime_fraud_detection_tpu.utils.config import (
                    Config,
                )

                art = str(body["from_quality_artifact"])
                weights = Config.load_selected_blend_weights(art)
                disabled = [
                    n for n in weights
                    if n in MODEL_NAMES
                    and not self.scorer.model_valid[MODEL_NAMES.index(n)]
                ]
                if disabled:
                    raise HttpError(
                        409, f"artifact blend uses branch(es) {disabled} "
                             f"that are disabled in the current "
                             f"deployment; enable them first (POST "
                             f"/reload-models with the artifact)")
                self.ab.experiment_from_artifact(
                    name, art,
                    traffic=float(body.get("traffic", 0.5)),
                    salt=body.get("salt", ""))
            else:
                variants = [Variant(v["name"], float(v["traffic"]),
                                    v.get("overrides", {}))
                            for v in body["variants"]]
                self.ab.create_experiment(name, variants,
                                          salt=body.get("salt", ""))
        except FileNotFoundError as e:
            raise HttpError(404, str(e))
        except (KeyError, TypeError) as e:
            raise HttpError(422, f"bad experiment spec: {e}")
        except ValueError as e:
            raise HttpError(422, str(e))
        return 200, {"status": "created", "experiment": name}

    async def _experiment_results(self, body, query) -> Tuple[int, Any]:
        name = query.get("name")
        if not name:
            raise HttpError(422, "query param 'name' required")
        try:
            return 200, self.ab.results(name)
        except KeyError:
            raise HttpError(404, f"no experiment {name!r}")

    # -------------------------------------------------------------- lifecycle
    async def start(self) -> None:
        await self.batcher.start()
        await self.http.start()
        if self.metrics_http is not None:
            await self.metrics_http.start()

    async def stop(self) -> None:
        if self.metrics_http is not None:
            await self.metrics_http.stop()
        await self.http.stop()
        await self.batcher.stop()

    @property
    def port(self) -> int:
        return self.http.port

    def run_forever(self) -> None:               # pragma: no cover - CLI path
        """Serve until SIGTERM/SIGINT, then stop GRACEFULLY: the HTTP
        server closes first (no new admissions), then the microbatcher
        drains — every already-admitted transaction is scored and its
        waiter resolved before the process exits. A mid-batch SIGTERM
        loses nothing (the graceful-shutdown satellite, ISSUE 12); only
        SIGKILL abandons in-flight work, by definition."""
        import signal as _signal

        async def _main():
            await self.start()
            stopping = asyncio.Event()
            loop = asyncio.get_running_loop()
            for sig in (_signal.SIGTERM, _signal.SIGINT):
                try:
                    loop.add_signal_handler(sig, stopping.set)
                except (NotImplementedError, RuntimeError, ValueError):
                    pass          # platform/thread without signal support
            try:
                await stopping.wait()
            finally:
                await self.stop()

        asyncio.run(_main())
