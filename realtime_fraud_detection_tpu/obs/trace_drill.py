"""Deterministic tracing drill: prove the plane on a virtual clock.

Drives the REAL stream path — MicrobatchAssembler → StreamJob.dispatch_batch/
complete_batch → tracing plane → QoS SLO gate → fan-out — with the two
substitutions every drill in this repo makes (qos/drill.py, feedback/drill.py):
time is a virtual clock, and the device is a deterministic stand-in scorer
whose per-stage costs are exact virtual durations. That makes the drill
reproducible bit-for-bit on any CPU, and lets it INJECT a slow stage:

- a slow-assembly run must be attributed to ``assemble`` by the
  critical-path analyzer (``Tracer.breakdown``),
- a slow-device run to ``device_wait``, with the SLO burn rate spiking
  over the threshold (the injected violation), engaging the QoS gate, and
  recovering once the violation clears,
- FIFO order and shed decisions must be IDENTICAL with tracing on vs off
  (the plane observes, never perturbs),
- the wall-clock overhead of the tracing plane itself must stay under the
  pinned per-transaction bound (and the disabled path under an even
  tighter one — the measured no-op contract).

Used by ``rtfd trace-drill`` (final stdout line: a compact <2 KB JSON
verdict) and smoke-tested in tier-1.
"""

from __future__ import annotations

import dataclasses
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from realtime_fraud_detection_tpu.obs.tracing import Tracer
from realtime_fraud_detection_tpu.utils.config import (
    QosSettings,
    TracingSettings,
)

__all__ = ["TraceDrillConfig", "run_trace_drill", "compact_trace_summary"]


@dataclasses.dataclass
class TraceDrillConfig:
    seed: int = 7
    max_batch: int = 64
    max_delay_ms: float = 5.0
    bursts_per_phase: int = 24
    # injected per-batch virtual stage costs (ms)
    fast_ms: float = 1.0
    slow_assemble_ms: float = 12.0
    slow_device_ms: float = 30.0
    pack_ms: float = 0.2
    dispatch_ms: float = 0.2
    finalize_ms: float = 0.3
    per_txn_us: float = 5.0
    # SLO objective + drill-scale windows (virtual seconds)
    objective_ms: float = 20.0
    slo_fast_window_s: float = 0.4
    slo_slow_window_s: float = 1.6
    slo_bucket_s: float = 0.02
    slo_burn_threshold: float = 2.0
    # wall-clock overhead pins: the enabled plane per scored txn, and the
    # disabled fast path (which must be near-free)
    overhead_txns: int = 4096
    overhead_bound_us: float = 75.0
    noop_bound_us: float = 5.0

    @staticmethod
    def fast() -> "TraceDrillConfig":
        return TraceDrillConfig(bursts_per_phase=8, overhead_txns=1536)


class _NoCache:
    """The drill generates unique transaction ids; dedupe never hits."""

    def get_transaction(self, txn_id, now=None):
        return None


class _DrillPending:
    __slots__ = ("records", "n", "features", "done_at", "trace", "cost_s")

    def __init__(self, records, done_at, trace, cost_s):
        self.records = list(records)
        self.n = len(self.records)
        self.features = None
        self.done_at = done_at
        self.trace = trace
        self.cost_s = cost_s


class TraceDrillScorer:
    """Deterministic FraudScorer stand-in with injectable stage costs.

    Advances the shared virtual clock through assemble/pack/dispatch on
    ``dispatch`` and through the device wait + finalize on ``finalize``,
    making the SAME trace marks the real scorer makes — the clock
    advances are unconditional, so traced and untraced runs follow
    identical virtual timelines (the FIFO/shed-equality pin depends on
    it). The QoS ladder's rungs genuinely buy device capacity
    (``SPEEDUP``), so the SLO gate closes a real control loop.
    """

    SPEEDUP = (1.0, 2.0, 4.0, 8.0)

    def __init__(self, clock: List[float], cfg: TraceDrillConfig):
        self.clock = clock
        self.cfg = cfg
        self.assemble_ms = cfg.fast_ms
        self.device_ms = cfg.fast_ms
        self.model_valid = np.ones(5, bool)
        self.txn_cache = _NoCache()
        self.qos_level = 0
        self.max_level_seen = 0     # did the gate actually degrade us?
        self._qos_rules_only = False

    def set_degradation(self, mask, rules_only: bool = False,
                        level: int = 0) -> None:
        self.qos_level = int(level)
        self.max_level_seen = max(self.max_level_seen, self.qos_level)
        self._qos_rules_only = bool(rules_only)

    def batch_cost_s(self, n: int) -> float:
        c = self.cfg
        host = (self.assemble_ms + c.pack_ms + c.dispatch_ms
                + n * c.per_txn_us / 1e3)
        dev = self.device_ms / self.SPEEDUP[self.qos_level]
        return (host + dev + c.finalize_ms) / 1e3

    def dispatch(self, records, now: Optional[float] = None,
                 trace: Optional[Any] = None) -> _DrillPending:
        c = self.cfg
        n = len(records)
        if trace is not None:
            trace.mark("assemble")
        self.clock[0] += (self.assemble_ms + n * c.per_txn_us / 1e3) / 1e3
        if trace is not None:
            trace.mark("pack")
        self.clock[0] += c.pack_ms / 1e3
        if trace is not None:
            trace.mark("dispatch")
        self.clock[0] += c.dispatch_ms / 1e3
        if trace is not None:
            trace.mark("device_wait")
        dev_s = (self.device_ms / self.SPEEDUP[self.qos_level]) / 1e3
        return _DrillPending(records, self.clock[0] + dev_s, trace,
                             self.batch_cost_s(n))

    def finalize(self, pending: _DrillPending,
                 now: Optional[float] = None, lock=None) -> List[Dict]:
        self.clock[0] = max(self.clock[0], pending.done_at)
        if pending.trace is not None:
            pending.trace.mark("finalize")
        self.clock[0] += self.cfg.finalize_ms / 1e3
        results = []
        for r in pending.records:
            tid = str(r.get("transaction_id", ""))
            score = (zlib.crc32(tid.encode()) % 650) / 1000.0
            results.append({
                "transaction_id": tid,
                "fraud_probability": score,
                "fraud_score": score,
                "risk_level": "LOW" if score < 0.3 else "MEDIUM",
                "decision": "APPROVE" if score < 0.6
                            else "APPROVE_WITH_MONITORING",
                "model_predictions": {},
                "confidence": 0.9,
                "processing_time_ms": pending.cost_s * 1e3
                                      / max(pending.n, 1),
                "explanation": {"drill": True,
                                "ladder_level": self.qos_level},
            })
        return results


def _burst_arrivals(cfg: TraceDrillConfig, t0: float, gap_s: float,
                    prefix: str, amount_fn=None
                    ) -> List[Tuple[float, Dict[str, Any]]]:
    """``bursts_per_phase`` bursts of exactly ``max_batch`` records, one
    burst per virtual instant: each burst closes one full (size-triggered)
    microbatch, so per-stage costs are deterministic and no backlog forms
    unless a phase injects one."""
    arrivals = []
    i = 0
    for b in range(cfg.bursts_per_phase):
        ts = t0 + b * gap_s
        for _ in range(cfg.max_batch):
            amount = amount_fn(i) if amount_fn is not None else 60.0
            arrivals.append((ts, {
                "transaction_id": f"{prefix}-{i}",
                "user_id": f"u{i % 97}",
                "merchant_id": f"m{i % 31}",
                "amount": amount,
                "timestamp": str(ts),
            }))
            i += 1
    return arrivals


def _make_job(clock, scorer, tracer, qos_plane, cfg: TraceDrillConfig):
    from realtime_fraud_detection_tpu.stream.job import JobConfig, StreamJob
    from realtime_fraud_detection_tpu.stream.microbatch import (
        MicrobatchAssembler,
    )
    from realtime_fraud_detection_tpu.stream.transport import InMemoryBroker

    broker = InMemoryBroker()
    job = StreamJob(broker, scorer, JobConfig(
        max_batch=cfg.max_batch, max_delay_ms=cfg.max_delay_ms,
        emit_features=False, emit_enriched=False,
        qos=qos_plane, tracing=tracer))
    job.assembler = MicrobatchAssembler(
        job.consumer, max_batch=cfg.max_batch,
        max_delay_ms=cfg.max_delay_ms, clock=lambda: clock[0])
    return broker, job


def _drive(clock, broker, job, arrivals) -> None:
    from realtime_fraud_detection_tpu.stream import topics as T

    next_i = 0
    idle_step = 0.001
    while True:
        while next_i < len(arrivals) and arrivals[next_i][0] <= clock[0]:
            ts, txn = arrivals[next_i]
            broker.produce(T.TRANSACTIONS, txn, key=txn["user_id"],
                           timestamp=ts)
            next_i += 1
        batch = job.assembler.next_batch(block=False)
        if not batch and next_i >= len(arrivals):
            batch = job.assembler.flush()
        if batch:
            ctx = job.dispatch_batch(batch, now=clock[0])
            if ctx is not None:
                job.complete_batch(ctx, now=clock[0])
            continue
        if next_i >= len(arrivals) and job.consumer.lag() == 0:
            return
        clock[0] = (max(clock[0] + idle_step, arrivals[next_i][0])
                    if next_i < len(arrivals) else clock[0] + idle_step)


def _tracing_settings(cfg: TraceDrillConfig) -> TracingSettings:
    return TracingSettings(
        enabled=True, ring_size=8192, slowest_n=16,
        slo_objective_ms=cfg.objective_ms,
        slo_fast_window_s=cfg.slo_fast_window_s,
        slo_slow_window_s=cfg.slo_slow_window_s,
        slo_bucket_s=cfg.slo_bucket_s,
        slo_burn_threshold=cfg.slo_burn_threshold,
        slo_gate_patience=2, slo_gate_up_patience=4)


def _measure_overhead(cfg: TraceDrillConfig) -> Dict[str, float]:
    """Wall-clock cost of the tracing plane itself, per transaction:
    begin + batch + the five batch marks + finish, at the drill's batch
    size — exactly the per-batch work the hot path pays. The disabled
    path runs the identical loop against an off tracer (every call
    returns None immediately)."""
    def loop(tracer: Tracer, n_txns: int) -> float:
        bs = cfg.max_batch
        # rtfd-lint: allow[wall-clock] measures real host overhead (the drill's pinned bound)
        t0 = time.perf_counter()
        done = 0
        i = 0
        while done < n_txns:
            ctxs = [tracer.begin(f"oh-{i + k}") for k in range(bs)]
            i += bs
            tb = tracer.batch(ctxs, batch_size=bs)
            if tb is not None:
                for s in ("assemble", "pack", "dispatch", "device_wait",
                          "finalize"):
                    tb.mark(s)
            tracer.finish_batch(tb)
            done += bs
        # rtfd-lint: allow[wall-clock] measures real host overhead (the drill's pinned bound)
        return (time.perf_counter() - t0) / done * 1e6

    on = Tracer(_tracing_settings(cfg))
    off = Tracer(dataclasses.replace(_tracing_settings(cfg), enabled=False))
    # best of 3: the bound pins the plane's cost, not scheduler noise
    on_us = min(loop(on, cfg.overhead_txns) for _ in range(3))
    off_us = min(loop(off, cfg.overhead_txns) for _ in range(3))
    return {"enabled_us_per_txn": round(on_us, 3),
            "disabled_us_per_txn": round(off_us, 4),
            "bound_us": cfg.overhead_bound_us,
            "noop_bound_us": cfg.noop_bound_us}


def run_trace_drill(cfg: Optional[TraceDrillConfig] = None) -> Dict[str, Any]:
    from realtime_fraud_detection_tpu.qos import QosPlane
    from realtime_fraud_detection_tpu.stream import topics as T

    cfg = cfg or TraceDrillConfig()
    clock = [0.0]
    tracer = Tracer(_tracing_settings(cfg), clock=lambda: clock[0])
    qos = QosPlane(QosSettings(enabled=True, budget_ms=cfg.objective_ms,
                               ladder_high_backlog=1e9,   # gate drives, not
                               ladder_low_backlog=1e8))   # the backlog signal
    scorer = TraceDrillScorer(clock, cfg)
    summary: Dict[str, Any] = {"config": dataclasses.asdict(cfg)}

    def run_phase(name: str, assemble_ms: float, device_ms: float,
                  gap_s: float) -> Dict[str, Any]:
        scorer.assemble_ms = assemble_ms
        scorer.device_ms = device_ms
        scorer.max_level_seen = scorer.qos_level
        tracer.reset()      # fresh attribution window; SLO history persists
        broker, job = _make_job(clock, scorer, tracer, qos, cfg)
        t_start = clock[0]
        arrivals = _burst_arrivals(cfg, clock[0] + 0.01, gap_s, name)
        _drive(clock, broker, job, arrivals)
        bd = tracer.breakdown()
        # peak burn over the phase, reconstructed from the retained SLO
        # buckets (the gate may have already degraded the scorer and let
        # the burn decay by phase end — the PEAK is what "reacted" means)
        burn_peak = 0.0
        t = t_start
        while t <= clock[0] + cfg.slo_bucket_s:
            burn_peak = max(burn_peak, tracer.slo.burn_rate(
                cfg.slo_fast_window_s, now=t))
            t += cfg.slo_bucket_s
        return {
            "scored": job.counters["scored"],
            "breakdown_p99": bd["quantiles"].get("p99", {}),
            "dominant_stage": bd["quantiles"].get("p99", {}).get(
                "dominant_stage"),
            "burn_fast": round(
                tracer.slo.burn_rate(cfg.slo_fast_window_s), 3),
            "burn_peak": round(burn_peak, 3),
            "gate_engaged": qos.slo_engaged,
            "max_degradation_level": scorer.max_level_seen,
            "traces_recorded": len(tracer.traces()),
        }

    # phase 1: injected slow assembly — analyzer must name `assemble`
    gap_slow_a = (cfg.slow_assemble_ms + cfg.fast_ms + 5.0) / 1e3 * 1.5
    phase_a = run_phase("slowasm", cfg.slow_assemble_ms, cfg.fast_ms,
                        gap_slow_a)
    summary["slow_assembly"] = phase_a

    # phase 2: injected slow device — analyzer must name `device_wait`,
    # and every e2e blows the objective: the burn rate must spike over
    # the threshold and engage the QoS gate
    gap_slow_d = (cfg.slow_device_ms + cfg.fast_ms + 5.0) / 1e3 * 1.5
    phase_d = run_phase("slowdev", cfg.fast_ms, cfg.slow_device_ms,
                        gap_slow_d)
    summary["slow_device"] = phase_d

    # phase 3: violation cleared — fresh fast traffic, then let the fast
    # window age out; the burn rate must fall back under the threshold
    # and the gate must disengage (the run loops feed the gate once per
    # batch; the drill's tail is that loop made explicit)
    phase_r = run_phase("recover", cfg.fast_ms, cfg.fast_ms, 0.01)
    clock[0] += cfg.slo_fast_window_s + cfg.slo_bucket_s
    recovery_obs = 0
    while qos.slo_engaged and recovery_obs < 32:
        qos.observe_slo_burn(
            tracer.slo.burn_rate(cfg.slo_fast_window_s),
            threshold=cfg.slo_burn_threshold, patience=2, up_patience=4)
        recovery_obs += 1
    burn_final = tracer.slo.burn_rate(cfg.slo_fast_window_s)
    summary["recovery"] = {**phase_r,
                           "burn_final": round(burn_final, 3),
                           "recovery_observations": recovery_obs,
                           "gate_engaged_final": qos.slo_engaged}
    summary["slo"] = tracer.slo.snapshot()

    # phase 4: FIFO + shed equality, traced vs untraced — identical
    # arrival schedule, identical admission-limited QoS plane, fresh
    # virtual clocks; the predictions topic must read back identically
    def shed_run(traced: bool) -> Tuple[List[tuple], set, int]:
        run_clock = [0.0]
        run_scorer = TraceDrillScorer(run_clock, cfg)
        run_scorer.assemble_ms = cfg.fast_ms
        run_scorer.device_ms = cfg.fast_ms
        capacity = cfg.max_batch / run_scorer.batch_cost_s(cfg.max_batch)
        run_qos = QosPlane(QosSettings(
            enabled=True, budget_ms=cfg.objective_ms,
            admission_rate=capacity * 0.25,
            admission_burst=cfg.max_batch * 1.5))
        run_tracer = (Tracer(_tracing_settings(cfg),
                             clock=lambda: run_clock[0])
                      if traced else None)
        broker, job = _make_job(run_clock, run_scorer, run_tracer,
                                run_qos, cfg)

        def amount_fn(i: int) -> float:
            return (1000.0, 60.0, 5.0)[(0 if i % 10 < 2 else
                                        1 if i % 10 < 7 else 2)]

        arrivals = _burst_arrivals(cfg, 0.01, 0.01, "shed", amount_fn)
        _drive(run_clock, broker, job, arrivals)
        preds = broker.consumer([T.PREDICTIONS], "check").poll(
            len(arrivals) + 10)
        seq = [(str(r.value["transaction_id"]),
                round(float(r.value["fraud_score"]), 6)) for r in preds]
        shed_ids = {str(r.value["transaction_id"]) for r in preds
                    if (r.value.get("explanation") or {}).get("shed")}
        return seq, shed_ids, job.counters["shed"]

    seq_off, shed_off, n_shed_off = shed_run(traced=False)
    seq_on, shed_on, n_shed_on = shed_run(traced=True)
    summary["fifo_shed"] = {
        "emitted": len(seq_on),
        "shed_traced": n_shed_on,
        "shed_untraced": n_shed_off,
        "fifo_identical": seq_on == seq_off,
        "shed_identical": shed_on == shed_off and n_shed_on == n_shed_off,
    }

    # phase 5: the tracing plane's own wall-clock cost per transaction
    summary["overhead"] = _measure_overhead(cfg)

    checks = {
        "slow_assembly_attributed":
            phase_a["dominant_stage"] == "assemble",
        "slow_device_attributed":
            phase_d["dominant_stage"] == "device_wait",
        "slo_burn_reacted":
            phase_d["burn_peak"] > cfg.slo_burn_threshold
            and phase_d["max_degradation_level"] >= 1,
        "slo_recovered":
            not qos.slo_engaged
            and burn_final <= cfg.slo_burn_threshold,
        "fifo_identical": summary["fifo_shed"]["fifo_identical"],
        "shed_identical": summary["fifo_shed"]["shed_identical"],
        "sheds_nonzero": n_shed_on > 0,
        "overhead_under_bound":
            summary["overhead"]["enabled_us_per_txn"]
            < cfg.overhead_bound_us,
        "noop_under_bound":
            summary["overhead"]["disabled_us_per_txn"]
            < cfg.noop_bound_us,
    }
    summary["checks"] = checks
    summary["passed"] = all(checks.values())
    return summary


def compact_trace_summary(summary: Dict[str, Any]) -> Dict[str, Any]:
    """The <2 KB final-stdout-line verdict."""
    oh = summary["overhead"]
    return {
        "drill": "trace",
        "passed": summary["passed"],
        "checks": summary["checks"],
        "dominant": {
            "slow_assembly": summary["slow_assembly"]["dominant_stage"],
            "slow_device": summary["slow_device"]["dominant_stage"],
        },
        "burn": {
            "slow_device_peak": summary["slow_device"]["burn_peak"],
            "final": summary["recovery"]["burn_final"],
            "threshold": summary["config"]["slo_burn_threshold"],
        },
        "shed": {
            "traced": summary["fifo_shed"]["shed_traced"],
            "untraced": summary["fifo_shed"]["shed_untraced"],
        },
        "overhead_us_per_txn": oh["enabled_us_per_txn"],
        "noop_us_per_txn": oh["disabled_us_per_txn"],
        "bound_us": oh["bound_us"],
    }
