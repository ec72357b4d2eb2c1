"""Unified typed configuration tree.

The reference has three disjoint config systems that drift from one another
(SURVEY.md section 5.6: Java JobConfig.java, Python config.py + an unloaded
configs/models.json, simulator argparse; the k8s ConfigMap even ships
*different* ensemble weights). Here there is exactly one tree with layering:

    defaults -> JSON file (``Config.from_file``) -> env vars (``RTFD_*`` and
    the reference's own names) -> explicit kwargs / CLI.

Model registry semantics mirror reference config.py:126-199 (names, types,
weights, hyperparameters); ensemble thresholds mirror config.py:118-124 and
ensemble_predictor.py:344-369.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List


VALID_STRATEGIES = ("weighted_average", "voting", "stacking")


def _env(name: str, default: str, *aliases: str) -> str:
    for key in (f"RTFD_{name}", name, *aliases):
        val = os.getenv(key)
        if val is not None:
            return val
    return default


@dataclass
class ModelConfig:
    """Per-model configuration (reference config.py:9-18)."""

    name: str
    model_type: str  # 'gbdt' | 'lstm' | 'bert' | 'gnn' | 'isolation_forest'
    weight: float = 1.0
    enabled: bool = True
    # reference parity field (config.py:13 per-model artifact path). Unused
    # by design here: all five branches live in ONE orbax checkpoint
    # (checkpoint.py) addressed by directory+step, not per-model files —
    # per-branch swaps go through set_models/per-branch validity instead.
    model_path: str = ""
    hyperparameters: Dict[str, Any] = field(default_factory=dict)


# Decision-ladder rung defaults (ensemble_predictor.py:344-356) — the ONE
# definition shared by EnsembleConfig, EnsembleParams, the compiled ladder
# (ensemble/combine.py) and its host-side twin (features/rules.py), so a
# default can't silently drift between them. Lives here because this module
# has no heavy deps and everything else already imports it.
DECLINE_THRESHOLD_DEFAULT = 0.95
REVIEW_THRESHOLD_DEFAULT = 0.8
MONITOR_THRESHOLD_DEFAULT = 0.6


@dataclass
class EnsembleConfig:
    """Ensemble strategy + decision thresholds (config.py:21-27)."""

    strategy: str = "weighted_average"  # weighted_average | voting | stacking
    confidence_threshold: float = 0.7
    fraud_threshold: float = 0.5
    enable_explanation: bool = True
    # Decision ladder (ensemble_predictor.py:344-356); validate() enforces
    # 0 <= monitor <= review <= decline <= 1 (a misordered ladder would
    # silently shadow rungs)
    decline_threshold: float = DECLINE_THRESHOLD_DEFAULT
    review_threshold: float = REVIEW_THRESHOLD_DEFAULT
    monitor_threshold: float = MONITOR_THRESHOLD_DEFAULT
    # Prediction cache (ensemble_predictor.py:57-58, 460-471)
    cache_ttl_seconds: float = 300.0
    cache_max_entries: int = 1000


# Branches whose params may take the sharded placement on the serving
# mesh (scoring/mesh_executor.py; parallel/layouts.SHARDABLE_BRANCHES maps
# these onto ScoringModels fields — a test pins the two in sync). Trees /
# iforest / rules are replicated by design.
MESH_SHARDABLE_BRANCHES = ("bert_text", "lstm_sequential", "graph_neural")


@dataclass
class MeshSettings:
    """Mesh geometry: the (data, model, seq) axes for core/mesh.py AND the
    GSPMD serving executor's knobs (scoring/mesh_executor.py).

    ``enabled`` opts a serving/stream deployment into mesh-sharded branch
    execution: ``replicas`` independent ``data x model`` meshes in
    round-robin rotation (pool x mesh — replicate the MESH, not the
    chip), each storing the ``shard_branches`` params sharded over
    ``model`` (per-chip HBM ~1/model) while the microbatch shards over
    ``data``. Off by default — the replicated DevicePool remains the
    baseline plane; ``rtfd mesh-drill`` gates the sharded path's
    bit-equality contract.
    """

    data: int | None = None
    model: int = 1
    seq: int = 1
    # serving executor (scoring/mesh_executor.py)
    enabled: bool = False
    replicas: int = 1
    inflight_depth: int = 2
    shard_branches: List[str] = field(
        default_factory=lambda: ["bert_text"])

    def validate(self) -> None:
        if self.model < 1 or self.seq < 1:
            raise ValueError(
                f"mesh axes must be >= 1, got model={self.model} "
                f"seq={self.seq}")
        if self.replicas < 1 or self.inflight_depth < 1:
            raise ValueError(
                "mesh.replicas and mesh.inflight_depth must be >= 1")
        bad = [b for b in self.shard_branches
               if b not in MESH_SHARDABLE_BRANCHES]
        if bad:
            raise ValueError(
                f"mesh.shard_branches {bad} not shardable; valid: "
                f"{list(MESH_SHARDABLE_BRANCHES)} (trees/iforest/rules "
                f"are replicated by design)")


@dataclass
class ServingConfig:
    """Scoring service settings (reference config.py:72-88 + TF-Serving
    batching config, k8s/manifests/ml-models-deployment.yaml:270-290)."""

    host: str = "0.0.0.0"
    port: int = 8080
    max_concurrent_predictions: int = 100
    prediction_timeout_seconds: float = 5.0
    batch_size_limit: int = 1000
    # Microbatcher: fixed-latency deadline + max batch
    microbatch_deadline_ms: float = 5.0
    microbatch_max_size: int = 256
    # Prediction TTL cache switch (reference ensemble_predictor.py:437-471),
    # keyed by transaction_id — idempotent retries of the same transaction
    # serve the cached §2.7 response without re-scoring. TTL/size come from
    # EnsembleConfig.cache_ttl_seconds / cache_max_entries (the reference
    # keeps the cache knobs on the ensemble config; one source of truth).
    enable_prediction_cache: bool = True
    # Two-phase pipelined microbatcher (serving/batcher.py): dispatch batch
    # N+1 (cache check + host assembly + device launch) while batch N's
    # finalize still waits on the device — host assembly overlaps device
    # compute. Results stay in per-request order; off by default so the
    # single-phase path remains the reproducible baseline. TRADEOFF: the
    # prediction cache's idempotent-retry window narrows — a retry of a
    # transaction arriving while its first copy is between dispatch and
    # finalize (the one-batch in-flight window, ~the device latency) misses
    # the cache and is scored + written back again (velocity counts that
    # transaction twice). The serial path closes that window by strict
    # put-before-next-lookup ordering.
    overlap_assembly: bool = False
    # Device-pool scoring (scoring/device_pool.py): replicate the model
    # onto every addressable device and dispatch whole microbatches
    # round-robin with per-replica in-flight depth. Implies the two-phase
    # pipelined microbatcher (overlap_assembly's machinery) with its
    # pipeline depth raised to the pool capacity, so the same
    # idempotent-retry-window tradeoff applies, widened to the pool's
    # in-flight window.
    device_pool: bool = False
    inflight_depth: int = 2
    # self-tuning host pipeline (tuning/): arrival-aware just-in-time
    # batch closing + the online config tuner drive the microbatcher's
    # close decisions instead of the fixed deadline. Knobs live in
    # Config.tuning (TuningSettings); this switch attaches the plane to
    # the serving path. Off = close decisions bit-identical to today.
    autotune: bool = False


@dataclass
class StreamConfig:
    """Transport settings (reference JobConfig.java:20-38 semantics)."""

    backend: str = "memory"  # memory | kafka
    bootstrap_servers: List[str] = field(default_factory=lambda: ["localhost:9092"])
    transactions_topic: str = "payment-transactions"
    enriched_topic: str = "transaction-enriched"
    features_topic: str = "transaction-features"
    predictions_topic: str = "fraud-predictions"
    alerts_topic: str = "fraud-alerts"
    alert_score_threshold: float = 0.7
    partitions: int = 12
    checkpoint_interval_ms: int = 10_000


@dataclass
class SimConfig:
    """Load-generator settings (reference simulator.py:480-489)."""

    tps: int = 100
    num_users: int = 10_000
    num_merchants: int = 5_000
    seed: int = 42


@dataclass
class MonitoringConfig:
    enable_prometheus: bool = True
    prometheus_port: int = 8081
    log_level: str = "INFO"
    # rotating JSON log file (reference logging_config.py file handler);
    # empty = console only. The service_name stamp rides the JSON lines.
    log_file: str = ""
    enable_performance_tracking: bool = True
    enable_drift_detection: bool = True


@dataclass
class QosSettings:
    """Deadline-aware QoS plane knobs (qos/): admission, budgets, ladder.

    Disabled by default — the plane is opt-in per deployment (``serve
    --qos``, ``run-job --qos``, or config/JSON overlay). All knobs are
    runtime state to the plane: changing them via ``POST /qos`` never
    recompiles anything.
    """

    enabled: bool = False
    # per-transaction latency budget (the p99 contract) and the slice of it
    # reserved for transfer+compute+return — assembly must close a batch
    # margin_ms before the oldest waiter's deadline
    budget_ms: float = 20.0
    assemble_margin_ms: float = 2.0
    # token-bucket admission: sustainable txn/s (0 = unlimited), bucket
    # size (0 = one second of tokens), and the reserve fraction under
    # which the low class sheds first
    admission_rate: float = 0.0
    admission_burst: float = 0.0
    low_reserve_frac: float = 0.25
    # priority classification by amount when the record carries no
    # explicit "priority" field: >= high_value_amount -> high (never
    # shed), < low_value_amount -> low (sheds first), else normal
    high_value_amount: float = 500.0
    low_value_amount: float = 25.0
    # degradation ladder (qos/ladder.py): backlog watermarks in records,
    # consecutive observations per step (the hysteresis)
    ladder_enabled: bool = True
    ladder_high_backlog: float = 2048.0
    ladder_low_backlog: float = 256.0
    ladder_patience: int = 2
    # recovery (step-up) patience; 0 = same as ladder_patience. Recovery
    # slower than degradation keeps a sustained overload from flapping the
    # ensemble (each recovery buys a fresh queueing spike)
    ladder_up_patience: int = 8

    def validate(self) -> None:
        """The QoS invariants — enforced at config load (Config.validate)
        AND on every runtime update (QosPlane.configure), so POST /qos can
        never put the plane into a state the loader would refuse."""
        if self.budget_ms <= 0 or self.assemble_margin_ms < 0 \
                or self.assemble_margin_ms >= self.budget_ms:
            raise ValueError(
                f"qos budget must satisfy 0 <= assemble_margin_ms < "
                f"budget_ms, got margin={self.assemble_margin_ms} "
                f"budget={self.budget_ms}")
        if self.ladder_low_backlog > self.ladder_high_backlog:
            # inverted watermarks would make the ladder step down and up
            # on the SAME backlog — the flapping hysteresis exists to
            # prevent
            raise ValueError(
                f"qos ladder watermarks must satisfy low_backlog <= "
                f"high_backlog, got low={self.ladder_low_backlog} "
                f"high={self.ladder_high_backlog}")


@dataclass
class TracingSettings:
    """End-to-end transaction tracing plane knobs (obs/tracing.py):
    flight recorder, critical-path analyzer, SLO burn-rate tracking.

    Disabled by default — the plane is opt-in per deployment (``serve
    --trace``, ``run-job --trace``, or config/JSON overlay) with a
    measured-no-op fast path when off (one ``is None`` branch per batch
    on the scoring paths; ``rtfd trace-drill`` pins the enabled-path
    overhead bound too). All knobs are host state; nothing recompiles.
    """

    enabled: bool = False
    # process identity stamped into minted trace ids and wire carriers
    # ("" = single-process id format): what keeps two workers' fresh
    # roots globally distinct when the coordinator stitches their rings
    origin: str = ""
    # flight recorder: ring of the most recent completed traces, plus the
    # slowest-N kept verbatim (the tail exemplars Chrome-trace export and
    # /latency/breakdown surface regardless of ring churn)
    ring_size: int = 4096
    slowest_n: int = 32
    # SLO objective: objective_frac of scored transactions complete under
    # objective_ms, evaluated over a fast and a slow window (the standard
    # multi-window burn-rate pair); bucket_s is the counting granularity
    slo_objective_ms: float = 20.0
    slo_objective_frac: float = 0.99
    slo_fast_window_s: float = 3600.0
    slo_slow_window_s: float = 21600.0
    slo_bucket_s: float = 60.0
    # QoS consultation: a fast-window burn rate above slo_burn_threshold
    # for slo_gate_patience consecutive observations engages an extra
    # degradation floor (>= ladder rung 1); recovery needs
    # slo_gate_up_patience consecutive under-threshold observations —
    # the same asymmetric hysteresis discipline as the backlog ladder
    slo_burn_threshold: float = 2.0
    slo_gate_patience: int = 3
    slo_gate_up_patience: int = 12

    def validate(self) -> None:
        if not 0.0 < self.slo_objective_frac < 1.0:
            raise ValueError(
                f"tracing.slo_objective_frac must be in (0, 1), got "
                f"{self.slo_objective_frac}")
        if self.slo_objective_ms <= 0 or self.ring_size < 16 \
                or self.slowest_n < 1:
            raise ValueError(
                "tracing requires slo_objective_ms > 0, ring_size >= 16 "
                "and slowest_n >= 1")
        if not (0 < self.slo_bucket_s <= self.slo_fast_window_s
                <= self.slo_slow_window_s):
            # a fast window longer than the slow one would invert the
            # burn-alerting pair; a bucket wider than the fast window
            # would make its burn rate a single stale cell
            raise ValueError(
                f"tracing SLO windows must satisfy 0 < bucket_s <= "
                f"fast_window_s <= slow_window_s, got "
                f"bucket={self.slo_bucket_s} fast={self.slo_fast_window_s} "
                f"slow={self.slo_slow_window_s}")
        if self.slo_burn_threshold <= 0 or self.slo_gate_patience < 1 \
                or self.slo_gate_up_patience < 1:
            raise ValueError(
                "tracing SLO gate requires burn_threshold > 0 and "
                "patience/up_patience >= 1")


@dataclass
class TuningSettings:
    """Self-tuning host pipeline knobs (tuning/): arrival-rate forecast,
    just-in-time batch closing, and the gradient-free online config tuner.

    Disabled by default — the plane is opt-in per deployment (``serve
    --autotune``, ``run-job --autotune``, or config/JSON overlay). With it
    off, batch-close decisions are BIT-IDENTICAL to the fixed-deadline
    path (the microbatchers take the controller branch only when one is
    attached). All knobs are host state; nothing recompiles.
    """

    enabled: bool = False
    # arrival forecaster (tuning/forecast.py): Holt double-exponential
    # smoothing over time-bucketed admission counts. bucket_s is the
    # counting granularity (and the forecast reaction time); alpha/beta
    # the level/trend smoothing factors
    forecast_bucket_s: float = 0.02
    forecast_alpha: float = 0.5
    forecast_beta: float = 0.2
    # just-in-time closer (tuning/controller.py): the tuned max-wait
    # deadline moves within [deadline_min_ms, deadline_max_ms]; with a
    # QoS plane configured, deadline_max_ms must leave the budget's
    # assembly slice intact (validated — the tuner can NEVER starve a
    # latency budget the QoS plane promised)
    deadline_min_ms: float = 0.25
    deadline_max_ms: float = 10.0
    # free-rider patience: waiting for one more (service-free, pad-riding)
    # txn is worth `patience_factor x T(bucket) / fill` of the current
    # waiters' time — the marginal-gain-vs-cost knob (arXiv:1904.07421)
    patience_factor: float = 1.0
    # candidate bucket sets the tuner may select among (index 0 is the
    # starting set). Each must be a non-empty ascending list of positive
    # sizes; the defaults are subsets of core/batching.BATCH_BUCKETS so a
    # tuned close boundary always lands on a compile-cached padded shape
    # (closing at an off-bucket size pads up and wastes the difference).
    bucket_sets: List[List[int]] = field(default_factory=lambda: [
        [1, 8, 32, 128, 256],
        [1, 32, 256],
        [1, 8, 32, 256],
    ])
    # online tuner (tuning/tuner.py): epoch length in completed batches,
    # the relative admitted-p99 improvement required to KEEP a move (the
    # hysteresis), and the post-move cooldown in epochs
    tune_interval_batches: int = 50
    hysteresis_frac: float = 0.05
    tuner_cooldown_epochs: int = 2
    # overlap / in-flight depth search range
    inflight_min: int = 1
    inflight_max: int = 4

    def clamp_to_qos(self, qos: "QosSettings | None") -> None:
        """Clamp the deadline search space to the QoS budget's assembly
        slice, then re-validate — the ONE clamp-then-check recipe the CLI
        entry points (`serve --autotune`, `run-job --autotune`) apply, so
        the floor rule can never diverge between them."""
        if qos is not None and getattr(qos, "enabled", False):
            limit = qos.budget_ms - qos.assemble_margin_ms
            self.deadline_max_ms = min(self.deadline_max_ms, limit)
            self.deadline_min_ms = min(self.deadline_min_ms,
                                       self.deadline_max_ms)
        self.validate(qos=qos)

    def validate(self, qos: "QosSettings | None" = None) -> None:
        if not (0.0 < self.deadline_min_ms <= self.deadline_max_ms):
            raise ValueError(
                f"tuning deadline bounds must satisfy 0 < deadline_min_ms "
                f"<= deadline_max_ms, got min={self.deadline_min_ms} "
                f"max={self.deadline_max_ms}")
        if not self.bucket_sets:
            raise ValueError("tuning.bucket_sets must not be empty")
        for bs in self.bucket_sets:
            if not bs or list(bs) != sorted(bs) or min(bs) < 1 \
                    or len(set(bs)) != len(bs):
                raise ValueError(
                    f"every tuning bucket set must be a non-empty strictly "
                    f"ascending list of positive sizes, got {bs!r}")
        if not (0.0 < self.forecast_alpha <= 1.0
                and 0.0 <= self.forecast_beta <= 1.0
                and self.forecast_bucket_s > 0):
            raise ValueError(
                "tuning forecast requires 0 < alpha <= 1, 0 <= beta <= 1 "
                "and bucket_s > 0")
        if self.tune_interval_batches < 1 or self.hysteresis_frac < 0 \
                or self.tuner_cooldown_epochs < 0:
            raise ValueError(
                "tuning requires tune_interval_batches >= 1, "
                "hysteresis_frac >= 0 and tuner_cooldown_epochs >= 0")
        if not (1 <= self.inflight_min <= self.inflight_max):
            raise ValueError(
                f"tuning requires 1 <= inflight_min <= inflight_max, got "
                f"min={self.inflight_min} max={self.inflight_max}")
        if self.patience_factor <= 0:
            raise ValueError("tuning.patience_factor must be > 0")
        if self.enabled and qos is not None \
                and getattr(qos, "enabled", False):
            # the hard QoS floor: the tuner's deadline search space may
            # never reach past the budget's assembly slice — a tuned
            # max-wait that outlives close_by would hold batches past the
            # deadline the QoS plane promised every admitted transaction.
            # Checked only when the plane is ON: a disabled tuner imposes
            # no constraint on an otherwise-valid QoS config.
            limit = qos.budget_ms - qos.assemble_margin_ms
            if self.deadline_max_ms > limit:
                raise ValueError(
                    f"tuning.deadline_max_ms={self.deadline_max_ms} "
                    f"violates the QoS budget: must be <= budget_ms - "
                    f"assemble_margin_ms = {limit}")


@dataclass
class FeedbackSettings:
    """Continuous-learning plane knobs (feedback/): label join, prequential
    evaluation, retrain policy, promotion gate. Disabled by default — the
    plane is opt-in per deployment (``serve``/``run-job --feedback``,
    config/JSON overlay). All knobs are host state: changing them never
    recompiles anything (a promoted blend that switches the combine
    STRATEGY recompiles once, like any strategy change).
    """

    enabled: bool = False
    # label-join windowing: how long an unlabeled prediction waits for its
    # chargeback before expiring, and the per-stream out-of-orderness
    label_horizon_s: float = 90 * 86_400.0
    label_ooo_s: float = 60.0
    pred_ooo_s: float = 5.0
    # hard cap on predictions waiting for a label (the watermark horizon
    # can't evict while the labels topic is silent; memory must not grow
    # with stream length)
    join_max_pending: int = 100_000
    # synthetic label emission (sim): compresses the chargeback delay
    # distribution (1.0 = realistic days; drills use tiny values)
    label_delay_scale: float = 1.0
    # labeled-example buffer (state/labeled.py)
    buffer_size: int = 50_000
    buffer_store_history: bool = False
    # prequential evaluation
    sliding_window: int = 2_000
    fading_gamma: float = 0.999
    operating_threshold: float = 0.5
    # retrain policy
    auc_drop: float = 0.08
    auc_floor: float = 0.0
    min_labels: int = 300
    cooldown_s: float = 600.0
    use_drift_trigger: bool = True
    # candidate training
    retrain_trees: int = 48
    retrain_depth: int = 5
    retrain_iforest_trees: int = 60
    retrain_neural: bool = False
    # promotion gate
    gate_holdout_frac: float = 0.2
    gate_select_frac: float = 0.2
    gate_min_positives: int = 12
    gate_auc_margin: float = 0.0
    gate_recall_tolerance: float = 0.02

    def validate(self) -> None:
        if not 0.0 < self.fading_gamma < 1.0:
            raise ValueError(
                f"feedback.fading_gamma must be in (0, 1), got "
                f"{self.fading_gamma}")
        if self.sliding_window < 10 or self.buffer_size < 10:
            raise ValueError(
                "feedback.sliding_window and buffer_size must be >= 10")
        if not (0.0 < self.gate_holdout_frac < 1.0
                and 0.0 < self.gate_select_frac < 1.0
                and self.gate_holdout_frac + self.gate_select_frac < 0.9):
            # the gate must always keep a real training majority: a split
            # that eats the training segment would gate candidates trained
            # on nothing
            raise ValueError(
                f"feedback gate fractions must satisfy 0 < holdout, select "
                f"and holdout + select < 0.9, got "
                f"holdout={self.gate_holdout_frac} "
                f"select={self.gate_select_frac}")
        if self.label_horizon_s <= 0 or self.label_delay_scale <= 0:
            raise ValueError(
                "feedback.label_horizon_s and label_delay_scale must be > 0")


@dataclass
class ChaosSettings:
    """Chaos plane knobs (chaos/): deterministic fault injection + the
    adversarial fraud-ring scenario, composed by ``rtfd chaos-drill``.

    Disabled by default — the plane exists for drills/tests/staging soaks,
    never wired into a hot path (injectors are explicit objects a harness
    constructs; production code paths carry no chaos branches). The knobs
    reach the drill via ``rtfd chaos-drill --config file.json``
    (``chaos.drill.apply_chaos_settings`` overlays them onto the drill
    config); all are virtual-clock quantities, so changing them reshapes
    the replayed timeline deterministically. ``enabled`` gates nothing
    today — it is the config-file switch a future always-on staging soak
    consults; the drill runs whenever invoked.
    """

    enabled: bool = False
    seed: int = 11
    # fault windows (virtual seconds, relative to their phase starts)
    broker_outage_s: float = 1.5       # replica down -> NotEnoughReplicas
    label_stall_s: float = 4.0         # label stream held back
    flash_crowd_mult: float = 2.5      # peak offered load / capacity
    flash_burst_mult: float = 1.6      # short bursts on top of the peak
    # adversarial fraud ring (sim/fraud_patterns.FraudRingConfig)
    ring_rate: float = 0.10
    ring_members: int = 24
    ring_merchants: int = 6
    ring_devices: int = 4
    ring_ips: int = 3
    # device-pool faults: how many in-flight fetches the dead replica
    # fails before revival, and the slow-device injected delay
    replica_faults: int = 1
    slow_device_ms: float = 40.0

    def validate(self) -> None:
        if self.broker_outage_s <= 0 or self.label_stall_s < 0:
            raise ValueError(
                "chaos.broker_outage_s must be > 0 and label_stall_s >= 0")
        if self.flash_crowd_mult < 1.0 or self.flash_burst_mult < 1.0:
            raise ValueError(
                f"chaos flash-crowd multipliers must be >= 1, got "
                f"crowd={self.flash_crowd_mult} "
                f"burst={self.flash_burst_mult}")
        if not 0.0 < self.ring_rate <= 1.0:
            raise ValueError(
                f"chaos.ring_rate must be in (0, 1], got {self.ring_rate}")
        if min(self.ring_members, self.ring_merchants, self.ring_devices,
               self.ring_ips) < 1:
            raise ValueError("chaos ring needs >= 1 of each entity kind")
        if self.replica_faults < 1 or self.slow_device_ms < 0:
            raise ValueError(
                "chaos.replica_faults must be >= 1 and slow_device_ms >= 0")


@dataclass
class ClusterSettings:
    """Partition-parallel worker plane knobs (cluster/): key-sharded
    state, checkpointed handoff, and the consistent-hash serving router.

    ``enabled`` turns on the serving-side router: this process serves
    ``/predict`` only for users whose partition the ring assigns to
    ``worker_id``; other keys answer 421 with the owning worker's
    address (``workers``), so a dumb HTTP client — or the ingress in
    front of the fleet — re-issues to the right shard. The
    partition↔worker placement is a pure function of (workers,
    n_partitions, virtual_nodes), identical in every process. The
    stream-side fleet (``cluster.fleet.WorkerFleet``) reads
    ``checkpoint_every`` for its handoff snapshot cadence.
    """

    enabled: bool = False
    # must match the transactions topic's partition count — the key →
    # partition hash is the transport's (stream/topics.py: 12)
    n_partitions: int = 12
    virtual_nodes: int = 256
    # completed batches between per-partition handoff snapshots
    # (round-robin over owned partitions; see ClusterWorker)
    checkpoint_every: int = 8
    # this process's identity in the ring ("" = not a fleet member)
    worker_id: str = ""
    # worker_id -> base URL, the router's redirect targets; the ring is
    # built over these ids
    workers: Dict[str, str] = field(default_factory=dict)
    # elastic process fleet (cluster/procfleet.py + cluster/autoscale.py):
    # worker-count bounds the autoscale controller moves between, the
    # capacity model it divides the forecast by, and the forecast lead
    # that lets the fleet grow BEFORE a diurnal peak (spawn latency is
    # paid inside the lead, not inside the latency budget)
    min_workers: int = 1
    max_workers: int = 8
    per_worker_tps: float = 200.0
    autoscale_headroom: float = 1.25
    autoscale_lead_s: float = 2.0
    autoscale_interval_s: float = 0.5
    autoscale_down_patience: int = 3

    def validate(self) -> None:
        if self.n_partitions < 1:
            raise ValueError(
                f"cluster.n_partitions must be >= 1, got "
                f"{self.n_partitions}")
        if self.virtual_nodes < 1 or self.checkpoint_every < 1:
            raise ValueError(
                "cluster.virtual_nodes and cluster.checkpoint_every "
                "must be >= 1")
        if not 1 <= self.min_workers <= self.max_workers:
            raise ValueError(
                f"cluster autoscale needs 1 <= min_workers <= "
                f"max_workers, got {self.min_workers}..{self.max_workers}")
        if (self.per_worker_tps <= 0 or self.autoscale_headroom < 1.0
                or self.autoscale_lead_s < 0
                or self.autoscale_interval_s <= 0
                or self.autoscale_down_patience < 1):
            raise ValueError(
                "cluster autoscale requires per_worker_tps > 0, "
                "headroom >= 1, lead_s >= 0, interval_s > 0 and "
                "down_patience >= 1")
        if self.enabled:
            if not self.workers:
                raise ValueError(
                    "cluster.enabled requires a non-empty cluster.workers "
                    "map (worker_id -> base URL)")
            if self.worker_id and self.worker_id not in self.workers:
                raise ValueError(
                    f"cluster.worker_id {self.worker_id!r} missing from "
                    f"cluster.workers {sorted(self.workers)}")


VALID_BERT_WEIGHTS = ("f32", "int8")
VALID_TREE_KERNELS = ("gather", "gemm")


@dataclass
class QuantSettings:
    """Quantized scoring plane knobs (models/quant.py + the GEMM-form tree
    kernels in models/trees.py): weight-only int8 for the BERT branch and
    contraction-form traversal for GBDT / isolation forest, selectable PER
    BRANCH.

    Disabled by default — the plane is opt-in per deployment (config/JSON
    overlay, or the CLI's ``--quant`` switches). Branch modes
    are STATIC arguments to the fused program: changing them recompiles
    once (like a combine-strategy change), then every microbatch runs the
    new kernel. The quality gate is ``rtfd quant-drill``: divergence below
    calibration noise, zero operating-point decision flips, AUC unchanged
    on the committed quality protocol — a mode that fails the drill has no
    business in a config file.
    """

    enabled: bool = False
    # BERT branch weights: "f32" (the baseline) or "int8" (weight-only
    # per-output-channel symmetric quantization, dequant-to-bf16 at the
    # matmul seam — ~4x smaller replicated params)
    bert_weights: str = "f32"
    # GBDT / isolation-forest traversal: "gather" (the D-step gather
    # oracle) or "gemm" (Hummingbird-style batched contractions)
    tree_kernel: str = "gather"
    iforest_kernel: str = "gather"

    def validate(self) -> None:
        if self.bert_weights not in VALID_BERT_WEIGHTS:
            raise ValueError(
                f"quant.bert_weights must be one of {VALID_BERT_WEIGHTS}, "
                f"got {self.bert_weights!r}")
        for name, kernel in (("tree_kernel", self.tree_kernel),
                             ("iforest_kernel", self.iforest_kernel)):
            if kernel not in VALID_TREE_KERNELS:
                raise ValueError(
                    f"quant.{name} must be one of {VALID_TREE_KERNELS}, "
                    f"got {kernel!r}")

    @classmethod
    def full(cls) -> "QuantSettings":
        """The everything-on preset behind the ``--quant`` switches: weight-only int8 BERT + GEMM-form kernels for both tree
        branches — exactly the configuration ``rtfd quant-drill`` gates."""
        return cls(enabled=True, bert_weights="int8",
                   tree_kernel="gemm", iforest_kernel="gemm")

    def bert_mode(self) -> str:
        """The effective BERT weight mode ("f32" when the plane is off)."""
        return self.bert_weights if self.enabled else "f32"

    def stamp(self) -> Dict[str, str]:
        """The quantization-mode arch stamp: only the BERT weight form —
        the one mode that is a PARAMETER property (checkpoint.py derives
        the same key from saved pytrees via ``_derive_quant_mode`` and
        refuses silent cross-mode restores on it). The tree kernels are
        program selections, not checkpoint state, so they are
        deliberately absent."""
        return {"bert_weights": self.bert_mode()}


VALID_KERNEL_SITES = ("dequant_matmul", "epilogue", "attention")
# (an encoder's row may name further sites that ``kernel_snapshot`` counts
# launches under and nothing sets: models/text_encoder.KernelSite)
VALID_KERNEL_MODES = ("off", "pallas")
VALID_ATTENTION_KERNELS = ("reference", "flash")


@dataclass
class KernelSettings:
    """Hand-written Pallas kernel plane (ops/): per-site kernel selection
    for the fused scoring program.

    Three sites, selectable independently (the quant-plane discipline:
    structural detection where possible, static program selection
    otherwise, no recompile-on-swap surprises):

    - ``dequant_matmul``: the int8 BERT branch's fused dequant-matmul
      (ops/dequant_matmul.py) — the i8 -> compute-dtype widen happens in
      VMEM inside the kernel instead of trusting XLA to fuse the
      ``(i8 -> bf16) * scale`` weight read. Only engages where the params
      actually carry the weight-only int8 layout (models/quant.py); f32
      sites keep the plain matmul.
    - ``epilogue``: the fused score-and-blend epilogue (ops/epilogue.py)
      — branch predictions, branch-validity/QoS masks, blend weights and
      the decision/risk ladders combine on-chip, and the packed result
      matrix grows the per-model contribution + rules-only ladder columns
      so ``FraudScorer.finalize`` does pure column reads instead of
      per-record host blend math.
    - ``attention``: the fused Pallas core (ops/attention.py) vs the XLA
      reference for the text encoder. Only consulted while the plane is
      ``enabled`` — it is how a drill or an A/B forces either side. With
      the plane off nothing here decides: the scorer picks the fused core
      on a TPU at the shapes ``flash_supported`` admits
      (``FraudScorer.effective_use_pallas``).

    Off by default: the plane is opt-in (config/JSON overlay, or the
    CLI's ``--kernels`` switches) until a chip run proves the
    MXU bet. Kernel selection is RUNTIME config — never
    serialized into checkpoints, never part of the arch stamp — and the
    modes are STATIC arguments to the fused program (changing them
    recompiles once, like a quant kernel change). On hosts without a TPU
    the kernels run through the Pallas interpreter, pinned against the
    XLA reference by ``rtfd kernel-drill``.
    """

    enabled: bool = False
    dequant_matmul: str = "off"     # off | pallas
    epilogue: str = "off"           # off | pallas
    attention: str = "reference"    # reference | flash

    def validate(self) -> None:
        for name, mode in (("dequant_matmul", self.dequant_matmul),
                           ("epilogue", self.epilogue)):
            if mode not in VALID_KERNEL_MODES:
                raise ValueError(
                    f"kernels.{name} must be one of {VALID_KERNEL_MODES}, "
                    f"got {mode!r}")
        if self.attention not in VALID_ATTENTION_KERNELS:
            raise ValueError(
                f"kernels.attention must be one of "
                f"{VALID_ATTENTION_KERNELS}, got {self.attention!r}")

    @classmethod
    def full(cls) -> "KernelSettings":
        """The everything-on preset behind the ``--kernels`` switches: fused dequant-matmul + fused epilogue + flash attention
        — exactly the configuration ``rtfd kernel-drill`` gates."""
        return cls(enabled=True, dequant_matmul="pallas",
                   epilogue="pallas", attention="flash")

    def site_modes(self) -> Dict[str, str]:
        """Effective per-site modes (everything off while disabled) —
        the shape ``FraudScorer.kernel_snapshot`` and the kernel_*
        Prometheus series report."""
        if not self.enabled:
            return {"dequant_matmul": "off", "epilogue": "off",
                    "attention": "reference"}
        return {"dequant_matmul": self.dequant_matmul,
                "epilogue": self.epilogue,
                "attention": self.attention}


@dataclass
class StateConfig:
    """Windowed state store settings (RedisService.java key TTLs)."""

    backend: str = "memory"  # memory | redis
    redis_host: str = "localhost"
    redis_port: int = 6379
    transaction_ttl_s: int = 24 * 3600
    features_ttl_s: int = 2 * 3600
    # NOTE deliberately no velocity TTL knob: velocity keys expire at their
    # own window period by design (state/shared.py — this FIXES the
    # reference's uniform 1h TTL, which let a 24h velocity hash die early)
    user_history_len: int = 100  # RedisService.java:296-306 last-100 list
    merchant_history_len: int = 500


def _default_models() -> Dict[str, ModelConfig]:
    """The 5-model registry (reference config.py:126-199)."""
    return {
        "xgboost_primary": ModelConfig(
            name="xgboost_primary",
            model_type="gbdt",
            weight=0.40,
            hyperparameters={
                "n_estimators": 100,
                "max_depth": 6,
                "learning_rate": 0.1,
                "subsample": 0.8,
                "colsample_bytree": 0.8,
            },
        ),
        "lstm_sequential": ModelConfig(
            name="lstm_sequential",
            model_type="lstm",
            weight=0.25,
            hyperparameters={
                "sequence_length": 10,
                "hidden_units": 128,
                "dropout": 0.2,
            },
        ),
        "bert_text": ModelConfig(
            name="bert_text",
            model_type="bert",
            weight=0.15,
            hyperparameters={
                "max_length": 128,  # reference uses 512 but its texts are <64 tokens
                "vocab_size": 30522,
                "hidden_size": 768,
                "num_layers": 6,
                "num_heads": 12,
                "intermediate_size": 3072,
            },
        ),
        "graph_neural": ModelConfig(
            name="graph_neural",
            model_type="gnn",
            weight=0.15,
            hyperparameters={
                "hidden_channels": 64,
                "num_layers": 3,
                "dropout": 0.1,
                "num_neighbors": 16,
            },
        ),
        "isolation_forest": ModelConfig(
            name="isolation_forest",
            model_type="isolation_forest",
            weight=0.05,
            hyperparameters={
                "contamination": 0.1,
                "n_estimators": 100,
                "random_state": 42,
            },
        ),
    }


# Confidence multipliers per model (ensemble_predictor.py:331-337).
MODEL_CONFIDENCE_MULTIPLIER: Dict[str, float] = {
    "xgboost_primary": 1.0,
    "lstm_sequential": 0.8,
    "bert_text": 0.7,
    "graph_neural": 0.6,
    "isolation_forest": 0.5,
}
DEFAULT_CONFIDENCE_MULTIPLIER = 0.5


@dataclass
class Config:
    """Root configuration."""

    service_name: str = "rtfd-tpu"
    environment: str = "development"
    models_base_path: str = "artifacts/models"
    models: Dict[str, ModelConfig] = field(default_factory=_default_models)
    ensemble: EnsembleConfig = field(default_factory=EnsembleConfig)
    mesh: MeshSettings = field(default_factory=MeshSettings)
    serving: ServingConfig = field(default_factory=ServingConfig)
    stream: StreamConfig = field(default_factory=StreamConfig)
    state: StateConfig = field(default_factory=StateConfig)
    sim: SimConfig = field(default_factory=SimConfig)
    monitoring: MonitoringConfig = field(default_factory=MonitoringConfig)
    qos: QosSettings = field(default_factory=QosSettings)
    feedback: FeedbackSettings = field(default_factory=FeedbackSettings)
    tracing: TracingSettings = field(default_factory=TracingSettings)
    tuning: TuningSettings = field(default_factory=TuningSettings)
    chaos: ChaosSettings = field(default_factory=ChaosSettings)
    quant: QuantSettings = field(default_factory=QuantSettings)
    cluster: ClusterSettings = field(default_factory=ClusterSettings)
    kernels: KernelSettings = field(default_factory=KernelSettings)

    def __post_init__(self) -> None:
        self._apply_env()
        self.validate()

    # -- env layering ------------------------------------------------------
    def _apply_env(self) -> None:
        self.models_base_path = _env("MODELS_PATH", self.models_base_path)
        self.serving.port = int(_env("ML_SERVICE_PORT", str(self.serving.port)))
        self.serving.host = _env("ML_SERVICE_HOST", self.serving.host)
        self.ensemble.strategy = _env("ENSEMBLE_STRATEGY", self.ensemble.strategy)
        self.ensemble.confidence_threshold = float(
            _env("CONFIDENCE_THRESHOLD", str(self.ensemble.confidence_threshold))
        )
        self.ensemble.fraud_threshold = float(
            _env("FRAUD_THRESHOLD", str(self.ensemble.fraud_threshold))
        )
        self.monitoring.log_level = _env("LOG_LEVEL", self.monitoring.log_level)
        self.monitoring.log_file = _env("LOG_FILE", self.monitoring.log_file)
        # the reference's Redis env contract (config.py REDIS_HOST/PORT):
        # with state.backend="redis" these select the shared state plane
        self.state.backend = _env("RTFD_STATE_BACKEND", self.state.backend)
        self.state.redis_host = _env("REDIS_HOST", self.state.redis_host)
        self.state.redis_port = int(
            _env("REDIS_PORT", str(self.state.redis_port)))

    # -- registry helpers (reference config.py:201-224) --------------------
    def get_model_config(self, model_name: str) -> ModelConfig:
        if model_name not in self.models:
            raise ValueError(f"Model '{model_name}' not found in configuration")
        return self.models[model_name]

    def get_enabled_models(self) -> Dict[str, ModelConfig]:
        return {n: c for n, c in self.models.items() if c.enabled}

    def normalized_weights(self) -> Dict[str, float]:
        enabled = self.get_enabled_models()
        total = sum(c.weight for c in enabled.values())
        if total <= 0:
            return {n: 0.0 for n in enabled}
        return {n: c.weight / total for n, c in enabled.items()}

    def update_model_weight(self, model_name: str, weight: float) -> None:
        if model_name in self.models:
            self.models[model_name].weight = weight

    def disable_model(self, model_name: str) -> None:
        if model_name in self.models:
            self.models[model_name].enabled = False

    def enable_model(self, model_name: str) -> None:
        if model_name in self.models:
            self.models[model_name].enabled = True

    @staticmethod
    def load_selected_blend_weights(artifact_path: str) -> Dict[str, float]:
        """Parse a quality-eval artifact's ``selected_blend.weights`` —
        the ONE place the artifact schema is read (apply_quality_artifact
        and the A/B canary both call it). Malformed shapes raise
        ValueError, never AttributeError."""
        with open(artifact_path) as f:
            artifact = json.load(f)
        blend = (artifact.get("selected_blend")
                 if isinstance(artifact, dict) else None)
        weights = blend.get("weights") if isinstance(blend, dict) else None
        if not isinstance(weights, dict) or not weights:
            raise ValueError(
                f"{artifact_path} has no selected_blend.weights — not a "
                f"quality-eval artifact?")
        return {str(n): float(w) for n, w in weights.items()}

    @staticmethod
    def load_selected_blend_strategy(artifact_path: str) -> str | None:
        """The artifact's measured combine strategy (selected_blend.
        strategy), or None for pre-strategy artifacts (which were all
        measured under weighted_average). Unknown names raise — a typo'd
        strategy must not silently serve the default."""
        with open(artifact_path) as f:
            artifact = json.load(f)
        blend = (artifact.get("selected_blend")
                 if isinstance(artifact, dict) else None)
        strategy = blend.get("strategy") if isinstance(blend, dict) else None
        if strategy is None:
            return None
        if strategy not in VALID_STRATEGIES:
            raise ValueError(
                f"{artifact_path} selected_blend.strategy {strategy!r} not "
                f"one of {VALID_STRATEGIES}")
        return str(strategy)

    @staticmethod
    def load_artifact_text_model(artifact_path: str) -> Dict[str, Any] | None:
        """The artifact's recorded text-encoder architecture
        (protocol.text_model: layers/width/vocab), or None when absent.
        The one place the key is read — serve/--quality-artifact and
        /reload-models both use it to refuse mixing artifacts and
        checkpoints from different architectures."""
        with open(artifact_path) as f:
            artifact = json.load(f)
        proto = (artifact.get("protocol")
                 if isinstance(artifact, dict) else None)
        tm = proto.get("text_model") if isinstance(proto, dict) else None
        return dict(tm) if isinstance(tm, dict) else None

    def apply_quality_artifact(self, artifact_path: str) -> Dict[str, float]:
        """Deploy a measured blend: set enabled models + weights from a
        quality-eval artifact (`rtfd quality-eval` / QUALITY_r*.json).

        This closes the loop from measurement to serving: the artifact's
        ``selected_blend`` — the branch set that survived the validation
        A/B gate, at its admitted weights — becomes this config's model
        table, so the scorer's validity mask and the device combine's
        weights are exactly what the protocol measured. Branches outside
        the blend stay configured but disabled (hot-enable later via
        /reload-models + enable_model without a recompile). When the
        artifact records a measured combine strategy (selected_blend.
        strategy — e.g. the stacked combiner), that deploys too (NOTE: a
        strategy change is the one blend knob that recompiles the fused
        program once, being a static argument). Returns the applied
        weights."""
        weights = self.load_selected_blend_weights(artifact_path)
        strategy = self.load_selected_blend_strategy(artifact_path)
        unknown = [n for n in weights if n not in self.models]
        if unknown:
            raise ValueError(
                f"artifact names unknown model(s) {unknown}; "
                f"configured: {sorted(self.models)}")
        for name, mc in self.models.items():
            if name in weights:
                mc.enabled = True
                mc.weight = float(weights[name])
            else:
                mc.enabled = False
        if strategy is not None:
            self.ensemble.strategy = strategy
        return {n: float(w) for n, w in weights.items()}

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_file(cls, config_path: str) -> "Config":
        with open(config_path) as f:
            data = json.load(f)
        return cls.from_dict(data)

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Config":
        cfg = cls()
        _merge_dataclass(cfg, data)
        # env re-applies AFTER the file overlay: defaults -> file -> env
        cfg._apply_env()
        cfg.validate()
        return cfg

    def validate(self) -> None:
        if self.ensemble.strategy not in VALID_STRATEGIES:
            raise ValueError(
                f"ensemble.strategy (env RTFD_ENSEMBLE_STRATEGY) must be one of "
                f"{VALID_STRATEGIES}, got {self.ensemble.strategy!r}"
            )
        e = self.ensemble
        if not (0.0 <= e.monitor_threshold <= e.review_threshold
                <= e.decline_threshold <= 1.0):
            # a misordered ladder silently shadows rungs (e.g. review 0.4 <
            # monitor 0.6 makes APPROVE_WITH_MONITORING unreachable) —
            # refuse it loudly, this is a fraud-decision path
            raise ValueError(
                "decision ladder must satisfy 0 <= monitor_threshold <= "
                "review_threshold <= decline_threshold <= 1, got "
                f"monitor={e.monitor_threshold} review={e.review_threshold} "
                f"decline={e.decline_threshold}")
        self.mesh.validate()
        self.qos.validate()
        self.feedback.validate()
        self.tracing.validate()
        self.tuning.validate(qos=self.qos)
        self.chaos.validate()
        self.quant.validate()
        self.cluster.validate()
        self.kernels.validate()


def _merge_dataclass(obj: Any, data: Dict[str, Any]) -> None:
    """Recursively overlay a dict onto a dataclass tree.

    Unknown keys WARN instead of silently vanishing: a typo'd or renamed
    knob in a config file must not quietly leave the default in force
    (e.g. a stale cache-TTL key silently serving cached fraud verdicts 10x
    longer than the operator configured).
    """
    import logging

    for key, value in data.items():
        if not hasattr(obj, key):
            logging.getLogger(__name__).warning(
                "config: unknown key %r on %s — ignored (typo or renamed "
                "knob?)", key, type(obj).__name__)
            continue
        current = getattr(obj, key)
        if dataclasses.is_dataclass(current) and isinstance(value, dict):
            _merge_dataclass(current, value)
        elif key == "models" and isinstance(value, dict):
            for model_name, model_data in value.items():
                if model_name in current and isinstance(model_data, dict):
                    for attr, v in model_data.items():
                        if hasattr(current[model_name], attr):
                            setattr(current[model_name], attr, v)
                elif isinstance(model_data, dict) and "model_type" in model_data:
                    current[model_name] = ModelConfig(
                        name=model_name, **{k: v for k, v in model_data.items() if k != "name"}
                    )
        else:
            setattr(obj, key, value)
