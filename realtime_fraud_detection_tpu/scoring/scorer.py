"""Host-side scoring orchestrator around the fused device program.

Plays the combined role of the reference's Flink ``TransactionProcessor``
(profile/velocity joins, TransactionProcessor.java:51-92), the serving
``FeatureProcessor`` + ``EnsemblePredictor`` (main.py:146-215), and the
``RedisTransactionSink`` state write-backs (RedisTransactionSink.java:53-135)
— but restructured TPU-first:

  host: join state -> encode dense batch -> pad to bucket -> shard over mesh
  device: ONE fused XLA program (features + 5 branches + ensemble + decisions)
  host: unpad -> response dicts -> state write-back

State reads happen before scoring and writes after, matching the reference's
read-then-sink ordering, but single-writer per process (fixing the
RMW races noted in SURVEY.md §5.2).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from realtime_fraud_detection_tpu.core.batching import (
    BATCH_BUCKETS,
    bucket_for,
)
from realtime_fraud_detection_tpu.core.mesh import (
    build_mesh,
    local_mesh_size,
    shard_batch,
)
from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
from realtime_fraud_detection_tpu.features.rules import (
    DECISIONS,
    RISK_LEVEL_NAMES,
)
from realtime_fraud_detection_tpu.features.schema import encode_transactions
from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
from realtime_fraud_detection_tpu.models.text import combined_text
from realtime_fraud_detection_tpu.models.text_encoder import (
    DEQUANT,
    INT8,
    LAUNCH_COUNTERS,
    MESH,
    POOL,
    TEXT_SPLIT,
    launch_counters,
)
from realtime_fraud_detection_tpu.models.tokenizer import FraudTokenizer
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.core.packing import pack_tree
from realtime_fraud_detection_tpu.scoring import text_split
from realtime_fraud_detection_tpu.scoring.pipeline import (
    MODEL_NAMES,
    NUM_MODELS,
    OUT_COLUMNS,
    ScoreBatch,
    ScorerConfig,
    ScoringModels,
    TextConfig,
    init_scoring_models,
    score_fused,
    score_fused_packed,
    text_encoder,
)
from realtime_fraud_detection_tpu.state.history import (
    EntityGraphStore,
    UserHistoryStore,
)
from realtime_fraud_detection_tpu.state.stores import (
    ProfileStore,
    TransactionCache,
    VelocityStore,
)
from realtime_fraud_detection_tpu.utils.config import (
    VALID_KERNEL_SITES,
    Config,
    KernelSettings,
)


import dataclasses


@dataclasses.dataclass
class PendingScore:
    """A dispatched-but-not-finalized microbatch.

    ``out`` holds device arrays still being computed (JAX async dispatch);
    ``features`` is the host copy of this batch's 64-wide feature rows,
    captured at dispatch time because a later dispatch overwrites the
    scorer's ``last_features``.
    """

    records: List[Mapping[str, Any]]
    n: int
    out: Any
    features: np.ndarray
    # Host-side assemble+dispatch cost, captured when dispatch() returns.
    # Under two-deep pipelining the wall time between dispatch and finalize
    # includes queue wait (the caller is off assembling the next batch), so
    # finalize() measures its own device wait and adds this — never the gap.
    dispatch_ms: float
    # The branch-validity mask and rules-only flag THIS batch was dispatched
    # under: the QoS ladder may step between dispatch and finalize, and the
    # response must describe the program that actually ran.
    model_valid: Optional[np.ndarray] = None
    rules_only: bool = False
    # pooled dispatch (scoring/device_pool.py): the PoolToken finalize
    # resolves through DevicePool.wait (retry-on-replica-failure) instead
    # of a plain device_get. None = single-device path.
    pool_token: Optional[Any] = None
    # tracing plane (obs/tracing.py): the microbatch's TraceBatch carrier.
    # The scorer marks assemble/pack/dispatch/device_wait/finalize on it;
    # the owner (stream job / serving app) finishes it after fan-out.
    # None = tracing off (the default no-op fast path).
    trace: Optional[Any] = None
    # The counters of the batch's launches (models/text_encoder.py says what
    # each counts), by the names ``StreamJob.counters`` sums them under:
    # every name of ``LAUNCH_COUNTERS`` from dispatch, the encoder's
    # finalize-time ones over them once ``text_stats`` — the program's second
    # output, where the encoder returns statistics — has been read.
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    text_stats: Optional[Any] = None


@dataclasses.dataclass
class _Launch:
    """One call of the fused program for (a part of) a microbatch."""

    rows: Optional[np.ndarray]      # record indices held; None = all, in order
    n: int                          # real rows
    size: int                       # bucket rows
    width: int                      # text positions
    blobs: Any = None               # core.packing.pack_tree's output
    spec: Any = None
    # where the encoder has routed blocks: the token slots they are compiled
    # for (text_split.capacity), and the real tokens the launch holds
    capacity: Optional[int] = None
    tokens: int = 0
    # whether the program was asked for its Pallas kernels
    # (effective_use_pallas at the launch's width; set at dispatch)
    kernels: bool = False


class _SplitResult:
    """The result matrices of a batch launched as two programs, read as the
    one matrix the unsplit launch returns: bucket rows, record order. The
    narrow launch held every row (the long ones cut short: those answers
    are dropped), so its matrix is the base and the long launch's rows go
    over it. The merge is the host's (``[long rows, 13]`` f32), made where
    the matrix is read: ``np.asarray(pending.out)`` and
    ``jax.device_get(pending.out)`` both come through ``__array__``."""

    def __init__(self, base: Any, long_out: Any, long_rows: np.ndarray):
        self._base, self._long, self._rows = base, long_out, long_rows

    def copy_to_host_async(self) -> None:
        self._base.copy_to_host_async()
        self._long.copy_to_host_async()

    def block_until_ready(self) -> "_SplitResult":
        self._base.block_until_ready()
        self._long.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        merged = np.array(self._base, dtype=dtype)   # blocks until done
        merged[self._rows] = np.asarray(self._long)[:len(self._rows)]
        return merged


class _EntityIndex:
    """Stable string-id -> dense int index with on-the-fly node features.

    Rows live in one preallocated, doubling (capacity, node_dim) table
    written in place — ``table()`` is a zero-copy slice, never a restack
    (the old stacked-row cache re-stacked every batch that saw a new
    entity, which on a fresh stream is every batch).
    """

    def __init__(self, node_dim: int):
        self.node_dim = node_dim
        self._idx: Dict[str, int] = {}
        self._profiled: set[str] = set()
        self._tbl = np.zeros((256, node_dim), np.float32)
        self._n = 0

    def __setstate__(self, state) -> None:
        """Checkpoint migration: pre-host-plane snapshots pickled the
        stacked-row form (``_rows``/``_table``); rebuild the in-place
        table from it."""
        if "_rows" not in state:
            self.__dict__.update(state)
            return
        self.node_dim = state["node_dim"]
        self._idx = state["_idx"]
        self._profiled = state["_profiled"]
        rows = state["_rows"]
        self._n = len(rows)
        cap = 256
        while cap < max(self._n, 1):
            cap *= 2
        self._tbl = np.zeros((cap, self.node_dim), np.float32)
        if rows:
            self._tbl[: self._n] = np.stack(rows, axis=0)

    def _grow(self, need: int) -> None:
        cap = self._tbl.shape[0]
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        tbl = np.zeros((cap, self.node_dim), np.float32)
        tbl[: self._tbl.shape[0]] = self._tbl
        self._tbl = tbl

    def lookup(self, entity_id: str, profile: Optional[Mapping[str, Any]],
               is_merchant: bool) -> int:
        i = self._idx.get(entity_id)
        if i is None:
            i = self._n
            self._idx[entity_id] = i
            self._grow(i + 1)
            self._tbl[i] = self._featurize(profile, is_merchant)
            self._n += 1
        elif profile is not None and entity_id not in self._profiled:
            # a profile arrived after first sight — refresh the stale zero row
            self._tbl[i] = self._featurize(profile, is_merchant)
        if profile is not None:
            self._profiled.add(entity_id)
        return i

    def lookup_batch(self, entity_ids: Sequence[str],
                     profiles: Mapping[str, Mapping[str, Any]],
                     is_merchant: bool) -> np.ndarray:
        """Batched lookup: one dense index vector for a whole microbatch.
        Featurization runs only for ids never seen (or first seen without a
        profile that has one now) — the steady-state batch is pure dict
        hits."""
        out = np.empty((len(entity_ids),), np.int64)
        idx_get = self._idx.get
        prof_get = profiles.get
        profiled = self._profiled
        for k, eid in enumerate(entity_ids):
            i = idx_get(eid)
            if i is None or (eid not in profiled
                             and prof_get(eid) is not None):
                i = self.lookup(eid, prof_get(eid), is_merchant)
            out[k] = i
        return out

    def _featurize(self, p: Optional[Mapping[str, Any]], is_merchant: bool) -> np.ndarray:
        """Node features mirroring models.gnn.build_node_features slots."""
        row = np.zeros((self.node_dim,), np.float32)
        if p is None:
            row[8] = 1.0 if is_merchant else 0.0
            return row
        if is_merchant:
            from realtime_fraud_detection_tpu.features.schema import (
                MERCHANT_CATEGORIES,
                _code,
            )

            risk = {"low": 0, "medium": 1, "high": 2}.get(str(p.get("risk_level")), 1)
            hours = p.get("operating_hours") or {}
            row[0] = risk / 2.0
            row[1] = float(p.get("fraud_rate", 0.05))
            row[2] = np.log1p(float(p.get("avg_transaction_amount", 0.0)))
            row[3] = float(bool(p.get("is_blacklisted", False)))
            row[4] = _code(MERCHANT_CATEGORIES, p.get("category")) / 10.0
            row[5] = float(hours.get("start_hour", 0)) / 24.0
            row[6] = float(hours.get("end_hour", 24)) / 24.0
            row[8] = 1.0
        else:
            patterns = p.get("behavioral_patterns") or {}
            row[0] = float(p.get("risk_score", 0.5))
            row[1] = np.log1p(float(p.get("avg_transaction_amount", 0.0)))
            row[2] = float(p.get("transaction_frequency", 0.0))
            row[3] = float(p.get("account_age_days", 0.0)) / 365.0
            row[4] = float(str(p.get("kyc_status", "")) == "verified")
            row[5] = float(patterns.get("weekend_activity", 0.5))
            row[6] = float(patterns.get("international_transactions", 0.0) or 0.0)
            row[7] = float(patterns.get("online_preference", 0.7))
        return row

    def table(self) -> np.ndarray:
        return self._tbl[: self._n] if self._n else self._tbl[:1]

    def peek_rows(self, entity_ids: Sequence[str]) -> np.ndarray:
        """Feature rows for KNOWN ids, zero rows for unknown — a read-only
        probe that never creates entries (the typed sampler resolves 2-hop
        users that may belong to other partitions; creating index rows for
        them would grow this table with entities this worker never
        scores)."""
        out = np.zeros((len(entity_ids), self.node_dim), np.float32)
        get = self._idx.get
        for k, eid in enumerate(entity_ids):
            i = get(eid)
            if i is not None:
                out[k] = self._tbl[i]
        return out


class _StagingBuffers:
    """Preallocated, reused pad staging per bucket shape.

    ``pad`` writes a microbatch's leaves into the bucket-sized buffers
    (write-into, not rebuild) with pad rows replicating row 0, exactly like
    core/batching.pad_to_bucket — minus the 65 fresh allocations per batch.
    Safe to reuse because core/packing.pack_tree copies every leaf into the
    transfer blobs before ``dispatch`` returns; nothing downstream holds a
    reference to the staging arrays. NOT safe for concurrent dispatches —
    the same contract as the scorer's state stores (single assembly thread).
    """

    def __init__(self) -> None:
        self._bufs: Dict[tuple, List[np.ndarray]] = {}
        self._masks: Dict[int, np.ndarray] = {}

    def pad(self, tree: Any, n: int, size: int,
            rows: Optional[np.ndarray] = None) -> tuple:
        """``rows`` (indices, ``n`` of them) picks the rows to stage where
        the launch holds a part of the batch; None stages the first ``n``.
        Buffers are kept per bucket shape, the leaves' trailing shapes
        included: a batch launched at two text widths has one set each."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        leaves = [np.asarray(lf) for lf in leaves]
        shapes = tuple(((size,) + lf.shape[1:], lf.dtype) for lf in leaves)
        bufs = self._bufs.get(shapes)
        if bufs is None:
            bufs = [np.empty(shape, dtype) for shape, dtype in shapes]
            self._bufs[shapes] = bufs
        for buf, arr in zip(bufs, leaves):
            if rows is None:
                buf[:n] = arr
            else:
                np.take(arr, rows, axis=0, out=buf[:n], mode="clip")
            if n < size:
                buf[n:] = buf[:1]          # replicate row 0 (pad_to_bucket)
        mask = self._masks.get(size)
        if mask is None:
            self._masks[size] = mask = np.zeros((size,), bool)
        mask[:n] = True
        mask[n:] = False
        return jax.tree_util.tree_unflatten(treedef, bufs), mask


def _stage_bf16(padded):
    """Downcast the float-heavy staging leaves to bfloat16 before packing.

    Runs on freshly written HOST staging buffers (``_StagingBuffers.pad``
    output) — never a device array, so it sits outside the dispatch path's
    d2h discipline by construction. The conversion halves the H2D payload
    for history/entity features (the batch-256 transfer lever)."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    out = padded.replace(
        history=np.asarray(padded.history, bf),
        user_feat=np.asarray(padded.user_feat, bf),
        merchant_feat=np.asarray(padded.merchant_feat, bf),
        user_neigh_feat=np.asarray(padded.user_neigh_feat, bf),
        merch_neigh_feat=np.asarray(padded.merch_neigh_feat, bf),
    )
    if padded.user_neigh2_feat is not None:
        # typed-graph two-hop context: by far the widest float payload
        # (K x K2 x D per row) — exactly the tensors the bf16 wire format
        # exists for
        out = out.replace(
            user_neigh2_feat=np.asarray(padded.user_neigh2_feat, bf),
            merch_neigh2_feat=np.asarray(padded.merch_neigh2_feat, bf),
        )
    return out


class FraudScorer:
    """Stateful streaming scorer: the framework's flagship serving object."""

    def __init__(
        self,
        config: Optional[Config] = None,
        models: Optional[ScoringModels] = None,
        mesh=None,
        scorer_config: Optional[ScorerConfig] = None,
        bert_config: TextConfig = TINY_CONFIG,
        seed: int = 0,
        state_client=None,
        stores=None,
    ):
        self.config = config or Config()
        self.sc = scorer_config or ScorerConfig()
        # the text branch's configuration picks its encoder by its class;
        # everything this scorer asks of it is its row's to answer
        # (models/text_encoder.py)
        self.bert_config = bert_config
        self._text = text_encoder(bert_config)
        self.mesh = mesh if mesh is not None else build_mesh()
        # device-pool scoring plane (scoring/device_pool.py): when attached,
        # dispatch_assembled routes whole microbatches round-robin across
        # per-device model replicas instead of sharding one batch over the
        # mesh — see DevicePool for the ordering/equality contract
        self._pool = None
        self.kernels = getattr(self.config, "kernels", None) or KernelSettings()
        for plane, asked in (
                (INT8, self.config.quant.bert_mode() == "int8"),
                (DEQUANT, self.kernels.enabled
                 and self.kernels.dequant_matmul == "pallas"),
                (MESH, self.mesh.devices.size > 1)):
            if asked:
                self.require_plane(plane)
        # feature extraction needs JAX's CPU backend next to the accelerator:
        # a process without one fails HERE, with a message, not as all-ERROR
        # results inside the stream job's degradation path
        from realtime_fraud_detection_tpu.features.extract import (
            host_cpu_device,
        )

        host_cpu_device()
        self.models = models if models is not None else init_scoring_models(
            jax.random.PRNGKey(seed), bert_config=bert_config,
            feature_dim=self.sc.feature_dim, node_dim=self.sc.node_dim,
        )
        # quantized scoring plane (models/quant.py + QuantSettings): the
        # BERT branch drops to weight-only int8 and the tree branches can
        # take the GEMM-form kernels. Applied HERE (and in set_models) so
        # every downstream consumer — the mesh path, the device pool's
        # per-replica replication, checkpoint save — sees one consistent
        # parameter form.
        self.quant = self.config.quant
        self.models = self._maybe_quantize(self.models)
        # divergence-gate verdict ledger (rtfd quant-drill records its
        # oracle verdicts here; obs.metrics.sync_quant mirrors the counts)
        self._quant_gate_counts: Dict[str, int] = {"pass": 0, "fail": 0}
        # Pallas kernel plane (ops/ + KernelSettings): per-site static
        # kernel selection for the fused program. Interpret mode is
        # resolved ONCE per scorer from the platform its mesh runs on: on
        # TPU the kernels lower through Mosaic, on CPU (tests and the CPU
        # drills) they run through the Pallas interpreter — and say so in
        # kernel_snapshot()["interpret"]. Any other platform is refused
        # rather than silently interpreted. Dispatch/fallback counters are
        # kept host-side using the SAME supports() predicates the traced
        # code consults (obs.metrics.sync_kernels mirrors them).
        platform = self.mesh.devices.flat[0].platform
        if self.kernels.enabled and platform not in ("tpu", "cpu"):
            raise ValueError(
                f"KernelSettings.enabled needs a TPU (compiled) or CPU "
                f"(interpreted) mesh; this scorer's devices are {platform!r}")
        self._platform = platform
        self._kernel_interpret = platform == "cpu"
        # (the three sites the plane has a mode for, then the encoder's own)
        sites = VALID_KERNEL_SITES + tuple(
            site.name for site in self._text.sites
            if site.name not in VALID_KERNEL_SITES)
        self._kernel_counts: Dict[str, Dict[str, int]] = {
            "dispatch": {s: 0 for s in sites},
            "fallback": {s: 0 for s in sites},
        }
        # memoized static-kwarg tuples (kernel_static/quant_static): the
        # hot dispatch path does a dict lookup instead of rebuilding the
        # dicts per microbatch. Keyed by settings VALUES, so mutating the
        # settings lands on a different entry — never a stale one.
        self._static_cache: Dict[tuple, Dict[str, Any]] = {}
        self.ensemble_params = EnsembleParams.from_config(self.config, MODEL_NAMES)
        enabled = self.config.get_enabled_models()
        self.model_valid = np.asarray(
            [n in enabled for n in MODEL_NAMES], bool
        )
        # QoS degradation (qos/ladder.py): an extra mask AND-ed over the
        # deployment validity, set per ladder rung; rules-only replaces the
        # ensemble output with the rule score host-side
        self._qos_mask: Optional[np.ndarray] = None
        self._qos_rules_only = False
        self.qos_level = 0

        # streaming state (the Redis-equivalent plane, SURVEY.md §2.5).
        # Default: in-process single-writer stores (state lives with the
        # microbatcher — no network hop in the hot loop). With
        # ``state_client`` (a state.RespClient), profiles/velocity/txn-cache
        # move to the shared RESP tier so N replicas share one state plane
        # (state/shared.py; the reference's Redis role). Config alone can
        # select the shared tier too: state.backend="redis" connects to
        # state.redis_host:redis_port (the reference's REDIS_HOST/PORT env
        # contract) when no explicit client is passed.
        st = self.config.state
        cache_kwargs = dict(
            txn_ttl_s=st.transaction_ttl_s,
            features_ttl_s=st.features_ttl_s,
            user_list_len=st.user_history_len,
            merchant_list_len=st.merchant_history_len,
        )
        self._owned_state_client = None
        if state_client is None and st.backend == "redis":
            from realtime_fraud_detection_tpu.state import RespClient

            state_client = RespClient(host=st.redis_host, port=st.redis_port)
            # config-driven connection: this scorer owns the socket and
            # close() releases it (an explicitly passed client stays the
            # caller's to manage)
            self._owned_state_client = state_client
        if stores is not None:
            # injected store bundle (cluster/partition.PartitionedStore,
            # or any object exposing the same four store attributes): the
            # partition-parallel worker plane hands each worker a scorer
            # whose state is key-sharded to its owned partitions — the
            # scorer itself stays shard-oblivious. Mutually exclusive
            # with the shared RESP tier: both decide where state lives.
            if state_client is not None:
                raise ValueError(
                    "pass either stores= (partitioned state) or "
                    "state_client= (shared RESP tier), not both")
            self.profiles = stores.profiles
            self.velocity = stores.velocity
            self.txn_cache = stores.txn_cache
            self.history = stores.history
            hist_seq = getattr(self.history, "seq_len", self.sc.seq_len)
            hist_dim = getattr(self.history, "feature_dim",
                               self.sc.feature_dim)
            if (hist_seq != self.sc.seq_len
                    or hist_dim != self.sc.feature_dim):
                # a mismatched history table would silently gather
                # wrong-shaped LSTM inputs — refuse at construction
                raise ValueError(
                    f"injected history store is ({hist_seq}, {hist_dim})"
                    f", scorer expects ({self.sc.seq_len}, "
                    f"{self.sc.feature_dim})")
        elif state_client is not None:
            from realtime_fraud_detection_tpu.state.shared import (
                SharedProfileStore,
                SharedTransactionCache,
                SharedVelocityStore,
            )

            self.profiles = SharedProfileStore(state_client)
            self.velocity = SharedVelocityStore(state_client)
            self.txn_cache = SharedTransactionCache(state_client,
                                                    **cache_kwargs)
            self.history = UserHistoryStore(self.sc.seq_len,
                                            self.sc.feature_dim)
        else:
            self.profiles = ProfileStore()
            self.velocity = VelocityStore()
            self.txn_cache = TransactionCache(**cache_kwargs)
            self.history = UserHistoryStore(self.sc.seq_len,
                                            self.sc.feature_dim)
        self.graph = EntityGraphStore(self.sc.fanout)
        # typed entity-graph plane (graph/): heterogeneous
        # user<->device<->merchant<->IP neighborhoods for the GNN branch.
        # The store rides the injected partition bundle when one is given
        # (PartitionedStore.graph facade — snapshot/handoff/digest for
        # free); otherwise it is scorer-local like the bipartite store.
        if self.sc.graph_mode not in ("bipartite", "typed"):
            raise ValueError(
                f"ScorerConfig.graph_mode must be 'bipartite' or 'typed', "
                f"got {self.sc.graph_mode!r}")
        self.typed_graph = None
        self._sampler = None
        if self.sc.graph_mode == "typed":
            from realtime_fraud_detection_tpu.graph.sampler import (
                NeighborSampler,
            )
            from realtime_fraud_detection_tpu.graph.store import (
                TypedEntityGraph,
            )

            tg = getattr(stores, "graph", None) if stores is not None \
                else None
            self.typed_graph = (tg if tg is not None
                                else TypedEntityGraph(self.sc.fanout))
            self._sampler = NeighborSampler(
                self.typed_graph, self.sc.node_dim, self.sc.fanout,
                self.sc.graph_fanout2,
                user_rows=lambda ids: self._users.peek_rows(ids),
                merchant_rows=lambda ids: self._merchants.peek_rows(ids))
        if self.sc.tokenizer == "wordpiece":
            from realtime_fraud_detection_tpu.models.wordpiece import (
                WordPieceTokenizer,
            )

            self.tokenizer = WordPieceTokenizer(
                max_length=self.sc.text_len,
                cache_entries=self.sc.token_cache_entries)
        elif self.sc.tokenizer == "word":
            self.tokenizer = FraudTokenizer(
                vocab_size=bert_config.vocab_size,
                max_length=self.sc.text_len,
                cache_entries=self.sc.token_cache_entries,
            )
        else:
            # a typo'd tokenizer name must not silently feed a text model
            # ids from the wrong vocabulary
            raise ValueError(
                f"ScorerConfig.tokenizer must be 'word' or 'wordpiece', "
                f"got {self.sc.tokenizer!r}")
        if self.tokenizer.vocab_size > bert_config.vocab_size:
            # JAX gathers clamp out-of-bounds indices SILENTLY: a token id
            # beyond the embedding table would score through row
            # vocab_size-1 with no error anywhere (ADVICE r5) — refuse the
            # pairing at construction instead
            raise ValueError(
                f"tokenizer vocab_size {self.tokenizer.vocab_size} exceeds "
                f"bert_config.vocab_size {bert_config.vocab_size}: "
                f"out-of-range ids would be silently clamped by the "
                f"embedding gather")
        self._users = _EntityIndex(self.sc.node_dim)
        self._merchants = _EntityIndex(self.sc.node_dim)
        # host-assembly plane: cross-batch entity join-row cache
        # (generation-stamped against the profile store), reusable pad
        # staging per bucket, and per-stage wall-clock spans
        # (assemble/pack/dispatch/device_wait) for the obs plane
        from realtime_fraud_detection_tpu.features.schema import (
            EntityRowCache,
        )
        from realtime_fraud_detection_tpu.obs.profiling import SpanTimer

        self._join_cache = EntityRowCache()
        self._staging = _StagingBuffers()
        # text shapes (scoring/text_split.py): the programs compiled for
        # each row bucket that has left the plain launch, and the launch
        # counters summed since construction
        self._text_families: Dict[int, tuple] = {}
        self._launch_totals: Dict[str, int] = dict.fromkeys(
            LAUNCH_COUNTERS, 0)
        self.spans = SpanTimer()
        self.last_features = np.zeros((0, self.sc.feature_dim), np.float32)
        self.stats: Dict[str, float] = {"scored": 0, "batches": 0, "total_time_s": 0.0}
        # top-10 global feature importances (reference explanation field,
        # ensemble_predictor.py:371-435); set after training via
        # set_feature_importances, attached to every explanation
        self._top_importances: Optional[Dict[str, float]] = None

    # ------------------------------------------------------------- state plane
    def seed_profiles(self, users: Mapping[str, Mapping[str, Any]],
                      merchants: Mapping[str, Mapping[str, Any]]) -> None:
        self.profiles.seed(users, merchants)

    def _model_valid_dev(self, mv: Optional[np.ndarray] = None):
        """Device copy of the branch-validity mask, re-pushed only when the
        mask changes — not one h2d transfer per microbatch."""
        cached = getattr(self, "_mv_cache", None)
        if mv is None:
            mv = self.effective_model_valid()
        mv = np.asarray(mv)
        if cached is None or not np.array_equal(cached[0], mv):
            self._mv_cache = (mv.copy(), jax.device_put(mv))
        return self._mv_cache[1]

    def plane_refusal(self, plane: str, asked_by: str = "") -> Optional[str]:
        """Why this scorer's text branch does not run under ``plane`` (a
        name of models/text_encoder.py; ``asked_by`` is the pool's class
        where the plane is one), or None where it does: the ONE check of
        what the encoder's row admits. The planes were written for the
        DistilBERT branch's parameter layout and its single result; an
        encoder whose row does not name one is refused by name instead of
        miscomputed."""
        if plane == TEXT_SPLIT and self._pool is not None:
            return (f"{type(self._pool).__name__}: every replica would "
                    "compile each bucket's family of programs")
        if plane in self._text.planes:
            return None
        name = type(self.bert_config).__name__
        if plane == TEXT_SPLIT:
            return (f"a {name} text branch: the narrow width is the "
                    "bidirectional encoder's attention kernel's; a causal "
                    "encoder's batch is one launch at text_len")
        if plane == POOL:
            return (f"{asked_by} (DevicePool / MeshExecutor) dispatches the "
                    "DistilBERT program's single result; not available with "
                    f"a {name} text branch, which runs on one chip")
        refused = {
            INT8: "QuantSettings(bert_weights='int8') quantizes "
                  "DistilBERT's dense layers (models/quant.py)",
            DEQUANT: "KernelSettings.dequant_matmul is the int8 DistilBERT "
                     "branch's kernel (ops/dequant_matmul.py)",
            MESH: f"a sharded mesh of {self.mesh.devices.size} devices would "
                  f"split the batch under {self._text.one_device} encoder "
                  "runs on one device "
                  "(build_mesh(devices=jax.devices()[:1]))",
        }[plane]
        return f"{refused}: not available with a {name} text branch"

    def require_plane(self, plane: str, asked_by: str = "") -> None:
        refused = self.plane_refusal(plane, asked_by)
        if refused:
            raise ValueError(refused)

    # ------------------------------------------------------------- pooling
    def attach_pool(self, pool) -> None:
        """Adopt a DevicePool: subsequent dispatches route through it.
        Called by DevicePool.__init__ — construct the scorer first, then
        the pool around it."""
        self.require_plane(POOL, type(pool).__name__)
        self._pool = pool

    # --------------------------------------------------------- graph plane
    def attach_graph_fetch(self, client) -> None:
        """Adopt a graph.fetch.GraphFetchClient: the typed sampler
        resolves non-owned neighbor nodes through it (budgeted,
        deadlined, degrade-to-local). Typed graph mode only."""
        if self._sampler is None:
            raise ValueError(
                "attach_graph_fetch needs ScorerConfig.graph_mode='typed'")
        self._sampler.attach_fetch(client)

    def graph_snapshot(self) -> Dict[str, Any]:
        """Graph-plane observability payload
        (obs.metrics.MetricsCollector.sync_graph): typed-store node/edge
        counts by type, sampler cache hits/misses/evictions, and — when a
        fetch client is attached — the cross-partition resolution
        counters. Bipartite mode reports just the mode (the legacy store
        has no typed series to mirror)."""
        snap: Dict[str, Any] = {"mode": self.sc.graph_mode}
        if self.typed_graph is not None:
            snap["store"] = self.typed_graph.stats()
            snap["sampler"] = self._sampler.stats()
            if self._sampler.fetch is not None:
                snap["fetch"] = self._sampler.fetch.stats()
        return snap

    @property
    def pool(self):
        return self._pool

    # ---------------------------------------------------------- degradation
    def set_degradation(self, mask: Optional[np.ndarray],
                        rules_only: bool = False, level: int = 0) -> None:
        """Apply a QoS ladder rung: ``mask`` narrows the enabled-branch set
        for subsequent dispatches (None = full ensemble); ``rules_only``
        swaps the served score for the rule score at response build. Cheap
        host-field writes — the fused program takes validity as a runtime
        tensor, so stepping the ladder never recompiles. With a device
        pool attached the rung fans out to all replicas atomically for
        free: every pooled dispatch passes the CURRENT host mask and each
        replica refreshes its device copy by value comparison, so every
        later dispatch — on any replica — runs the new rung while
        in-flight batches complete under their dispatch-time snapshot."""
        self._qos_mask = None if mask is None else np.asarray(mask, bool)
        self._qos_rules_only = bool(rules_only)
        self.qos_level = int(level)

    def effective_model_valid(self) -> np.ndarray:
        """Deployment validity AND the current QoS rung's mask."""
        if self._qos_mask is None:
            return np.asarray(self.model_valid)
        return np.asarray(self.model_valid) & self._qos_mask

    # ----------------------------------------------------------------- models
    def set_feature_importances(self, importances) -> None:
        """Attach global gain importances (e.g. ``GBDTTrainer.
        feature_importances_``) to prediction explanations as the top-10
        name->score mapping the reference emits (§2.2). Pass None to clear."""
        if importances is None:
            self._top_importances = None
            return
        from realtime_fraud_detection_tpu.features.extract import (
            top_feature_importances,
        )

        self._top_importances = top_feature_importances(importances)

    def refresh_blend_from_config(self) -> None:
        """Re-read ensemble weights/strategy and the enabled-branch set
        from ``self.config`` — the zero-recompile blend swap (weights and
        validity are runtime tensors to the fused program, not compile
        constants). Callers hold the score lock; the next microbatch runs
        the new blend."""
        self.ensemble_params = EnsembleParams.from_config(
            self.config, MODEL_NAMES)
        enabled = self.config.get_enabled_models()
        self.model_valid = np.asarray(
            [n in enabled for n in MODEL_NAMES], bool)

    def set_models(self, models: ScoringModels) -> None:
        """Swap the model set (hot reload). Params are replicated onto this
        scorer's mesh — arrays restored from checkpoint arrive committed to
        one device, which would clash with mesh-sharded batch arguments.
        With the quant plane on, incoming f32 params are quantized FIRST
        (host-side, before replication), so a hot swap — /reload-models,
        feedback promotion, drill retrain — always serves this scorer's
        configured form and the pool fan-out replicates the small blobs.

        Clears any attached feature importances: they describe the OLD
        trees; the caller re-attaches via set_feature_importances if it has
        importances for the new model set.
        """
        from realtime_fraud_detection_tpu.core.mesh import replicated_sharding

        models = self._maybe_quantize(models)
        self.models = jax.device_put(models, replicated_sharding(self.mesh))
        self._top_importances = None
        if self._pool is not None:
            # replica-by-replica fan-out; in-flight batches keep the params
            # reference they captured at launch — never mixed within a batch
            self._pool.set_models(models)

    # ------------------------------------------------------------ quantization
    def _maybe_quantize(self, models: ScoringModels) -> ScoringModels:
        """Apply the configured weight quantization to an incoming model
        set (idempotent — already-quantized params pass through). The
        calibrated pytree is committed back onto the mesh immediately:
        calibration runs host-side, and leaving numpy leaves in
        ``self.models`` would re-upload the whole branch H2D on every
        dispatch of the non-pool path."""
        if self.quant.bert_mode() != "int8":
            return models
        from realtime_fraud_detection_tpu.core.mesh import (
            replicated_sharding,
        )
        from realtime_fraud_detection_tpu.models.quant import (
            quantize_bert_params,
        )

        qbert = quantize_bert_params(models.bert)
        if qbert is models.bert:           # already quantized: no re-put
            return models
        return models.replace(
            bert=jax.device_put(qbert, replicated_sharding(self.mesh)))

    def quant_static(self) -> Dict[str, str]:
        """The static kernel-selection kwargs for the fused program —
        threaded into every dispatch (mesh path AND the device pool's
        per-replica launches). The BERT mode needs no static flag: the
        compute seam detects the quantized parameter layout structurally.
        Memoized by settings values — callers splat the returned dict and
        must not mutate it."""
        q = self.quant
        key = ("quant", q.enabled, q.tree_kernel, q.iforest_kernel)
        cached = self._static_cache.get(key)
        if cached is None:
            if not q.enabled:
                cached = {"tree_kernel": "gather",
                          "iforest_kernel": "gather"}
            else:
                cached = {"tree_kernel": q.tree_kernel,
                          "iforest_kernel": q.iforest_kernel}
            self._static_cache[key] = cached
        return cached

    def record_quant_gate(self, passed: bool) -> None:
        """Record a divergence-oracle verdict (rtfd quant-drill / any
        caller running the quantized-vs-f32 comparison); mirrored to the
        ``quant_gate_verdicts_total`` Prometheus series by sync_quant."""
        self._quant_gate_counts["pass" if passed else "fail"] += 1

    def quant_snapshot(self) -> Dict[str, Any]:
        """Quant-plane observability payload (obs.metrics.sync_quant):
        the SERVED per-branch modes (read from the live params, not the
        config — the truth after any allow_arch_mismatch restore), param
        bytes per quantizable branch, and cumulative gate verdicts."""
        from realtime_fraud_detection_tpu.models.quant import (
            bert_param_bytes,
            is_quantized_bert,
        )

        static = self.quant_static()
        return {
            "modes": {
                "bert_text": ("int8" if is_quantized_bert(self.models.bert)
                              else "f32"),
                "xgboost_primary": static["tree_kernel"],
                "isolation_forest": static["iforest_kernel"],
            },
            "param_bytes": {"bert_text": bert_param_bytes(self.models.bert)},
            "gate": dict(self._quant_gate_counts),
        }

    # ------------------------------------------------------------ kernel plane
    def kernel_static(self) -> Dict[str, Any]:
        """The kernel-plane static kwargs for the fused program — threaded
        into every dispatch next to ``quant_static()``. All-off while the
        plane is disabled, so the compiled program (and the packed result
        layout) is byte-identical to the legacy one. The QoS rung is a
        runtime mask and never part of the key: stepping the ladder
        compiles nothing. Memoized by settings values — callers splat,
        never mutate."""
        k = self.kernels
        if not k.enabled:
            key = ("kernel", False)
            cached = self._static_cache.get(key)
            if cached is None:
                cached = {"dequant_kernel": "off", "epilogue_kernel": "off",
                          "kernel_interpret": False}
                self._static_cache[key] = cached
            return cached
        key = ("kernel", True, k.dequant_matmul, k.epilogue, k.attention,
               self._kernel_interpret)
        cached = self._static_cache.get(key)
        if cached is None:
            cached = {"dequant_kernel": k.dequant_matmul,
                      "epilogue_kernel": k.epilogue,
                      "kernel_interpret": self._kernel_interpret}
            self._static_cache[key] = cached
        return cached

    def effective_use_pallas(self, devices: Optional[int] = None,
                             text_len: Optional[int] = None) -> bool:
        """Whether the text branch is ASKED to run its Pallas kernels: the
        traced guard of each of the encoder's kernel sites
        (``models/text_encoder.KernelSite``) still sends a shape its
        predicate declines to the XLA form, and the engagement counters say
        so. With the kernel plane on, ``KernelSettings.attention`` decides —
        how a drill or an A/B forces either side. With it off, nothing a
        user sets does: the kernels run where the devices are TPUs, one of
        the sites takes the shape and the program is one device's — XLA
        cannot partition a Mosaic call, so a program whose batch is sharded
        over a mesh keeps the XLA forms. ``devices`` is how many the
        program spans: the scorer's own mesh by default, one for a
        ``DevicePool`` replica, a ``MeshExecutor`` replica's sub-mesh.
        ``text_len`` is the width the program is launched at:
        ``ScorerConfig.text_len`` by default, the narrower one for the
        short rows of a split batch (``scoring/text_split.py``)."""
        if self.kernels.enabled:
            return self.kernels.attention == "flash"
        if devices is None:
            devices = self.mesh.devices.size
        return (self._platform == "tpu" and devices == 1
                and self._text_kernel_shape_ok(text_len))

    def _not_asked(self, devices: Optional[int] = None,
                   text_len: Optional[int] = None) -> Optional[str]:
        """What keeps the selector (``effective_use_pallas``) from asking a
        launch at ``text_len`` for its kernels, by name, or None where it
        asks."""
        if self.effective_use_pallas(devices, text_len):
            return None
        if self.kernels.enabled:
            return f"KernelSettings.attention is {self.kernels.attention!r}"
        if self._platform != "tpu":
            return (f"a {self._platform} mesh: the kernel is chosen on TPU "
                    "devices")
        if devices is None:
            devices = self.mesh.devices.size
        if devices != 1:
            return (f"a program over {devices} devices: XLA cannot "
                    "partition a Mosaic call")
        return "none of the encoder's kernel sites takes the shape"

    def _text_kernel_shape_ok(self, text_len: Optional[int] = None) -> bool:
        """Whether any of the encoder's kernel sites has a Pallas kernel
        for a launch at ``text_len`` (each site's own guard then decides
        for it). The smallest bucket's one row decides for routed blocks:
        every larger bucket is a multiple of it, and a narrow capacity is
        whole tiles by ``text_split.CAPACITY_MULTIPLE``."""
        t = text_len or self.sc.text_len
        return any(site.refusal(self.bert_config, t, t) is None
                   for site in self._text.sites)

    def _record_kernel_dispatch(self, size: int, text_len: int,
                                capacity: Optional[int] = None) -> bool:
        """Host-side mirror of the per-site kernel engagement for one
        launch of ``size`` rows at ``text_len`` positions (a split batch
        records each of its two), its routed blocks at ``capacity`` token
        slots (None: every slot). The encoder's own sites
        (``models/text_encoder.KernelSite``) count EVERY launch, plane on
        or off: dispatched where the program is asked for its kernels and
        the shape guard the TRACED code consults (the site's ``refusal``)
        lets the launch through, a fallback where the selector or the guard
        sent it to the XLA form. The plane's other two sites count as
        dispatched when their mode asks for the Pallas kernel, and as a
        fallback when the guard routes them back — so
        ``kernel_fallback_total`` reports exactly what the compiled program
        did, without a device readback. Returns whether the launch is asked
        for the encoder's kernels."""
        disp, fall = (self._kernel_counts["dispatch"],
                      self._kernel_counts["fallback"])
        asked = self.effective_use_pallas(
            getattr(self._pool, "program_devices", None), text_len)
        slots = capacity or size * text_len
        for site in self._text.sites:
            held = asked and site.refusal(self.bert_config, text_len,
                                          slots) is None
            (disp if held else fall)[site.name] += 1
        if not self.kernels.enabled:
            return asked
        from realtime_fraud_detection_tpu.models.quant import (
            is_quantized_bert,
        )
        from realtime_fraud_detection_tpu.ops import (
            epilogue_supported,
            matmul_supported,
            rows_supported,
        )

        modes = self.kernels.site_modes()
        h = self.bert_config.hidden_size
        ffn = self.bert_config.intermediate_size
        s = text_len
        m = size * s
        if modes["dequant_matmul"] == "pallas":
            disp["dequant_matmul"] += 1
            # f32 params have no int8 site to fuse — structurally the XLA
            # path, counted as a fallback like any other guard miss
            ok = (is_quantized_bert(self.models.bert)
                  and matmul_supported(m, h, h)
                  and matmul_supported(m, h, ffn)
                  and matmul_supported(m, ffn, h)
                  and rows_supported(m, h) and rows_supported(s, h))
            if not ok:
                fall["dequant_matmul"] += 1
        if modes["epilogue"] == "pallas":
            disp["epilogue"] += 1
            if not epilogue_supported(size, NUM_MODELS):
                fall["epilogue"] += 1
        return asked

    def kernel_snapshot(self) -> Dict[str, Any]:
        """Kernel-plane observability payload (obs.metrics.sync_kernels):
        effective per-site modes, whether the Pallas interpreter is
        serving (a CPU mesh), cumulative dispatch/fallback counts per
        site, and why a launch at ``text_len`` keeps the XLA form at each of
        the encoder's sites whose reason follows from the width, by name
        (None where it holds the kernel): the shape the kernel declines —
        the predicate the traced guard consults — else what kept the
        selector (``effective_use_pallas``) from asking."""
        devices = getattr(self._pool, "program_devices", None)
        t = self.sc.text_len
        refused = {site.name: (site.refusal(self.bert_config, t, t)
                               or self._not_asked(devices))
                   for site in self._text.sites if site.by_width}
        return {
            "modes": self.kernels.site_modes(),
            "interpret": bool(self.kernels.enabled
                              and self._kernel_interpret),
            "dispatch": dict(self._kernel_counts["dispatch"]),
            "fallback": dict(self._kernel_counts["fallback"]),
            "refused": refused,
        }

    # ---------------------------------------------------------------- assembly
    def assemble(self, records: Sequence[Mapping[str, Any]],
                 now: Optional[float] = None,
                 trace: Optional[Any] = None) -> ScoreBatch:
        """Join state + encode one dense ScoreBatch (host side of the seam).

        Columnar: profile/velocity joins gather through the generation-
        stamped entity row cache (features/schema.EntityRowCache), entity
        indices resolve in one batched lookup, history gathers from the
        slot-table ring store, and repeated merchant texts hit the token
        LRU — the per-record Python work shrinks to the transaction-core
        fields. Bit-identical to ``assemble_serial`` (the record-at-a-time
        reference path) by construction and by test.
        """
        from realtime_fraud_detection_tpu.features.extract import (
            extract_features_host,
        )
        from realtime_fraud_detection_tpu.features.schema import (
            encode_transactions_columnar,
        )

        span = self.spans.span
        with span(scopes.ASSEMBLE, trace=trace):
            with span(scopes.ASSEMBLE_ENCODE):
                user_ids = [str(r.get("user_id", "")) for r in records]
                merchant_ids = [str(r.get("merchant_id", ""))
                                for r in records]
                uprofs = {u: p for u in user_ids
                          if (p := self.profiles.get_user(u)) is not None}
                mprofs = {m: p for m in merchant_ids
                          if (p := self.profiles.get_merchant(m)) is not None}
                velocities = {u: self.velocity.get_all(u, now)
                              for u in set(user_ids)}
                self._join_cache.sync(self.profiles)
                txn = encode_transactions_columnar(records, uprofs, mprofs,
                                                   velocities,
                                                   cache=self._join_cache)

            # feature history for the LSTM branch: append-then-gather
            # semantics. Extraction runs on the HOST backend: the rows are
            # needed host-side regardless (see extract_features_host).
            with span(scopes.ASSEMBLE_FEATURES):
                feats = extract_features_host(txn)
                self.last_features = feats  # host copy: feature-topic fan-out
            with span(scopes.ASSEMBLE_HISTORY):
                history, history_len = self.history.append_and_gather(
                    user_ids, feats)

            # entity graph for the GNN branch (ONE seam for both assemble
            # paths; ``graph`` is its own span, inside ``_graph_join``)
            u_idx = self._users.lookup_batch(user_ids, uprofs, False)
            m_idx = self._merchants.lookup_batch(merchant_ids, mprofs, True)
            graph_t = self._graph_join(user_ids, merchant_ids, u_idx, m_idx)

            with span(scopes.ASSEMBLE_TOKENIZE):
                token_ids, token_mask = self.tokenizer.encode_batch(
                    self._texts_for(records, merchant_ids, mprofs))

            return ScoreBatch(
                txn=txn,
                features=feats,
                history=history,
                history_len=history_len,
                token_ids=token_ids.astype(np.int32),
                token_mask=token_mask.astype(bool),
                valid=np.ones((len(records),), bool),
                **graph_t,
            )

    def _graph_join(self, user_ids: Sequence[str],
                    merchant_ids: Sequence[str],
                    u_idx: np.ndarray, m_idx: np.ndarray,
                    ) -> Dict[str, np.ndarray]:
        """The GNN branch's graph tensors — the ONE seam both assemble
        paths (columnar and record-at-a-time serial) call, so graph-on
        can never diverge columnar-vs-serial (edge maintenance used to
        live in two hand-kept copies).

        Bipartite mode keeps the historical sample-then-insert order:
        this batch's neighborhoods see only earlier batches' edges, then
        the batch's own edges are committed for the NEXT batch. Typed
        mode samples here too, but commits edges at FINALIZE time
        (``_write_back`` → ``TypedEntityGraph.add_batch``): the typed
        store lives in the partition bundle, and write-back is where
        every other partition-owned store mutates."""
        with self.spans.span(scopes.GRAPH):
            utable, mtable = self._users.table(), self._merchants.table()
            out: Dict[str, np.ndarray] = {
                "user_feat": utable[u_idx],
                "merchant_feat": mtable[m_idx],
            }
            if self._sampler is not None:
                out.update(self._sampler.sample(user_ids, merchant_ids))
            else:
                un_idx, un_mask = self.graph.user_neighbors(u_idx)
                mn_idx, mn_mask = self.graph.merchant_neighbors(m_idx)
                out.update(
                    user_neigh_feat=mtable[np.where(un_mask, un_idx, 0)],
                    user_neigh_mask=un_mask,
                    merch_neigh_feat=utable[np.where(mn_mask, mn_idx, 0)],
                    merch_neigh_mask=mn_mask,
                )
                self.graph.add_edges(u_idx, m_idx)
            return out

    def _texts_for(self, records, merchant_ids, mprofs) -> List[str]:
        """Combined text per record for the text branch (models/text.py)."""
        texts = []
        for r, m in zip(records, merchant_ids):
            mp = mprofs.get(m) or {}
            texts.append(combined_text({
                "merchant_name": mp.get("name") or str(r.get("merchant_name", "")),
                "description": str(r.get("description", "") or ""),
                "category": str(mp.get("category", "") or ""),
                "location": str(r.get("location", "") or ""),
            }))
        return texts

    def assemble_serial(self, records: Sequence[Mapping[str, Any]],
                        now: Optional[float] = None) -> ScoreBatch:
        """Record-at-a-time reference assembly: the pre-columnar baseline.

        Every record runs the full join/encode/tokenize path alone (one
        1-row encode, one 1-row feature extraction, one history append, one
        tokenize) and the rows are stacked at the end — exactly the cost
        profile of the reference's per-request serving loop
        (main.py:235-248). Kept as the equivalence oracle for the columnar
        path. The one batch-level carve-out: graph neighbor sampling for
        ALL records precedes this batch's edge inserts, matching the batch
        path's sample-then-insert order (per-record interleaving would make
        row i+1 see row i's edge — a different, order-dependent batch).
        """
        from realtime_fraud_detection_tpu.features.extract import (
            extract_features_host,
        )

        n = len(records)
        user_ids = [str(r.get("user_id", "")) for r in records]
        merchant_ids = [str(r.get("merchant_id", "")) for r in records]
        txns: List[Any] = []
        feat_rows: List[np.ndarray] = []
        hist_rows: List[np.ndarray] = []
        hist_lens: List[np.ndarray] = []
        tok_rows: List[np.ndarray] = []
        tok_masks: List[np.ndarray] = []
        u_idx = np.empty((n,), np.int64)
        m_idx = np.empty((n,), np.int64)
        mprofs: Dict[str, Any] = {}
        for i, (r, uid, mid) in enumerate(zip(records, user_ids,
                                              merchant_ids)):
            up = self.profiles.get_user(uid)
            mp = self.profiles.get_merchant(mid)
            if mp is not None:
                mprofs[mid] = mp
            txn = encode_transactions(
                [r],
                {uid: up} if up is not None else {},
                {mid: mp} if mp is not None else {},
                {uid: self.velocity.get_all(uid, now)})
            feats = extract_features_host(txn)
            hist, hlen = self.history.append_and_gather([uid], feats)
            u_idx[i] = self._users.lookup(uid, up, False)
            m_idx[i] = self._merchants.lookup(mid, mp, True)
            ids, mask = self.tokenizer.encode_batch(
                self._texts_for([r], [mid], mprofs))
            txns.append(txn)
            feat_rows.append(feats)
            hist_rows.append(hist)
            hist_lens.append(hlen)
            tok_rows.append(ids)
            tok_masks.append(mask)

        graph_t = self._graph_join(user_ids, merchant_ids, u_idx, m_idx)

        txn_all = jax.tree_util.tree_map(
            lambda *leaves: np.concatenate([np.asarray(lf) for lf in leaves],
                                           axis=0), *txns)
        feats = np.concatenate(feat_rows, axis=0)
        self.last_features = feats
        return ScoreBatch(
            txn=txn_all,
            features=feats,
            history=np.concatenate(hist_rows, axis=0),
            history_len=np.concatenate(hist_lens, axis=0),
            token_ids=np.concatenate(tok_rows, axis=0).astype(np.int32),
            token_mask=np.concatenate(tok_masks, axis=0).astype(bool),
            valid=np.ones((n,), bool),
            **graph_t,
        )

    def host_stats(self) -> Dict[str, Any]:
        """Host-assembly observability payload: per-stage span stats (every
        name of obs/scopes.BATCH_SPANS that ran, a StreamJob's ``job.*``
        spans included; each with ``total_s``, ``self_s`` and ``parent``)
        and cache hit/miss counters — the source
        obs/metrics.MetricsCollector.sync_host_stats exports as Prometheus
        series — and ``text_split``: the narrower width short rows are
        launched at (None where there is none), why it is refused if it
        is, rows launched at either width, batches that took two launches,
        the programs compiled per bucket (``(rows, width)``; of an encoder
        with routed blocks ``(rows, width, capacity)``), and
        ``expert_token_slots`` (the capacities launched, summed) and
        ``compact_batches`` (launches at a narrow one) — and ``compile``:
        the process's compile ledger (``obs/profiling.CompileLedger``) as
        totals by phase (``trace``, ``lower``, ``compile``: records and
        self seconds), programs compiled and persistent-cache hits and
        misses, since the process started and ``since_reset`` (the last
        ``spans.reset()``), and the newest ``records``, each with the span
        it was ``caused_by``."""
        caches: Dict[str, Any] = {"entity_rows": self._join_cache.stats()}
        cache_stats = getattr(self.tokenizer, "cache_stats", None)
        if cache_stats is not None:
            caches["tokens"] = cache_stats()
        text_split_stats = dict(
            {key: self._launch_totals[key] for key in (
                "short_text_rows", "long_text_rows", "split_batches",
                "expert_token_slots", "compact_batches")},
            width=self._narrow_text_len(self.sc.text_len),
            refused=self.plane_refusal(TEXT_SPLIT),
            families={size: list(programs) for size, programs
                      in self._text_families.items()})
        return {"stages": self.spans.stats(), "caches": caches,
                "text_split": text_split_stats,
                "compile": self.spans.compile_stats()}

    # ----------------------------------------------------------------- scoring
    def dispatch(self, records: Sequence[Mapping[str, Any]],
                 now: Optional[float] = None,
                 trace: Optional[Any] = None) -> "PendingScore":
        """Assemble + launch the fused device program WITHOUT blocking.

        JAX dispatch is async: the returned ``PendingScore`` holds device
        arrays still being computed, so the caller can assemble/dispatch the
        next microbatch (or do fan-out work) while the TPU runs this one.
        ``finalize`` blocks, builds §2.7 responses, and write-backs state.
        This is the in-path version of stream/microbatch.DoubleBufferedScorer
        — host→device pipelining, the reference operator pipeline's analog
        (SURVEY.md §2.8).

        ``trace`` (an obs.tracing.TraceBatch) collects batch-granular
        stage marks from the same spans (``SpanTimer.span``); None is the
        default.
        """
        # rtfd-lint: allow[wall-clock] dispatch_ms / processing_time_ms of the §2.7 response, not scoring control flow
        t0 = time.perf_counter()
        n = len(records)
        if n == 0:
            return PendingScore(records=[], n=0, out=None,
                                features=self.last_features[:0],
                                dispatch_ms=0.0)
        batch = self.assemble(records, now, trace=trace)
        return self.dispatch_assembled(batch, records, t0=t0, trace=trace)

    def dispatch_assembled(self, batch: ScoreBatch,
                           records: Sequence[Mapping[str, Any]],
                           t0: Optional[float] = None,
                           trace: Optional[Any] = None) -> "PendingScore":
        """Pad + pack + launch an already-assembled batch (the device half
        of ``dispatch``). Split out so the overlapped assembler stage
        (scoring/host_pipeline.py) can run ``assemble`` on its own thread
        and hand the result here."""
        if t0 is None:
            # rtfd-lint: allow[wall-clock] dispatch_ms / processing_time_ms of the §2.7 response, not scoring control flow
            t0 = time.perf_counter()
        n = len(records)
        # (a sum over the rows: a third of count_nonzero(axis=1)'s time)
        lengths = np.add.reduce(batch.token_mask, axis=1, dtype=np.int32)
        with self.spans.span(scopes.PACK, trace=trace):
            # an attached mesh executor (scoring/mesh_executor.py) shards
            # the batch over ITS data axis, which may differ from this
            # scorer's own mesh (e.g. a 1-device reference scorer driving a
            # 4x2 executor) — pad to whichever seam the batch will cross
            multiple = (getattr(self._pool, "batch_multiple", None)
                        or local_mesh_size(self.mesh))

            def bucket_of(rows: int) -> int:
                return bucket_for(rows, BATCH_BUCKETS, multiple_of=multiple)

            size = bucket_of(n)
            full = int(batch.token_ids.shape[1])
            launches = self._plan_launches(batch, lengths, size, full,
                                           bucket_of)
            for launch in launches:
                self._pack_launch(batch, launch)

        # the tracer's ``device_wait`` stage begins where the launch
        # returns: from the transaction's point of view the device
        # residency (compute + any pipeline dwell) starts there
        with self.spans.span(scopes.DISPATCH, trace=trace,
                             then=scopes.DEVICE_WAIT):
            mv = self.effective_model_valid()
            rules_only = self._qos_rules_only
            for launch in launches:
                launch.kernels = self._record_kernel_dispatch(
                    launch.size, launch.width, launch.capacity)
            token = None
            if self._pool is not None:
                # pooled mode: the whole microbatch runs on ONE replica
                # (model replication, not batch sharding) picked
                # round-robin by the pool; in-flight depth and retry live
                # there. A pool keeps the one launch (plane_refusal).
                token = self._pool.dispatch_packed(
                    launches[0].blobs, launches[0].spec,
                    self.ensemble_params, mv)
                out = token.out
                if trace is not None:
                    # which replica got the batch, and how deep its queue
                    # was at dispatch — the tail-attribution metadata the
                    # ISSUE's "where did the p99 go" question needs
                    trace.annotate(replica=token.replica_idx,
                                   inflight_depth=token.inflight_at_dispatch)
            elif len(launches) == 1:
                out = self._launch_packed(launches[0], mv)
            else:
                narrow, long_part = launches
                out = _SplitResult(self._launch_packed(narrow, mv),
                                   self._launch_packed(long_part, mv),
                                   long_part.rows)
            # Start the device->host copy NOW (it queues behind the
            # compute): by the time finalize() calls device_get, the
            # transfer is already in flight or done, so the d2h RTT
            # overlaps the next batch's assemble instead of serializing
            # after it.
            text_stats = None
            if isinstance(out, tuple):
                # the program's second, small output, where the encoder
                # returns statistics (pipeline.py)
                out, text_stats = out
            if self.sc.async_d2h:
                out.copy_to_host_async()
                if text_stats is not None:
                    text_stats.copy_to_host_async()
        counters = launch_counters(self._text, self.bert_config, launches,
                                   lengths, full)
        for key, value in counters.items():
            self._launch_totals[key] += value
        return PendingScore(records=list(records), n=n, out=out,
                            # rtfd-lint: allow[d2h] batch.features is a host-assembled ndarray
                            features=np.asarray(batch.features),
                            # rtfd-lint: allow[wall-clock] dispatch_ms / processing_time_ms of the §2.7 response, not scoring control flow
                            dispatch_ms=(time.perf_counter() - t0) * 1000.0,
                            model_valid=mv, rules_only=rules_only,
                            pool_token=token, trace=trace,
                            counters=counters, text_stats=text_stats)

    # ------------------------------------------------ text shapes of a batch
    def _narrow_text_len(self, full: int) -> Optional[int]:
        """The width short rows are launched at: the encoder's narrow width
        (``TextEncoder.narrow_width``) where the scorer takes the text split
        and that width is under the ``full`` one the batch was tokenised
        to; else None."""
        if self.plane_refusal(TEXT_SPLIT) is not None:
            return None
        narrow = self._text.narrow_width(self.bert_config)
        return narrow if narrow is not None and narrow < full else None

    def _plan_launches(self, batch: ScoreBatch, lengths: np.ndarray,
                       size: int, full: int, bucket_of) -> List["_Launch"]:
        """The launches of an assembled batch (``lengths``: its rows' real
        tokens), by the encoder's launch rule (``models/text_encoder.py``):
        one at ``full`` width, or the rows with no real token past the
        narrow width apart from the long ones (``text_split.plan``); the
        routed blocks of each, where the encoder has any, at the narrowest
        capacity that holds the launch's real tokens
        (``text_split.capacity``: a shape of the program, every real token
        is routed at any rung). The first time a bucket takes a shape
        either rule chose, its family is built (``_build_family``)."""
        n = len(lengths)
        narrow = self._narrow_text_len(full)
        launches = [_Launch(None, n, size, full)]
        if narrow is not None:
            is_long = np.asarray(batch.token_mask)[:, narrow:].any(axis=1)
            n_long = int(np.count_nonzero(is_long))
            # a narrow launch holds the long rows too, cut short, in the
            # places its bucket would pad anyway: no row is gathered, and
            # the long launch's answers replace theirs
            launches = [
                _Launch(np.flatnonzero(is_long), n_long, rows, width)
                if which == text_split.LONG else _Launch(None, n, rows, width)
                for which, rows, width in text_split.plan(
                    n - n_long, n_long, size, narrow, full, bucket_of)]
        for launch in launches:
            slots = launch.size * launch.width
            if self._text.capacities(slots) is not None:
                # (the tokenizer pads on the right: a cut keeps a prefix)
                held = lengths if launch.rows is None else lengths[launch.rows]
                launch.tokens = int(np.minimum(held, launch.width).sum())
                launch.capacity = text_split.capacity(launch.tokens, slots)
        first = launches[0]
        if size not in self._text_families and (
                first.width != full or first.capacity is not None):
            self._build_family(batch, n, size, narrow, full, bucket_of)
        return launches

    def _build_family(self, batch: ScoreBatch, n: int, size: int,
                      narrow: Optional[int], full: int, bucket_of) -> None:
        """Compile and run every program the launch rule can ask of bucket
        ``size`` — each ``(rows, width)`` of ``text_split.family`` at each
        rung of ``TextEncoder.capacities`` — under span ``build_programs``
        (``rows=``, ``programs=``, and the encoder's own ids), so that none
        first appears under load (each costs 0.6-1.1 s with a warm compile
        cache, 4-10 s cold, nearly all of it the interpreter's: a thread
        made it slower on the v5e; the compile ledger has each phase,
        ``host_stats()["compile"]``). Every member here, from this one
        place, whichever of them the batch needs itself: a program's entry
        in the persistent compile cache follows the call stack it was traced
        under (utils/compile_cache.py), and which members a bucket's first
        batch launches differs from run to run."""
        shapes = (text_split.family(size, narrow, full, bucket_of)
                  if narrow is not None else ((size, full),))
        programs = [(rows, width, rung) for rows, width in shapes
                    for rung in self._text.capacities(rows * width) or (None,)]
        ids = {}
        if self.effective_use_pallas(
                getattr(self._pool, "program_devices", None), full):
            ids = self._text.build_ids(self.bert_config, programs)
        mv = self.effective_model_valid()
        # rows with no token stand in where a rung has to hold them (nothing
        # to hold, so every rung takes them), else the batch's first rows:
        # the results are dropped
        empty = batch.replace(
            token_mask=np.zeros_like(np.asarray(batch.token_mask)))
        with self.spans.span(scopes.BUILD_PROGRAMS, rows=size,
                             programs=len(programs), **ids):
            for rows, width, rung in programs:
                k = min(n, rows)
                warm = _Launch(np.arange(k), k, rows, width, capacity=rung)
                self._pack_launch(batch if rung is None else empty, warm)
                jax.block_until_ready(self._launch_packed(warm, mv))
        self._text_families[size] = tuple(
            program if program[2] is not None else program[:2]
            for program in programs)

    def _pack_launch(self, batch: ScoreBatch, launch: "_Launch") -> None:
        """Pad ``launch``'s rows of ``batch`` to its bucket at its text
        width and pack them: the tokenizer pads on the right and [CLS] is
        position 0, so a row with no real token past the width loses
        nothing to the cut (a long row in a narrow launch does, and its
        answer there is not used)."""
        if launch.width != batch.token_ids.shape[1]:
            batch = batch.replace(
                token_ids=np.asarray(batch.token_ids)[:, :launch.width],
                token_mask=np.asarray(batch.token_mask)[:, :launch.width])
        # write-into staging: pad rows replicate row 0, the real
        # validity is the staging mask (same contract as pad_to_bucket)
        padded, mask = self._staging.pad(batch, launch.n, launch.size,
                                         rows=launch.rows)
        padded = padded.replace(valid=mask)
        if launch.capacity is not None and launch.n < launch.size:
            # the routed blocks run on real tokens: the bucket's filler rows
            # hold none (the staging buffer is this launch's to write)
            padded.token_mask[launch.n:] = False
        # Packed seam (core/packing.py): the 65-leaf ScoreBatch
        # collapses to 3 dense blobs (one h2d payload), the program
        # returns ONE f32 matrix (one d2h payload).
        if self.sc.transfer_bf16:
            padded = _stage_bf16(padded)
        launch.blobs, launch.spec = pack_tree(padded)

    def _launch_packed(self, launch: "_Launch", mv: np.ndarray):
        """One call of the fused program on this scorer's own mesh."""
        routed = {}
        if launch.capacity is not None:
            if launch.tokens > launch.capacity:
                raise ValueError(
                    f"text_capacity {launch.capacity} cannot hold the "
                    f"launch's {launch.tokens} real tokens: "
                    "text_split.capacity picks one that does")
            routed["text_capacity"] = launch.capacity
        sharded = shard_batch(self.mesh, launch.blobs)
        return score_fused_packed(
            self.models, sharded["f32"], sharded["i32"], sharded["u8"],
            spec=launch.spec, params=self.ensemble_params,
            model_valid=self._model_valid_dev(mv),
            blob_bf16=sharded["bf16"],
            bert_config=self.bert_config,
            use_pallas=self.effective_use_pallas(text_len=launch.width),
            **self.quant_static(), **self.kernel_static(), **routed,
        )

    def finalize(self, pending: "PendingScore", now: Optional[float] = None,
                 lock=None) -> List[Dict[str, Any]]:
        """Block on a dispatched batch, build responses, write back state.

        ``lock`` (optional) is held only around the state write-back, not
        the device wait — a concurrent caller can assemble/dispatch the next
        batch while this one's device result is still in flight.
        """
        import contextlib

        if pending.n == 0:
            return []
        span, token = self.spans.span, pending.pool_token
        # pooled path: the span says which replica it waited for
        ids = {"replica": token.replica_idx} if token is not None else {}
        # rtfd-lint: allow[wall-clock] dispatch_ms / processing_time_ms of the §2.7 response, not scoring control flow
        t_fin = time.perf_counter()
        # result in hand: everything after this span (response build,
        # state write-back, the owner's fan-out) is the tracer's
        # ``finalize`` stage
        with span(scopes.DEVICE_WAIT, trace=pending.trace, then="finalize",
                  **ids):
            if token is not None:
                # pooled completion: DevicePool.wait retries the batch on
                # a healthy replica if this one's result fetch fails
                out = self._pool.wait(token)
            else:
                out = jax.device_get(pending.out)  # blocks until done
            if pending.text_stats is not None:
                pending.counters.update(self._text.finalize_counters(
                    self.bert_config, jax.device_get(pending.text_stats)))
        # processing time = assemble/dispatch + device wait; excludes any
        # pipeline queue wait between dispatch() returning and this call
        elapsed_ms = (pending.dispatch_ms
                      # rtfd-lint: allow[wall-clock] dispatch_ms / processing_time_ms of the §2.7 response, not scoring control flow
                      + (time.perf_counter() - t_fin) * 1000.0)
        with span(scopes.FINALIZE_RESPONSES):
            results = self._build_responses(pending.records, out, pending.n,
                                            elapsed_ms,
                                            model_valid=pending.model_valid,
                                            rules_only=pending.rules_only)
        with span(scopes.FINALIZE_WRITE_BACK):
            with (lock if lock is not None else contextlib.nullcontext()):
                self._write_back(pending.records, results, now)
                self.stats["scored"] += pending.n
                self.stats["batches"] += 1
                self.stats["total_time_s"] += elapsed_ms / 1000.0
        return results

    def score_batch(self, records: Sequence[Mapping[str, Any]],
                    now: Optional[float] = None) -> List[Dict[str, Any]]:
        """Score transaction dicts -> FraudPrediction dicts (§2.7 schema)."""
        return self.finalize(self.dispatch(records, now), now)

    def _build_responses(self, records, out, n, elapsed_ms,
                         model_valid=None,
                         rules_only=False) -> List[Dict[str, Any]]:
        # ``out`` is the packed f32[B, 8+M] matrix from score_fused_packed:
        # OUT_COLUMNS then per-model predictions (one d2h transfer's worth).
        if model_valid is None:
            model_valid = self.model_valid
        mat = np.asarray(out)[:n]
        col = {name: mat[:, j] for j, name in enumerate(OUT_COLUMNS)}
        probs = col["fraud_probability"]
        conf = col["confidence"]
        decisions = col["decision"].astype(np.int32)
        risk = col["risk_level"].astype(np.int32)
        base_w = len(OUT_COLUMNS) + NUM_MODELS
        # fused-epilogue extension (pipeline.EXT_COLUMNS, detected by
        # width): the device already computed the explanation
        # contributions and the rules-only ladder — finalize reads the
        # columns instead of re-deriving them per record
        extended = mat.shape[1] >= base_w + NUM_MODELS + 2
        preds = mat[:, len(OUT_COLUMNS):base_w]
        contrib_cols = mat[:, base_w:base_w + NUM_MODELS] if extended else None
        rule = col["rule_score"]
        if rules_only and extended:
            probs = rule
            conf = np.ones_like(probs)
            decisions = mat[:, base_w + NUM_MODELS].astype(np.int32)
            risk = mat[:, base_w + NUM_MODELS + 1].astype(np.int32)
        elif rules_only:
            # the ladder's last rung: no learned branch survives; serve the
            # rule score with the decision/risk ladders recomputed host-side
            # (the device combine saw zero valid branches). Confidence is
            # 1.0 — the rule ladder is deterministic, and anything under
            # the confidence threshold would force every decision to REVIEW.
            from realtime_fraud_detection_tpu.features.rules import (
                APPROVE,
                APPROVE_WITH_MONITORING,
                DECLINE,
                REVIEW,
                risk_level_codes_np,
            )

            p = self.ensemble_params
            probs = rule
            conf = np.ones_like(probs)
            decisions = np.where(
                probs >= p.decline_threshold, DECLINE,
                np.where(probs >= p.review_threshold, REVIEW,
                         np.where(probs >= p.monitor_threshold,
                                  APPROVE_WITH_MONITORING,
                                  APPROVE))).astype(np.int32)
            risk = risk_level_codes_np(probs)
        high_amount = col["high_amount"] > 0.5
        unusual_hour = col["unusual_hour"] > 0.5
        high_risk_payment = col["high_risk_payment"] > 0.5
        per_txn_ms = elapsed_ms / max(n, 1)

        results = []
        weights = np.asarray(self.ensemble_params.weights)
        with_explanation = self.config.ensemble.enable_explanation
        for i, rec in enumerate(records):
            model_predictions = {
                name: float(preds[i, j])
                for j, name in enumerate(MODEL_NAMES) if model_valid[j]
            }
            if with_explanation:
                factors = []
                if high_amount[i]:
                    factors.append("high_transaction_amount")
                if unusual_hour[i]:
                    factors.append("unusual_transaction_hour")
                if high_risk_payment[i]:
                    factors.append("high_risk_payment_method")
                if contrib_cols is not None:
                    # device-computed (ops/epilogue.py), bit-equal to the
                    # single host f32 product it replaces
                    contributions = {
                        name: float(contrib_cols[i, j])
                        for j, name in enumerate(MODEL_NAMES)
                        if model_valid[j]
                    }
                else:
                    contributions = {
                        name: float(weights[j] * preds[i, j])
                        for j, name in enumerate(MODEL_NAMES)
                        if model_valid[j]
                    }
                explanation = {
                    "model_contributions": contributions,
                    "key_factors": factors,
                    "rule_score": float(rule[i]),
                }
                if rules_only:
                    explanation["degraded"] = "rules_only"
                if self._top_importances is not None:
                    # fresh dict per response: a consumer mutating one
                    # explanation must not corrupt its batch-mates
                    explanation["top_feature_importances"] = dict(
                        self._top_importances)
            else:
                # ensemble.enable_explanation=False (reference config.py:85
                # analog): schema keeps the key, host skips the per-record
                # dict assembly
                explanation = {}
            results.append({
                "transaction_id": str(rec.get("transaction_id", "")),
                "fraud_probability": float(probs[i]),
                "fraud_score": float(probs[i]),
                "risk_level": RISK_LEVEL_NAMES[int(risk[i])],
                "decision": DECISIONS[int(decisions[i])],
                "model_predictions": model_predictions,
                "confidence": float(conf[i]),
                "processing_time_ms": per_txn_ms,
                "explanation": explanation,
            })
        return results

    def replay_state(self, records: Sequence[Mapping[str, Any]],
                     now: Optional[float] = None) -> None:
        """State-only replay for the partition-handoff path
        (cluster/fleet.ClusterWorker): re-apply the state updates of
        records that were ALREADY scored, emitted, and committed by a
        worker that died after its last partition snapshot — without
        re-scoring on device or re-emitting anything.

        ``assemble`` reconstructs the history rings + profile/velocity
        read path exactly as the dead worker's scoring pass did; the
        write-back caches each transaction with an explicit marker
        result (the dead worker's served score is unknowable host-side —
        unlike the shard drill's deterministic stand-in — so a later
        duplicate re-emits a REVIEW marker rather than inventing a
        score). Effectively-once scoring and dedupe are preserved; the
        marker is honest about what was lost."""
        if not records:
            return
        self.assemble(records, now=now)
        markers = [{
            "transaction_id": str(r.get("transaction_id", "")),
            "fraud_score": 0.5,
            "decision": "REVIEW",
            "risk_level": "UNKNOWN",
            "confidence": 0.0,
            "explanation": {"replay_restored": True},
        } for r in records]
        self._write_back(records, markers, now)

    def _write_back(self, records, results, now: Optional[float]) -> None:
        """Post-scoring state updates (RedisTransactionSink.java:53-135)."""
        # rtfd-lint: allow[wall-clock] production default time base; virtual-clock callers pass now
        ts = now if now is not None else time.time()
        for rec, res in zip(records, results):
            uid = str(rec.get("user_id", ""))
            self.velocity.update(uid, float(rec.get("amount", 0.0)), ts)
            merged = dict(rec)
            merged["fraud_score"] = res["fraud_score"]
            merged["decision"] = res["decision"]
            # enough for the dedupe path to re-emit a faithful prediction
            # from cache (stream/job.py _emit_cached_dups)
            merged["risk_level"] = res["risk_level"]
            merged["confidence"] = res["confidence"]
            self.txn_cache.cache_transaction(merged, now=ts)
        if self.typed_graph is not None:
            # typed-graph ingest at the finalize seam: the shared
            # device_id/ip_address entity links (the FraudRing signature)
            # flow into per-entity state through ONE path-independent
            # seam — replay_state takes it too, so handoff's committed-
            # gap replay rebuilds the graph exactly like the live pass
            self.typed_graph.add_batch(
                [str(r.get("user_id", "")) for r in records],
                [str(r.get("merchant_id", "")) for r in records],
                [str(r.get("device_id")
                     or r.get("device_fingerprint") or "")
                 for r in records],
                [str(r.get("ip_address") or "") for r in records])
            self._sampler.sync()

    def close(self) -> None:
        """Release resources this scorer owns (currently: the state-tier
        connection it auto-created for config.state.backend="redis")."""
        if self._owned_state_client is not None:
            try:
                self._owned_state_client.close()
            finally:
                self._owned_state_client = None

    # ------------------------------------------------------------------ info
    def model_info(self) -> Dict[str, Any]:
        norm = self.config.normalized_weights()
        return {
            "models": {
                name: {
                    "enabled": bool(self.model_valid[j]),
                    "weight": float(norm.get(name, 0.0)),
                }
                for j, name in enumerate(MODEL_NAMES)
            },
            "strategy": self.config.ensemble.strategy,
            "num_models": NUM_MODELS,
            "mesh": dict(self.mesh.shape),
        }
