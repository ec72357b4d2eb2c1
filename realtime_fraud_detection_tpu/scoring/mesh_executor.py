"""GSPMD mesh-sharded scoring: ``data x model`` sharding behind the pool seam.

``DevicePool`` (scoring/device_pool.py) replicates FULL params onto every
chip: model size is capped by one chip's HBM and the only parallelism is
whole-microbatch replication. This module is the next unlock the ROADMAP
names — jit + ``NamedSharding`` over the named 2-D ``data x model`` mesh
(core/mesh.py), per the production pattern in "Scaling TensorFlow to 300M
predictions/sec" (arXiv:2109.09541): the microbatch shards over ``data``
(every chip computes B/data rows — the FLOPs lever) while selected branch
params shard over ``model`` (every chip stores 1/model of the branch — the
HBM lever), with trees/iforest/rules always replicated.

Numerics contract — why storage sharding, not Megatron compute sharding:
serving scores must be BIT-IDENTICAL to single-device scoring
(``rtfd mesh-drill`` pins it, like pool-drill before it). Megatron-style
row-parallel blocks end in partial-sum all-reduces that reorder float
additions — fine for training (the dryrun gates TP at rtol 2e-4), fatal
for a bit-replayable serving plane. So a "sharded" branch here stores its
params split over ``model`` and the fused program re-gathers them at the
use seam (``_regather_models`` — ZeRO-3/FSDP semantics): the all-gather
reconstructs exact bytes, the branch computes replicated per model shard,
and activations stay sharded over ``data`` only. Per-chip param bytes at
rest shrink ~1/model_axis; XLA frees the gathered temporaries after each
branch's last use, so transient peak is one branch, not the model. The
Megatron column/row STORAGE positions are kept (parallel/layouts.py
serving specs) so a later flip to true compute sharding is a gather
removal, not a re-layout.

One honest boundary on the bit-equality claim: the gather makes the
PARAMS exact, but splitting the batch over ``data`` changes how many rows
one device multiplies at once. On the CPU backend that moved at most one
row by one ulp at micro shapes (bucket 8 over a 4-way data axis), so
``rtfd mesh-drill`` pins equality with whole-batch single-device scoring
at the served bucket shapes (>= 8 rows per data shard). On four v5e chips
(chip_smoke.py --chips 4, PR 21) the text branch's bf16 rounding depends on
that row count for EVERY row: a 4x1 or 2x2 mesh differs from whole-batch
single-device scoring by up to 7.5e-4 in the branch (1.1e-4 in the score)
— exactly as one device scoring 256 rows differs from the same device
scoring 4 x 64 — and is bit-identical to single-device scoring of each
data shard's rows at the shard's shape. The contract the hardware keeps is
therefore: sharding adds nothing to the result beyond the per-device batch
shape, which is the shape-granularity caveat the bucket ladder already
owns.

Pool x mesh composition — replicate the MESH, not the chip: the executor
partitions its devices into ``replicas`` equal subsets, builds one
``data x model`` mesh per subset, and round-robins whole microbatches
across mesh replicas with per-replica in-flight depth — exactly
``DevicePool``'s dispatch shape with "device" generalized to "mesh".
``replicas=N, model_axis=1, one device each`` degenerates to the pool's
layout; ``replicas=1`` is a single program spanning every chip. The
executor sits behind the SAME dispatch/finalize seam the pool uses
(``FraudScorer.attach_pool``), so the overlapped assembler, QoS
degradation masks (per-dispatch snapshot of the host mask), tracing
annotations, and hot swap under the score lock all compose unchanged.

Unlike the pool there is NO retry-on-replica-failure rescue: a mesh
replica's batch lives sharded across its whole device subset, and a chip
loss there is a topology event (rebuild the executor over the survivors),
not a relaunch — ``wait`` marks the replica unhealthy, releases the slot,
and raises. The pool remains the fault-absorbing plane.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from realtime_fraud_detection_tpu.scoring.pipeline import (
    _PACKED_STATIC,
    MODEL_NAMES,
    _score_fused_packed_impl,
)

__all__ = ["MeshExecutor", "MeshToken", "mesh_score_packed",
           "mesh_score_packed_donated"]


def _regather_models(models, gather_fields: Tuple[str, ...], mesh):
    """Constrain the named ScoringModels fields back to replicated INSIDE
    the jitted program: GSPMD lowers the constraint to an all-gather of
    the stored shards — exact bytes, so the branch that follows computes
    the identical arithmetic to a single-device run. Branches not named
    are already replicated and pass through untouched."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if not gather_fields or mesh is None:
        return models
    rep = NamedSharding(mesh, P())
    gathered = {
        f: jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, rep),
            getattr(models, f))
        for f in gather_fields
    }
    return models.replace(**gathered)


# the mesh entry's static arguments: the packed program's own, forwarded
# as they are, and the two the re-gather reads
_MESH_STATIC = _PACKED_STATIC + ("gather_fields", "mesh")


def _mesh_score_packed_impl(models, blob_f32, blob_i32, blob_u8, *,
                            params, model_valid, blob_bf16=None,
                            gather_fields: Tuple[str, ...] = (),
                            mesh=None, **statics):
    models = _regather_models(models, gather_fields, mesh)
    return _score_fused_packed_impl(
        models, blob_f32, blob_i32, blob_u8, params=params,
        model_valid=model_valid, blob_bf16=blob_bf16, **statics)


def _jit_entries():
    """Build the jitted (and donated) mesh entries lazily so importing
    this module never initializes a JAX backend (the drill CLI parents
    stay jax-free)."""
    import jax

    plain = partial(jax.jit, static_argnames=_MESH_STATIC)(
        _mesh_score_packed_impl)
    donated = partial(
        jax.jit, static_argnames=_MESH_STATIC,
        donate_argnames=("blob_f32", "blob_i32", "blob_u8", "blob_bf16"),
    )(_mesh_score_packed_impl)
    return plain, donated


_ENTRIES: Optional[tuple] = None


def mesh_entry(donate: bool = False):
    """The jitted mesh scoring entry (donated or plain) — the executor
    dispatches through this, and the drill lowers it to verify the
    donation annotations reach the compiler."""
    global _ENTRIES
    if _ENTRIES is None:
        _ENTRIES = _jit_entries()
    return _ENTRIES[1 if donate else 0]


def mesh_score_packed(*args, **kwargs):
    return mesh_entry(False)(*args, **kwargs)


def mesh_score_packed_donated(*args, **kwargs):
    return mesh_entry(True)(*args, **kwargs)


class MeshToken:
    """One in-flight mesh-dispatched microbatch. Field names mirror
    ``PoolToken`` so the scorer's tracing annotations (replica id,
    in-flight depth at dispatch) read either token unchanged."""

    __slots__ = ("out", "replica_idx", "t_dispatch", "inflight_at_dispatch",
                 "staged")

    def __init__(self, out, replica_idx, t_dispatch,
                 inflight_at_dispatch=0, staged=None):
        self.out = out
        self.replica_idx = replica_idx
        self.t_dispatch = t_dispatch
        self.inflight_at_dispatch = inflight_at_dispatch
        # the device-side staged blobs — with donation on, runtimes that
        # honor it (accelerators; CPU only when the aliasing is strict)
        # consume these at launch, which is exactly why the executor never
        # reads them back (the host blobs stay the caller's)
        self.staged = staged


class _MeshReplica:
    """One ``data x model`` sub-mesh: committed sharded params + dispatch
    bookkeeping (the ``_Replica`` analog with "device" -> "mesh")."""

    def __init__(self, idx: int, mesh, models, shardings,
                 multihost: bool = False):
        import jax

        self.idx = idx
        self.mesh = mesh
        self.shardings = shardings           # NamedSharding tree (storage)
        if multihost:
            # a spanning mesh: every process holds the identical host
            # value (deterministic init / checkpoint), each commits only
            # the shards its chips own — no cross-host param bytes move
            from realtime_fraud_detection_tpu.core.mesh import (
                make_global_batch,
            )

            self.models = make_global_batch(mesh, models, shardings)
        else:
            self.models = jax.device_put(models, shardings)
        self.healthy = True
        self.inflight = 0
        self.dispatched = 0
        self.completed = 0
        self.failures = 0
        self.queue_wait_s = 0.0
        self._mv_cache: Optional[tuple] = None

    def mv_dev(self, mv: np.ndarray):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        cached = self._mv_cache
        if cached is None or not np.array_equal(cached[0], mv):
            self._mv_cache = (
                mv.copy(),
                jax.device_put(mv, NamedSharding(self.mesh, P())))
        return self._mv_cache[1]


class MeshExecutor:
    """Mesh-sharded microbatch executor behind the pool dispatch seam.

    ``devices`` split into ``replicas`` equal subsets; each subset becomes
    a ``(data=per/model_axis) x model_axis`` mesh holding one copy of the
    params, placed per branch (``shard_branches`` store sharded over
    ``model``; the rest replicate). Dispatch is strict round-robin across
    healthy mesh replicas with ``inflight_depth`` programs riding each —
    deterministic for the drill, exactly the pool's discipline.
    """

    def __init__(self, scorer, devices: Optional[Sequence] = None,
                 model_axis: int = 1, replicas: int = 1,
                 inflight_depth: int = 2, donate: Optional[bool] = None,
                 shard_branches: Sequence[str] = ("bert_text",),
                 mesh=None):
        import jax

        scorer.require_plane("pool", type(self).__name__)
        from realtime_fraud_detection_tpu.core.mesh import (
            DATA_AXIS,
            MODEL_AXIS,
            MeshConfig,
            build_mesh,
        )
        from realtime_fraud_detection_tpu.parallel.layouts import (
            SHARDABLE_BRANCHES,
            branch_serving_specs,
            tree_specs_to_shardings,
        )

        if mesh is not None:
            # pre-built mesh — the multihost serving mode: the caller
            # constructed it over jax.distributed's global device set
            # (core.mesh.build_multihost_mesh, process-major data axis so
            # model-axis collectives stay on ICI) and this executor is one
            # per-process participant of a single spanning program
            if replicas != 1 or devices is not None:
                raise ValueError(
                    "pass either a pre-built mesh= (one spanning replica) "
                    "or devices/replicas, not both")
            devs = list(mesh.devices.flat)
            model_axis = int(mesh.shape[MODEL_AXIS])
            per = len(devs)
        else:
            devs = (list(devices) if devices is not None
                    else list(jax.devices()))
            if not devs:
                raise ValueError("mesh executor needs at least one device")
            replicas = max(1, int(replicas))
            if len(devs) % replicas:
                raise ValueError(
                    f"{len(devs)} devices do not split into {replicas} "
                    f"equal mesh replicas")
            per = len(devs) // replicas
            model_axis = max(1, int(model_axis))
            if per % model_axis:
                raise ValueError(
                    f"model_axis={model_axis} does not divide the {per} "
                    f"devices of each mesh replica")
        self.scorer = scorer
        self.model_axis = model_axis
        self.data_axis = (int(mesh.shape[DATA_AXIS]) if mesh is not None
                          else per // model_axis)
        # >1 process = the spanning program's inputs/outputs are only
        # partially addressable here: staging goes through
        # make_global_batch and wait() returns THIS host's rows
        self.multihost = len({d.process_index for d in devs}) > 1
        # the scorer pads every microbatch to a multiple of this so the
        # data-axis split is always even (FraudScorer.dispatch_assembled)
        self.batch_multiple = self.data_axis
        # devices one replica's program spans (what the scorer's attention
        # selector asks: FraudScorer.effective_use_pallas)
        self.program_devices = self.data_axis * model_axis
        self.inflight_depth = max(1, int(inflight_depth))
        # donation needs accelerator buffer aliasing; the CPU backend only
        # warns and ignores it (same default rule as DevicePool)
        self.donate = (devs[0].platform != "cpu" if donate is None
                       else bool(donate))
        # effective placement: requested branches that exist AND an axis to
        # shard over; with model_axis=1 everything is replicated and the
        # gather seam compiles away entirely
        bad = [b for b in shard_branches if b not in SHARDABLE_BRANCHES]
        if bad:
            raise ValueError(
                f"branch(es) {bad} not shardable; expected a subset of "
                f"{sorted(SHARDABLE_BRANCHES)} (trees/iforest/rules are "
                f"replicated by design)")
        self.shard_branches: Tuple[str, ...] = tuple(
            sorted(b for b in shard_branches)) if model_axis > 1 else ()
        self._gather_fields: Tuple[str, ...] = tuple(
            sorted(SHARDABLE_BRANCHES[b] for b in self.shard_branches))
        self._cv = threading.Condition()
        self.replicas: List[_MeshReplica] = []
        for i in range(replicas):
            if mesh is not None:
                rep_mesh = mesh
            else:
                sub = devs[i * per:(i + 1) * per]
                rep_mesh = build_mesh(MeshConfig(model=model_axis), sub)
            specs = branch_serving_specs(scorer.models, model_axis,
                                         self.shard_branches)
            self.replicas.append(_MeshReplica(
                i, rep_mesh, scorer.models,
                tree_specs_to_shardings(rep_mesh, specs),
                multihost=self.multihost))
        self._rr = 0
        self.assignment_log: deque = deque(maxlen=4096)
        scorer.attach_pool(self)

    # ------------------------------------------------------------- capacity
    def __len__(self) -> int:
        return len(self.replicas)

    @property
    def healthy_count(self) -> int:
        return sum(1 for r in self.replicas if r.healthy)

    def total_slots(self) -> int:
        return max(1, self.healthy_count * self.inflight_depth)

    # ------------------------------------------------------------- dispatch
    def _pick_replica(self) -> tuple:
        """Strict round-robin over healthy mesh replicas, blocking at
        depth — the same deterministic discipline as DevicePool (the
        drill replays the assignment log)."""
        with self._cv:
            n = len(self.replicas)
            for off in range(n):
                rep = self.replicas[(self._rr + off) % n]
                if rep.healthy:
                    self._rr = (self._rr + off + 1) % n
                    break
            else:
                raise RuntimeError("mesh executor has no healthy replicas")
            # rtfd-lint: allow[wall-clock] queue-wait diagnostics (host stats), not control flow
            t0 = time.perf_counter()
            while rep.inflight >= self.inflight_depth:
                if not self._cv.wait(timeout=120.0):
                    raise TimeoutError(
                        f"mesh replica {rep.idx} stuck at inflight depth "
                        f"{rep.inflight} for 120s")
                if not rep.healthy:
                    return self._pick_replica()
            # rtfd-lint: allow[wall-clock] queue-wait diagnostics (host stats), not control flow
            rep.queue_wait_s += time.perf_counter() - t0
            rep.inflight += 1
            rep.dispatched += 1
            self.assignment_log.append(rep.idx)
            return rep, rep.inflight

    def _stage(self, rep: _MeshReplica, blobs: Dict[str, np.ndarray]):
        """Device-put the packed blobs sharded over the replica's data
        axis (batch sizes arrive pre-padded to ``batch_multiple``). On a
        multihost mesh each process feeds only the row span its chips own
        (process-major data axis — the per-TM Kafka-partition analog):
        hosts never exchange batch bytes."""
        import jax

        from realtime_fraud_detection_tpu.core.mesh import (
            batch_sharding,
            make_global_batch,
        )

        if not self.multihost:
            return {
                k: jax.device_put(
                    v, batch_sharding(rep.mesh, np.ndim(v) - 1))
                for k, v in blobs.items() if v is not None
            }
        nproc = jax.process_count()
        pid = jax.process_index()
        staged = {}
        for k, v in blobs.items():
            if v is None:
                continue
            rows = v.shape[0] // nproc
            local = v[pid * rows:(pid + 1) * rows]
            staged[k] = make_global_batch(
                rep.mesh, local, batch_sharding(rep.mesh, np.ndim(v) - 1))
        return staged

    def dispatch_packed(self, blobs: Dict[str, np.ndarray], spec, params,
                        model_valid: np.ndarray) -> MeshToken:
        """Stage + launch one packed microbatch on the next mesh replica.
        Non-blocking (JAX async dispatch) except for the depth
        backpressure, which is recorded as queue wait."""
        rep, depth = self._pick_replica()
        # rtfd-lint: allow[d2h] host bool[M] validity mask, never a device array
        mv = np.asarray(model_valid)
        try:
            staged = self._stage(rep, blobs)
            with self._cv:
                models = rep.models      # snapshot: hot swap never tears it
                mv_dev = rep.mv_dev(mv)
            fn = (mesh_score_packed_donated if self.donate
                  else mesh_score_packed)
            out = fn(models, staged["f32"], staged["i32"], staged["u8"],
                     spec=spec, params=params, model_valid=mv_dev,
                     blob_bf16=staged.get("bf16"),
                     bert_config=self.scorer.bert_config,
                     use_pallas=self.scorer.effective_use_pallas(
                         self.program_devices),
                     gather_fields=self._gather_fields,
                     mesh=rep.mesh,
                     # quant + kernel planes: same static kernel selection
                     # on every mesh replica (params are already quantized,
                     # so the sharded storage carries the int8 form for
                     # free, and no batch ever mixes kernel modes)
                     **self.scorer.quant_static(),
                     **self.scorer.kernel_static())
        except Exception:
            self._mark_failed(rep)
            raise
        return MeshToken(out, rep.idx,
                         # rtfd-lint: allow[wall-clock] dispatch-time diagnostics (host stats), not control flow
                         time.perf_counter(),
                         inflight_at_dispatch=depth, staged=staged)

    # ------------------------------------------------------------ completion
    def _mark_failed(self, rep: _MeshReplica) -> None:
        with self._cv:
            rep.failures += 1
            rep.healthy = False
            rep.inflight = max(0, rep.inflight - 1)
            self._cv.notify_all()

    def _release(self, rep: _MeshReplica) -> None:
        with self._cv:
            rep.inflight = max(0, rep.inflight - 1)
            rep.completed += 1
            self._cv.notify_all()

    def wait(self, token: MeshToken) -> np.ndarray:
        """Block on a mesh batch's result. A fetch failure marks the
        replica unhealthy, releases its slot and RAISES — a sharded
        program has no single-chip rescue copy (see module docstring);
        the caller's degradation path owns what happens next.

        Multihost: only this host's shards are addressable, so the
        return is THIS process's row span (in row order) — each host
        fans out the rows it fed, the multihost serving contract."""
        import jax

        rep = self.replicas[token.replica_idx]
        try:
            if self.multihost:
                jax.block_until_ready(token.out)
                # one shard per distinct row span: the model axis holds
                # replicated copies of each output row block on every
                # tile device — keep exactly one
                uniq = {}
                for s in token.out.addressable_shards:
                    uniq.setdefault(s.index[0].start or 0, s)
                parts = []
                for k in sorted(uniq):
                    # rtfd-lint: allow[d2h] the designated completion pull (finalize path)
                    parts.append(np.asarray(uniq[k].data))
                out = np.concatenate(parts, axis=0)
            else:
                # rtfd-lint: allow[d2h] the designated completion pull (finalize path)
                out = np.asarray(jax.device_get(token.out))
        except Exception:
            self._mark_failed(rep)
            raise
        self._release(rep)
        return out

    # -------------------------------------------------------------- control
    def set_models(self, models) -> None:
        """Re-shard a model swap replica-by-replica per the SAME placement
        (callers hold the score lock — the /reload-models recipe). A batch
        in flight keeps the params reference captured at launch, so no
        batch ever computes on mixed params."""
        import jax

        from realtime_fraud_detection_tpu.parallel.layouts import (
            branch_serving_specs,
            tree_specs_to_shardings,
        )

        from realtime_fraud_detection_tpu.core.mesh import make_global_batch

        for rep in self.replicas:
            specs = branch_serving_specs(models, self.model_axis,
                                         self.shard_branches)
            shardings = tree_specs_to_shardings(rep.mesh, specs)
            new = (make_global_batch(rep.mesh, models, shardings)
                   if self.multihost
                   else jax.device_put(models, shardings))
            with self._cv:
                rep.models = new
                rep.shardings = shardings

    def donation_lowering(self, blobs: Dict[str, np.ndarray], spec, params,
                          model_valid: np.ndarray,
                          donate: bool = True) -> str:
        """Lower (never execute) the selected entry for these blobs on
        replica 0 and return the StableHLO text. The drill greps it for
        the donation annotations (``tf.aliasing_output`` /
        ``jax.buffer_donor``) — the truthful donation evidence on EVERY
        backend: the fused program's output shape matches no input, so
        CPU PJRT (strict aliasing only) drops the donation at run time,
        while TPU reuses the donated space for temporaries. What must
        hold everywhere is that the annotation reaches the compiler."""
        rep = self.replicas[0]
        staged = self._stage(rep, blobs)
        # rtfd-lint: allow[d2h] host bool[M] validity mask, never a device array
        mv = np.asarray(model_valid)
        return mesh_entry(donate).lower(
            rep.models, staged["f32"], staged["i32"], staged["u8"],
            spec=spec, params=params, model_valid=rep.mv_dev(mv),
            blob_bf16=staged.get("bf16"),
            bert_config=self.scorer.bert_config,
            use_pallas=self.scorer.effective_use_pallas(
                self.program_devices),
            gather_fields=self._gather_fields, mesh=rep.mesh,
            **self.scorer.quant_static(),
            **self.scorer.kernel_static()).as_text()

    # ---------------------------------------------------------------- stats
    def _branch_fields(self) -> Dict[str, str]:
        return {"xgboost_primary": "trees", "lstm_sequential": "lstm",
                "bert_text": "bert", "graph_neural": "gnn",
                "isolation_forest": "iforest"}

    def param_bytes(self) -> Dict[str, Dict[str, int]]:
        """Per-branch param bytes as COMMITTED on mesh replica 0: the
        max-over-chips resident shard bytes vs the replicated-equivalent
        (full pytree bytes, what DevicePool would hold per chip). Read
        from the actual array shardings, never the spec intent — this is
        the number the drill's <=60% acceptance gate and the
        ``mesh_param_bytes_per_chip`` series report."""
        import jax

        rep = self.replicas[0]
        out: Dict[str, Dict[str, int]] = {}
        for branch, field in self._branch_fields().items():
            per_chip: Dict[Any, int] = {}
            total = 0
            for leaf in jax.tree_util.tree_leaves(getattr(rep.models,
                                                          field)):
                total += leaf.nbytes
                for shard in leaf.addressable_shards:
                    per_chip[shard.device] = (per_chip.get(shard.device, 0)
                                              + shard.data.nbytes)
            out[branch] = {
                "per_chip": max(per_chip.values()) if per_chip else 0,
                "replicated": total,
            }
        return out

    def stats(self) -> Dict[str, Any]:
        with self._cv:
            per_replica = [{
                "index": rep.idx,
                "healthy": rep.healthy,
                "dispatched": rep.dispatched,
                "completed": rep.completed,
                "inflight": rep.inflight,
                "failures": rep.failures,
                "queue_wait_ms": round(rep.queue_wait_s * 1e3, 3),
                "devices": int(np.prod(list(rep.mesh.shape.values()))),
            } for rep in self.replicas]
        return {
            "kind": "mesh",
            "replicas": per_replica,
            "n_replicas": len(per_replica),
            "healthy": sum(1 for r in per_replica if r["healthy"]),
            "inflight_depth": self.inflight_depth,
            "data_axis": self.data_axis,
            "model_axis": self.model_axis,
            "dispatched": sum(r["dispatched"] for r in per_replica),
            "completed": sum(r["completed"] for r in per_replica),
        }

    def mesh_snapshot(self) -> Dict[str, Any]:
        """Observability payload for ``obs.metrics.sync_mesh``: mesh
        geometry, the per-branch placement as 0/1 flags, per-chip vs
        replicated param bytes, and the cumulative dispatch counters."""
        pb = self.param_bytes()
        st = self.stats()
        return {
            "data_axis": self.data_axis,
            "model_axis": self.model_axis,
            "replicas": len(self.replicas),
            "placement": {name: ("sharded" if name in self.shard_branches
                                 else "replicated")
                          for name in MODEL_NAMES},
            "param_bytes": pb,
            "dispatched": {str(r["index"]): r["dispatched"]
                           for r in st["replicas"]},
            "completed": {str(r["index"]): r["completed"]
                          for r in st["replicas"]},
            "healthy": st["healthy"],
        }
