"""Replicated multi-chip scoring pool: N model instances, deep dispatch.

Every serving/stream path before this PR drove exactly ONE device — the
mesh sharded a microbatch ACROSS chips, but the hot loops
(serving/batcher.py, stream/job.py, scoring/host_pipeline.py) kept a
single program in flight, so on a v5e-8 seven chips idled while one chip
capped the plane. The throughput shape that actually scales ads/fraud
scoring — "Scaling TensorFlow to 300 million predictions per second"
(arXiv:2109.09541) and Google's
ads-serving writeup (arXiv:2501.10546) — is the opposite: REPLICATE the
model onto every chip and keep several whole microbatches in flight per
replica, so each chip runs its own fused program and the host's job is
only to keep the queues fed.

``DevicePool`` implements that shape over the existing packed seam:

- params are replicated per device at construction (one ``device_put``
  per replica — the ``core.mesh.replicated_sharding`` analog, minus the
  mesh: each replica is its own single-device program);
- ``dispatch_packed`` picks a replica round-robin, stages the packed
  blobs onto it (fresh buffers per dispatch = double-buffered H2D; on
  accelerators the donated-input jit lets XLA recycle them — the
  batch-256 h2d p99 lever), and launches without blocking;
- at most ``inflight_depth`` batches ride each replica; a full replica
  backpressures the dispatcher (the wait is recorded as queue-wait);
- completion order is the CALLER's: ``FraudScorer.finalize`` blocks on
  batches in dispatch order, so FIFO per source holds by construction;
- a replica whose result fetch fails is marked unhealthy and its batch
  is relaunched from the host-side blob copy on a healthy replica
  (counted in stats);
- ``set_models`` swaps params replica-by-replica (callers hold the score
  lock); an in-flight batch keeps the reference it captured at launch,
  so no batch ever sees mixed params;
- the branch-validity mask is snapshotted per dispatch: every launch
  passes the scorer's CURRENT host mask and each replica refreshes its
  device copy by value comparison (``_Replica.mv_dev``), so a QoS ladder
  step (``FraudScorer.set_degradation`` — one host-field write) fans out
  to all replicas atomically: every batch dispatched after the step runs
  the new mask on whichever replica it lands on, every batch before it
  completes under its own.

Bit-equality contract: a pooled batch runs the IDENTICAL packed program
on identical inputs — only the device differs — so scores are
bit-identical to single-device scoring on the same platform
(``rtfd pool-drill`` pins it).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["DevicePool", "PoolToken"]


class PoolToken:
    """One pooled in-flight batch: the launched device array plus
    everything needed to relaunch it elsewhere if its replica dies."""

    __slots__ = ("out", "replica_idx", "blobs", "spec", "params",
                 "model_valid", "t_dispatch", "inflight_at_dispatch")

    def __init__(self, out, replica_idx, blobs, spec, params, model_valid,
                 t_dispatch, inflight_at_dispatch=0):
        self.out = out
        self.replica_idx = replica_idx
        self.blobs = blobs              # host numpy copies (retry source)
        self.spec = spec
        self.params = params
        self.model_valid = model_valid  # host bool[M] snapshot
        self.t_dispatch = t_dispatch
        # the replica's queue depth (this batch included) captured under
        # the pool lock at assignment — tail-attribution metadata for the
        # tracing plane (a p99 outlier dispatched at depth 2 waited out a
        # predecessor's compute; one dispatched at depth 1 did not)
        self.inflight_at_dispatch = inflight_at_dispatch


class _Replica:
    """One device's model instance + dispatch bookkeeping."""

    def __init__(self, idx: int, device, models):
        import jax

        self.idx = idx
        self.device = device
        self.models = jax.device_put(models, device)
        self.healthy = True
        self.inflight = 0
        self.dispatched = 0
        self.completed = 0
        self.retries = 0            # batches RESCUED ONTO this replica
        self.failures = 0           # fetch failures observed ON this replica
        self.queue_wait_s = 0.0
        self.fail_next = 0          # test fault injection (see inject_fault)
        self.slow_next = 0          # slow-device injection (inject_slow)
        self.slow_s = 0.0           # per-injected-fetch added delay
        self._mv_cache: Optional[tuple] = None  # (host mask, device copy)

    def mv_dev(self, mv: np.ndarray):
        import jax

        cached = self._mv_cache
        if cached is None or not np.array_equal(cached[0], mv):
            self._mv_cache = (mv.copy(), jax.device_put(mv, self.device))
        return self._mv_cache[1]


class DevicePool:
    """Round-robin replicated dispatch across every addressable device.

    ``inflight_depth`` is PER REPLICA (>= 2 keeps a replica's compute
    back-to-back: one batch running while the next one's H2D stages).
    Thread-safe: dispatch and completion may come from different threads
    (AssemblerStage dispatches, the finalize path completes).
    """

    def __init__(self, scorer, devices: Optional[Sequence] = None,
                 inflight_depth: int = 2, donate: Optional[bool] = None):
        import jax

        scorer.require_plane("pool", type(self).__name__)
        self.scorer = scorer
        devs = list(devices) if devices is not None else list(jax.devices())
        if not devs:
            raise ValueError("device pool needs at least one device")
        self.inflight_depth = max(1, int(inflight_depth))
        # each replica's program is one device's (what the scorer's
        # attention selector asks: FraudScorer.effective_use_pallas)
        self.program_devices = 1
        # donation needs accelerator buffer aliasing; the CPU backend only
        # warns and ignores it, so default it off there to keep logs clean
        self.donate = (devs[0].platform != "cpu" if donate is None
                       else bool(donate))
        self._cv = threading.Condition()
        self.replicas = [_Replica(i, d, scorer.models)
                         for i, d in enumerate(devs)]
        self._rr = 0
        # bounded trace of replica assignments in dispatch order (rescue
        # launches included): the drill replays the REAL schedule on its
        # virtual timeline instead of assuming the rotation worked
        self.assignment_log: deque = deque(maxlen=4096)
        scorer.attach_pool(self)

    # ------------------------------------------------------------- capacity
    def __len__(self) -> int:
        return len(self.replicas)

    @property
    def healthy_count(self) -> int:
        return sum(1 for r in self.replicas if r.healthy)

    def total_slots(self) -> int:
        """Max batches in flight across the pool (healthy replicas only) —
        what the stream/serving pipeline depth should rise to so every
        replica actually receives work."""
        return max(1, self.healthy_count * self.inflight_depth)

    # ------------------------------------------------------------- dispatch
    def _pick_replica(self) -> tuple:
        """Round-robin over healthy replicas; blocks (queue wait) while the
        chosen replica is at depth. Strict rotation — not shortest-queue —
        so the assignment sequence is deterministic for the drill.
        Returns ``(replica, inflight_after_assignment)`` — the depth is
        captured under the lock so the tracing plane's dispatch metadata
        is exact, never a racy re-read."""
        with self._cv:
            n = len(self.replicas)
            for off in range(n):
                rep = self.replicas[(self._rr + off) % n]
                if rep.healthy:
                    self._rr = (self._rr + off + 1) % n
                    break
            else:
                raise RuntimeError("device pool has no healthy replicas")
            # rtfd-lint: allow[wall-clock] queue-wait/dispatch diagnostics (host stats), not control flow
            t0 = time.perf_counter()
            while rep.inflight >= self.inflight_depth:
                if not self._cv.wait(timeout=120.0):
                    raise TimeoutError(
                        f"device {rep.idx} stuck at inflight depth "
                        f"{rep.inflight} for 120s")
                if not rep.healthy:     # died while we waited: re-pick
                    return self._pick_replica()
            # rtfd-lint: allow[wall-clock] queue-wait/dispatch diagnostics (host stats), not control flow
            rep.queue_wait_s += time.perf_counter() - t0
            rep.inflight += 1
            rep.dispatched += 1
            self.assignment_log.append(rep.idx)
            return rep, rep.inflight

    def _launch(self, rep: "_Replica", blobs: Dict[str, np.ndarray], spec,
                params, model_valid: np.ndarray):
        import jax

        from realtime_fraud_detection_tpu.scoring.pipeline import (
            score_fused_packed,
            score_fused_packed_donated,
        )

        staged = {k: jax.device_put(v, rep.device)
                  for k, v in blobs.items() if v is not None}
        with self._cv:
            models = rep.models         # snapshot: hot swap never tears it
            # rtfd-lint: allow[d2h] host bool[M] validity mask, never a device array
            mv_dev = rep.mv_dev(np.asarray(model_valid))
        fn = score_fused_packed_donated if self.donate else score_fused_packed
        return fn(models, staged["f32"], staged["i32"], staged["u8"],
                  spec=spec, params=params, model_valid=mv_dev,
                  blob_bf16=staged.get("bf16"),
                  bert_config=self.scorer.bert_config,
                  use_pallas=self.scorer.effective_use_pallas(
                      self.program_devices),
                  # quant + kernel planes: same static kernel selection on
                  # every replica (the scorer's params are already
                  # quantized, so replication/hot-swap carries the int8
                  # form for free, and a kernel-on scorer never mixes
                  # kernel modes within a batch)
                  **self.scorer.quant_static(),
                  **self.scorer.kernel_static())

    def dispatch_packed(self, blobs: Dict[str, np.ndarray], spec, params,
                        model_valid: np.ndarray) -> PoolToken:
        """Stage + launch one packed microbatch on the next replica.

        Returns without blocking on the result; blocks only when the
        chosen replica already has ``inflight_depth`` batches in flight
        (backpressure, recorded as queue wait)."""
        rep, depth = self._pick_replica()
        # rtfd-lint: allow[d2h] host bool[M] validity mask, never a device array
        mv = np.asarray(model_valid)
        host_blobs = {k: v for k, v in blobs.items() if v is not None}
        try:
            out = self._launch(rep, host_blobs, spec, params, mv)
        except Exception:
            # a launch failure is a replica failure too: free the slot,
            # mark it, and let the caller's dispatch path degrade
            self._mark_failed(rep)
            raise
        return PoolToken(out, rep.idx, host_blobs, spec, params, mv,
                         # rtfd-lint: allow[wall-clock] queue-wait/dispatch diagnostics (host stats), not control flow
                         time.perf_counter(), inflight_at_dispatch=depth)

    # ------------------------------------------------------------ completion
    def _mark_failed(self, rep: "_Replica") -> None:
        with self._cv:
            rep.failures += 1
            rep.healthy = False
            rep.inflight = max(0, rep.inflight - 1)
            self._cv.notify_all()

    def _release(self, rep: "_Replica") -> None:
        with self._cv:
            rep.inflight = max(0, rep.inflight - 1)
            rep.completed += 1
            self._cv.notify_all()

    def wait(self, token: PoolToken) -> np.ndarray:
        """Block on a pooled batch's result; on a replica failure, relaunch
        the batch from its host blobs on a healthy replica (per-device
        retry counters feed the metrics plane)."""
        import jax

        attempts = len(self.replicas) + 1
        for _ in range(attempts):
            rep = self.replicas[token.replica_idx]
            self._maybe_slow(rep)
            try:
                if rep.fail_next > 0:
                    rep.fail_next -= 1
                    raise RuntimeError(
                        f"injected device fault on replica {rep.idx}")
                # rtfd-lint: allow[d2h] the designated completion pull (finalize path)
                out = np.asarray(jax.device_get(token.out))
            except Exception:
                self._mark_failed(rep)
                # rescue bypasses depth backpressure: the caller may be the
                # only thread draining the pool, with every healthy replica
                # at full depth — waiting for a slot here would deadlock.
                # A transient depth overshoot on the least-loaded healthy
                # replica is the lesser evil. A rescue replica whose OWN
                # launch fails is marked too (releasing its slot) and the
                # next candidate is tried.
                while True:
                    with self._cv:
                        candidates = [r for r in self.replicas if r.healthy]
                        if not candidates:
                            raise
                        retry_rep = min(candidates,
                                        key=lambda r: r.inflight)
                        retry_rep.inflight += 1
                        retry_rep.dispatched += 1
                        retry_rep.retries += 1
                        self.assignment_log.append(retry_rep.idx)
                    try:
                        token.out = self._launch(
                            retry_rep, token.blobs, token.spec,
                            token.params, token.model_valid)
                    except Exception:
                        self._mark_failed(retry_rep)
                        continue
                    token.replica_idx = retry_rep.idx
                    break
                continue
            self._release(rep)
            return out
        raise RuntimeError("device pool retry budget exhausted")

    # -------------------------------------------------------------- control
    def set_models(self, models) -> None:
        """Fan a model swap out replica-by-replica. Callers hold the score
        lock (the /reload-models recipe); a batch in flight keeps the
        params reference captured at its launch, so the swap never serves
        mixed params within one batch."""
        import jax

        for rep in self.replicas:
            new = jax.device_put(models, rep.device)
            with self._cv:
                rep.models = new

    def inject_fault(self, replica_idx: int, n: int = 1) -> None:
        """Test hook: make the next ``n`` result fetches on a replica
        raise, exercising the retry-on-healthy-replica path without
        needing real device loss."""
        with self._cv:
            self.replicas[replica_idx].fail_next += n

    def inject_slow(self, replica_idx: int, delay_s: float,
                    n: int = 1) -> None:
        """Chaos hook: the next ``n`` result fetches on a replica take an
        extra ``delay_s`` — a DELAYED device, not a dead one. The batch
        still completes on its own replica (no retry, no health change);
        what must hold is FIFO completion across the pool while one
        replica lags (pinned in tests/test_device_pool.py)."""
        with self._cv:
            rep = self.replicas[replica_idx]
            rep.slow_next += max(0, int(n))
            # rtfd-lint: allow[d2h] delay_s is a host scalar argument, not a device value
            rep.slow_s = float(delay_s)

    def _maybe_slow(self, rep: "_Replica") -> None:
        """Apply an injected slow-device delay OUTSIDE the pool lock (a
        stalled fetch must not block dispatch to healthy replicas)."""
        # lock-free fast path: this runs on EVERY pooled result fetch, and
        # slow_next is nonzero only while a chaos harness has armed
        # inject_slow — a stale read at worst delays one injection by a
        # fetch, so production fetches never contend on the pool CV here
        if rep.slow_next <= 0:
            return
        with self._cv:
            if rep.slow_next <= 0:
                return
            rep.slow_next -= 1
            delay = rep.slow_s
        time.sleep(delay)

    def revive(self, replica_idx: int) -> None:
        """Re-admit a failed replica to the rotation (operator action
        after the underlying device recovers). A revived device is a
        HEALTHY device: any still-armed injected faults/delays are
        cleared — a stale arm must not re-kill the replica after its
        fault window closed."""
        with self._cv:
            rep = self.replicas[replica_idx]
            rep.healthy = True
            rep.fail_next = 0
            rep.slow_next = 0
            self._cv.notify_all()

    # ---------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        """Per-device counters for the obs plane
        (obs.metrics.MetricsCollector.sync_device_pool)."""
        with self._cv:
            per_device: List[Dict[str, Any]] = [{
                "device": str(rep.device),
                "index": rep.idx,
                "healthy": rep.healthy,
                "dispatched": rep.dispatched,
                "completed": rep.completed,
                "inflight": rep.inflight,
                "retries": rep.retries,
                "failures": rep.failures,
                "queue_wait_ms": round(rep.queue_wait_s * 1e3, 3),
            } for rep in self.replicas]
        return {
            "devices": per_device,
            "n_devices": len(self.replicas),
            "healthy": sum(1 for d in per_device if d["healthy"]),
            "inflight_depth": self.inflight_depth,
            "dispatched": sum(d["dispatched"] for d in per_device),
            "completed": sum(d["completed"] for d in per_device),
            "retries": sum(d["retries"] for d in per_device),
        }
