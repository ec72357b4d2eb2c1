"""Deterministic device-pool drill: the ``rtfd pool-drill`` acceptance gate.

Runs the REAL pooled scoring path (FraudScorer + DevicePool over the
host-platform's virtual devices) on a deterministic stream and pins the
pool's whole contract in one verdict:

1. **bit-equality** — pooled scores are bit-identical to single-device
   scoring of the same stream under the same dispatch/finalize
   interleaving (same window W of in-flight batches, so host state
   evolves identically);
2. **FIFO** — results come back in submit order, per batch and across
   batches;
3. **utilization** — every replica received work, zero retries;
4. **hot-swap** — a mid-stream ``set_models`` swap is replica-by-replica:
   every batch matches EITHER the old-params reference or the new-params
   reference wholesale — no batch ever serves mixed params;
5. **scaling** — the pool's actual dispatch schedule, replayed on a
   deterministic virtual timeline (nominal v5e-shaped per-batch costs:
   host work ``host_ms``, device compute ``device_ms``, true device
   parallelism), sustains >= 3x the 1-device aggregate throughput.

Why the scaling gate is virtual-time: the drill must be deterministic,
and CI hosts running 8 *virtual* CPU devices share one physical core
budget — XLA's host platform timeslices one intra-op pool, so wall-clock
"scaling" there measures the CI box, not the scheduler. The virtual
replay uses the pool's REAL assignment sequence and in-flight constraint
(a broken round-robin or a depth leak collapses it) with device
parallelism as the hardware would provide it; scaling on four chips is
not measured (no admitted cell of the benchmark runs the pool).
Wall-clock numbers are reported alongside, ungated.

Convention matches qos/feedback drills: virtual event clock for state
TTLs, full summary JSON then a compact (<2 KB) verdict as the final
stdout line (cli.cmd_pool_drill).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

__all__ = ["PoolDrillConfig", "run_pool_drill", "compact_pool_summary"]


@dataclasses.dataclass
class PoolDrillConfig:
    n_devices: int = 8
    inflight_depth: int = 2
    batch: int = 64
    n_batches: int = 24          # equality/utilization phase
    swap_batches: int = 16       # hot-swap phase (swap at the midpoint)
    seed: int = 7
    # nominal per-batch costs for the virtual-time schedule replay:
    # ~5 ms host assemble+pack+dispatch (PR-2 columnar at batch 256) and
    # ~25 ms device compute (nominal; the schedule replay needs a ratio,
    # not a measurement)
    host_ms: float = 5.0
    device_ms: float = 25.0
    min_scaling: float = 3.0

    @classmethod
    def fast(cls) -> "PoolDrillConfig":
        """Tier-1 smoke sizes: every phase runs, compiles stay small."""
        return cls(batch=16, n_batches=10, swap_batches=8)


def _make_scorer(cfg: PoolDrillConfig, model_seed: int = 0):
    from realtime_fraud_detection_tpu.scoring import (
        FraudScorer,
        ScorerConfig,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    gen = TransactionGenerator(num_users=500, num_merchants=100,
                               seed=cfg.seed)
    scorer = FraudScorer(scorer_config=ScorerConfig(), seed=model_seed)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return gen, scorer


def _run_stream(scorer, batches: List[list], window: int,
                now: float = 1000.0,
                swap_at: Optional[int] = None, swap_models=None,
                ) -> List[List[Dict[str, Any]]]:
    """Dispatch/finalize ``batches`` with at most ``window`` in flight.

    The SAME routine drives the pooled scorer and the single-device
    reference, so both see identical host-state interleaving (batch N+1
    may assemble before batch N's write-back — identically on both
    sides); that is what makes bit-equality a fair assertion.
    ``swap_at``: call set_models(swap_models) right before dispatching
    that batch index (the hot-swap phase).
    """
    results: List[List[Dict[str, Any]]] = []
    inflight: deque = deque()
    for i, recs in enumerate(batches):
        if swap_at is not None and i == swap_at:
            # rtfd-lint: allow[lock-order] the drill IS the only dispatcher; swap purity is what it pins
            scorer.set_models(swap_models)
        inflight.append(scorer.dispatch(recs, now=now))
        while len(inflight) >= window:
            results.append(scorer.finalize(inflight.popleft(), now=now))
    while inflight:
        results.append(scorer.finalize(inflight.popleft(), now=now))
    return results


def _rows(results: List[List[Dict[str, Any]]]) -> List[tuple]:
    return [(r["transaction_id"], r["fraud_probability"], r["confidence"],
             r["decision"]) for batch in results for r in batch]


def _virtual_makespan_ms(assignments: List[int], n_devices: int,
                         depth: int, host_ms: float,
                         device_ms: float) -> float:
    """Replay a dispatch-assignment sequence on a deterministic timeline:
    one serial host producing a batch every ``host_ms``, each device
    computing for ``device_ms``, at most ``depth`` batches in flight per
    device (the host blocks on the oldest — exactly DevicePool's
    backpressure)."""
    host_t = 0.0
    free = [0.0] * n_devices
    inflight = [deque() for _ in range(n_devices)]
    last_done = 0.0
    for r in assignments:
        while len(inflight[r]) >= depth:
            host_t = max(host_t, inflight[r].popleft())
        host_t += host_ms
        end = max(host_t, free[r]) + device_ms
        free[r] = end
        inflight[r].append(end)
        last_done = max(last_done, end)
    return last_done


def run_pool_drill(cfg: Optional[PoolDrillConfig] = None) -> Dict[str, Any]:
    import jax

    from realtime_fraud_detection_tpu.scoring import DevicePool
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    cfg = cfg or PoolDrillConfig()
    devices = jax.devices()
    if len(devices) < cfg.n_devices:
        raise RuntimeError(
            f"pool drill needs {cfg.n_devices} devices, found "
            f"{len(devices)} — run via `rtfd pool-drill` (it re-execs on a "
            f"virtual {cfg.n_devices}-device host platform) or set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{cfg.n_devices}")
    devices = devices[:cfg.n_devices]

    summary: Dict[str, Any] = {
        "drill": "device_pool",
        "n_devices": cfg.n_devices,
        "inflight_depth": cfg.inflight_depth,
        "batch": cfg.batch,
        "platform": devices[0].platform,
        "checks": {},
    }
    checks = summary["checks"]

    # Warm the per-device executables with a THROWAWAY scorer (same bucket
    # shape -> same jit cache) so phase wall-clocks measure scoring, not
    # 8x XLA compile; the throwaway's state mutations never touch the
    # drill scorers, keeping bit-equality fair.
    gen_w, warm_scorer = _make_scorer(cfg)
    warm_pool = DevicePool(warm_scorer, devices=devices,
                           inflight_depth=cfg.inflight_depth)
    warm_pend = [warm_scorer.dispatch(gen_w.generate_batch(cfg.batch),
                                      now=1000.0)
                 for _ in range(cfg.n_devices)]
    for p in warm_pend:
        warm_scorer.finalize(p, now=1000.0)

    # ---------------------------------------------------- phase 1: equality
    gen_a, serial = _make_scorer(cfg)
    batches = [gen_a.generate_batch(cfg.batch) for _ in range(cfg.n_batches)]

    gen_b, pooled_scorer = _make_scorer(cfg)
    pool = DevicePool(pooled_scorer, devices=devices,
                      inflight_depth=cfg.inflight_depth)
    window = min(cfg.n_batches, pool.total_slots())
    batches_b = [gen_b.generate_batch(cfg.batch)
                 for _ in range(cfg.n_batches)]

    # rtfd-lint: allow[wall-clock] wall time reported ungated (virtual CPU devices share one core)
    t0 = time.perf_counter()
    ref = _run_stream(serial, batches, window)
    # rtfd-lint: allow[wall-clock] wall time reported ungated (virtual CPU devices share one core)
    wall_serial = time.perf_counter() - t0
    # rtfd-lint: allow[wall-clock] wall time reported ungated (virtual CPU devices share one core)
    t0 = time.perf_counter()
    got = _run_stream(pooled_scorer, batches_b, window)
    # rtfd-lint: allow[wall-clock] wall time reported ungated (virtual CPU devices share one core)
    wall_pooled = time.perf_counter() - t0

    checks["bit_identical"] = _rows(ref) == _rows(got)
    submitted = [str(r.get("transaction_id", "")) for b in batches_b
                 for r in b]
    returned = [tid for tid, *_ in _rows(got)]
    checks["fifo_order"] = returned == submitted

    stats = pool.stats()
    per_dev = [d["dispatched"] for d in stats["devices"]]
    checks["all_devices_utilized"] = all(n > 0 for n in per_dev)
    checks["zero_retries"] = stats["retries"] == 0
    summary["per_device_dispatched"] = per_dev
    summary["wall_clock"] = {
        "serial_s": round(wall_serial, 3),
        "pooled_s": round(wall_pooled, 3),
        "note": "informational only — virtual CPU devices timeslice one "
                "physical core budget; the gated scaling number is the "
                "virtual-time replay below",
    }

    # ---------------------------------------------------- phase 2: hot swap
    new_models = init_scoring_models(
        jax.random.PRNGKey(101), bert_config=pooled_scorer.bert_config,
        feature_dim=pooled_scorer.sc.feature_dim,
        node_dim=pooled_scorer.sc.node_dim)
    swap_at = cfg.swap_batches // 2

    gen_old, serial_old = _make_scorer(cfg)
    swap_old_ref = _run_stream(
        serial_old, [gen_old.generate_batch(cfg.batch)
                     for _ in range(cfg.swap_batches)], window)
    gen_new, serial_new = _make_scorer(cfg, model_seed=0)
    # rtfd-lint: allow[lock-order] serial oracle scorer, single-threaded by construction
    serial_new.set_models(new_models)
    swap_new_ref = _run_stream(
        serial_new, [gen_new.generate_batch(cfg.batch)
                     for _ in range(cfg.swap_batches)], window)

    gen_sw, swap_scorer = _make_scorer(cfg)
    swap_pool = DevicePool(swap_scorer, devices=devices,
                           inflight_depth=cfg.inflight_depth)
    swap_got = _run_stream(
        swap_scorer, [gen_sw.generate_batch(cfg.batch)
                      for _ in range(cfg.swap_batches)],
        min(cfg.swap_batches, swap_pool.total_slots()),
        swap_at=swap_at, swap_models=new_models)

    mixed = 0
    matches_old = matches_new = 0
    for i, batch_res in enumerate(swap_got):
        rows = _rows([batch_res])
        if rows == _rows([swap_old_ref[i]]):
            matches_old += 1
        elif rows == _rows([swap_new_ref[i]]):
            matches_new += 1
        else:
            mixed += 1
    checks["no_mixed_params_batch"] = (
        mixed == 0 and matches_old > 0 and matches_new > 0)
    summary["hot_swap"] = {
        "swap_at_batch": swap_at,
        "batches_on_old_params": matches_old,
        "batches_on_new_params": matches_new,
        "mixed_batches": mixed,
    }

    # --------------------------------------- phase 3: virtual-time scaling
    # the REAL assignment sequence the pool produced in phase 1, in
    # dispatch order (DevicePool.assignment_log) — a broken rotation
    # shows up both here and in the strict round-robin check below
    assignments = list(pool.assignment_log)
    checks["round_robin_assignment"] = (
        assignments == [i % cfg.n_devices for i in range(cfg.n_batches)])

    pooled_ms = _virtual_makespan_ms(
        assignments, cfg.n_devices, cfg.inflight_depth,
        cfg.host_ms, cfg.device_ms)
    single_ms = _virtual_makespan_ms(
        [0] * cfg.n_batches, 1, cfg.inflight_depth,
        cfg.host_ms, cfg.device_ms)
    scaling = single_ms / max(pooled_ms, 1e-9)
    txn = cfg.n_batches * cfg.batch
    summary["virtual_time"] = {
        "model": {"host_ms_per_batch": cfg.host_ms,
                  "device_ms_per_batch": cfg.device_ms},
        "single_device_makespan_ms": round(single_ms, 3),
        "pooled_makespan_ms": round(pooled_ms, 3),
        "single_device_txn_per_s": round(txn / (single_ms / 1e3), 1),
        "pooled_txn_per_s": round(txn / (pooled_ms / 1e3), 1),
        "scaling": round(scaling, 3),
        "min_scaling": cfg.min_scaling,
    }
    checks["scaling_ge_min"] = scaling >= cfg.min_scaling

    summary["passed"] = all(bool(v) for v in checks.values())
    return summary


def compact_pool_summary(summary: Dict[str, Any]) -> Dict[str, Any]:
    """<2 KB single-line verdict (the final stdout line)."""
    vt = summary.get("virtual_time") or {}
    return {
        "drill": "device_pool",
        "passed": summary.get("passed", False),
        "checks": {k: bool(v)
                   for k, v in (summary.get("checks") or {}).items()},
        "n_devices": summary.get("n_devices"),
        "inflight_depth": summary.get("inflight_depth"),
        "scaling": vt.get("scaling"),
        "pooled_txn_per_s": vt.get("pooled_txn_per_s"),
        "per_device_dispatched": summary.get("per_device_dispatched"),
    }
