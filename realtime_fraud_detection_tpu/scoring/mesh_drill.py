"""Deterministic mesh-sharding drill: the ``rtfd mesh-drill`` acceptance gate.

Runs the REAL mesh-sharded scoring path (FraudScorer + MeshExecutor over
the host platform's virtual devices, scoring/mesh_executor.py) on
deterministic streams and pins the executor's whole contract in one
verdict:

1. **bit-equality per placement** — every branch-placement combo (pure
   data sharding, BERT-only model sharding, all three neural branches
   sharded, pool x mesh with two mesh replicas, and the int8-quantized
   forms of the sharded combos) scores bit-identical to a true
   single-device reference driven with the same in-flight window;
2. **ladder rungs** — a stream that steps the QoS degradation ladder
   mid-flight (every rung, rules-only included) stays bit-identical, so
   the per-dispatch mask snapshot fans out over the mesh exactly like it
   does over the pool;
3. **hot swap** — a mid-stream ``set_models`` re-shards replica-by-replica
   under the same placement: every batch matches EITHER the old-params or
   the new-params reference wholesale, and the swapped params are still
   sharded (per-chip bytes keep the ratio);
4. **memory** — per-chip resident BERT-branch bytes on the 2-way model
   axis are <= ``max_bert_per_chip_frac`` (60%) of the replicated
   equivalent, read from the COMMITTED array shardings, f32 and int8 both;
5. **donation** — the donated entry carries every staged blob's donation
   annotation into the compiled program (the plain entry carries none)
   and a donated run scores identically, so accelerator deployments
   recycle H2D staging instead of holding depth x blobs per replica
   (CPU PJRT drops non-aliasable donations at RUN time, so the lowering
   is the truthful cross-backend evidence);
6. **replay** — a second full pass replays bit-identically (sha256 digest
   over every scored row of every phase).

Wall-clock scaling is deliberately NOT gated here: 8 virtual CPU devices
timeslice one core budget (the pool-drill precedent), and model-sharding
is an HBM bet that can LOSE on CPU — throughput on a mesh of chips is not
measured (the benchmark has no four-chip cell). Convention matches the other seven
drills: full summary JSON, then a compact (<2 KB) verdict as the final
stdout line (cli.cmd_mesh_drill).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["MeshDrillConfig", "run_mesh_drill", "compact_mesh_summary"]


@dataclasses.dataclass
class MeshDrillConfig:
    n_devices: int = 8
    model_axis: int = 2
    inflight_depth: int = 2
    batch: int = 32
    n_batches: int = 12          # per placement combo
    swap_batches: int = 12       # hot-swap phase (swap at the midpoint)
    rung_batches: int = 2        # batches scored AT each ladder rung
    seed: int = 7
    # the memory acceptance bar: per-chip resident BERT bytes vs the
    # replicated equivalent at model_axis=2 (sharding halves the dense
    # kernels and embeddings; layer norms + head stay whole, hence 0.6
    # rather than 0.5)
    max_bert_per_chip_frac: float = 0.60
    replay_check: bool = True

    @classmethod
    def fast(cls) -> "MeshDrillConfig":
        """Tier-1 smoke sizes: every phase runs, compiles stay small."""
        return cls(batch=16, n_batches=6, swap_batches=8)


ALL_NEURAL = ("bert_text", "graph_neural", "lstm_sequential")


def _make_scorer(cfg: MeshDrillConfig, model_seed: int = 0,
                 quant: bool = False):
    """Fresh generator + scorer pair. The scorer's OWN mesh is pinned to
    one device so the reference runs are genuinely single-device; an
    attached MeshExecutor overrides the batch seam with its data axis."""
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import (
        FraudScorer,
        ScorerConfig,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    config = None
    if quant:
        from realtime_fraud_detection_tpu.utils.config import (
            Config,
            QuantSettings,
        )

        config = Config(quant=QuantSettings.full())
    gen = TransactionGenerator(num_users=500, num_merchants=100,
                               seed=cfg.seed)
    scorer = FraudScorer(config=config, scorer_config=ScorerConfig(),
                         mesh=build_mesh(devices=jax.devices()[:1]),
                         seed=model_seed)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return gen, scorer


def _run_stream(scorer, batches: List[list], window: int,
                now: float = 1000.0,
                swap_at: Optional[int] = None, swap_models=None,
                rung_schedule: Optional[Dict[int, int]] = None,
                ) -> List[List[Dict[str, Any]]]:
    """Dispatch/finalize with at most ``window`` in flight — the SAME
    routine drives the meshed scorer and the single-device reference, so
    both see identical host-state interleaving (the pool-drill fairness
    argument). ``rung_schedule`` maps batch index -> ladder level to
    apply right before that dispatch (mask fan-out mid-stream)."""
    from collections import deque

    from realtime_fraud_detection_tpu.qos.ladder import LADDER_LEVELS
    from realtime_fraud_detection_tpu.scoring import MODEL_NAMES

    results: List[List[Dict[str, Any]]] = []
    inflight: deque = deque()
    for i, recs in enumerate(batches):
        if swap_at is not None and i == swap_at:
            # rtfd-lint: allow[lock-order] the drill IS the only dispatcher; swap purity is what it pins
            scorer.set_models(swap_models)
        if rung_schedule is not None and i in rung_schedule:
            level = rung_schedule[i]
            rung = LADDER_LEVELS[level]
            # rtfd-lint: allow[d2h] host bool list -> validity mask, never a device array
            mask = np.asarray(
                [n not in rung.dropped_branches for n in MODEL_NAMES])
            # rtfd-lint: allow[lock-order] the drill IS the only dispatcher; rung fan-out is what it pins
            scorer.set_degradation(mask, rules_only=rung.rules_only,
                                   level=level)
        inflight.append(scorer.dispatch(recs, now=now))
        while len(inflight) >= window:
            results.append(scorer.finalize(inflight.popleft(), now=now))
    while inflight:
        results.append(scorer.finalize(inflight.popleft(), now=now))
    return results


def _rows(results: List[List[Dict[str, Any]]]) -> List[tuple]:
    return [(r["transaction_id"], r["fraud_probability"], r["confidence"],
             r["decision"]) for batch in results for r in batch]


def _bert_frac(executor) -> float:
    pb = executor.param_bytes()["bert_text"]
    return pb["per_chip"] / max(pb["replicated"], 1)


def _one_pass(cfg: MeshDrillConfig) -> Tuple[Dict[str, Any], str]:
    """One full drill pass; returns (summary, digest-over-every-row)."""
    import jax

    from realtime_fraud_detection_tpu.qos.ladder import LADDER_LEVELS
    from realtime_fraud_detection_tpu.scoring import MeshExecutor
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    devices = jax.devices()
    if len(devices) < cfg.n_devices:
        raise RuntimeError(
            f"mesh drill needs {cfg.n_devices} devices, found "
            f"{len(devices)} — run via `rtfd mesh-drill` (it re-execs on a "
            f"virtual {cfg.n_devices}-device host platform) or set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{cfg.n_devices}")
    devices = devices[:cfg.n_devices]
    window = cfg.inflight_depth       # identical for ref and every combo

    summary: Dict[str, Any] = {
        "drill": "mesh",
        "n_devices": cfg.n_devices,
        "model_axis": cfg.model_axis,
        "inflight_depth": cfg.inflight_depth,
        "batch": cfg.batch,
        "platform": devices[0].platform,
        "checks": {},
        "placements": {},
    }
    checks = summary["checks"]
    digest = hashlib.sha256()

    def fold(rows: List[tuple]) -> None:
        digest.update(json.dumps(rows, sort_keys=True).encode())

    # ------------------------------------------- phase 1: placement combos
    # (name, quantized, executor kwargs) — every combo re-scores the SAME
    # deterministic stream against a fresh single-device reference
    combos: List[Tuple[str, bool, Dict[str, Any]]] = [
        ("data_only", False,
         dict(model_axis=cfg.model_axis, replicas=1, shard_branches=())),
        ("bert_sharded", False,
         dict(model_axis=cfg.model_axis, replicas=1,
              shard_branches=("bert_text",))),
        ("all_neural_sharded", False,
         dict(model_axis=cfg.model_axis, replicas=1,
              shard_branches=ALL_NEURAL)),
        ("pool_x_mesh", False,
         dict(model_axis=cfg.model_axis, replicas=2,
              shard_branches=("bert_text",))),
        ("quant_bert_sharded", True,
         dict(model_axis=cfg.model_axis, replicas=1,
              shard_branches=("bert_text",))),
        ("quant_all_neural_sharded", True,
         dict(model_axis=cfg.model_axis, replicas=1,
              shard_branches=ALL_NEURAL)),
    ]
    ref_rows: Dict[bool, List[tuple]] = {}
    for quant in (False, True):
        gen, ref = _make_scorer(cfg, quant=quant)
        batches = [gen.generate_batch(cfg.batch)
                   for _ in range(cfg.n_batches)]
        ref_rows[quant] = _rows(_run_stream(ref, batches, window))
        fold(ref_rows[quant])

    for name, quant, kwargs in combos:
        gen, scorer = _make_scorer(cfg, quant=quant)
        executor = MeshExecutor(scorer, devices=devices,
                                inflight_depth=cfg.inflight_depth,
                                **kwargs)
        batches = [gen.generate_batch(cfg.batch)
                   for _ in range(cfg.n_batches)]
        got = _rows(_run_stream(scorer, batches, window))
        fold(got)
        bit = got == ref_rows[quant]
        checks[f"bit_identical_{name}"] = bit
        submitted = [str(r.get("transaction_id", "")) for b in batches
                     for r in b]
        checks[f"fifo_{name}"] = [t for t, *_ in got] == submitted
        entry: Dict[str, Any] = {
            "quantized": quant,
            "shard_branches": list(kwargs["shard_branches"]),
            "replicas": kwargs["replicas"],
            "bert_per_chip_frac": round(_bert_frac(executor), 4),
        }
        if kwargs["shard_branches"]:
            checks[f"bert_bytes_{name}"] = (
                entry["bert_per_chip_frac"] <= cfg.max_bert_per_chip_frac)
        if kwargs["replicas"] > 1:
            st = executor.stats()
            entry["per_replica_dispatched"] = [
                r["dispatched"] for r in st["replicas"]]
            checks["all_mesh_replicas_utilized"] = all(
                r["dispatched"] > 0 for r in st["replicas"])
            checks["round_robin_assignment"] = (
                list(executor.assignment_log)
                == [i % kwargs["replicas"]
                    for i in range(cfg.n_batches)])
        summary["placements"][name] = entry

    # --------------------------------------------- phase 2: ladder rungs
    # one stream stepping DOWN through every rung mid-flight (rules-only
    # included), mirrored on the reference — pins the per-dispatch mask
    # snapshot across the mesh, not just a statically-degraded program
    n_rungs = len(LADDER_LEVELS)
    rung_schedule = {i * cfg.rung_batches: i for i in range(n_rungs)}
    n_rung_batches = n_rungs * cfg.rung_batches

    gen_r, rung_ref = _make_scorer(cfg)
    ref_r = _rows(_run_stream(
        rung_ref, [gen_r.generate_batch(cfg.batch)
                   for _ in range(n_rung_batches)],
        window, rung_schedule=rung_schedule))
    gen_m, rung_scorer = _make_scorer(cfg)
    MeshExecutor(rung_scorer, devices=devices,
                 model_axis=cfg.model_axis,
                 inflight_depth=cfg.inflight_depth,
                 shard_branches=ALL_NEURAL)
    got_r = _rows(_run_stream(
        rung_scorer, [gen_m.generate_batch(cfg.batch)
                      for _ in range(n_rung_batches)],
        window, rung_schedule=rung_schedule))
    fold(got_r)
    checks["bit_identical_all_ladder_rungs"] = got_r == ref_r
    summary["ladder"] = {"rungs": n_rungs,
                         "batches_per_rung": cfg.rung_batches}

    # ------------------------------------------------ phase 3: hot swap
    new_models = init_scoring_models(
        jax.random.PRNGKey(101), bert_config=rung_scorer.bert_config,
        feature_dim=rung_scorer.sc.feature_dim,
        node_dim=rung_scorer.sc.node_dim)
    swap_at = cfg.swap_batches // 2

    gen_old, serial_old = _make_scorer(cfg)
    swap_old_ref = _run_stream(
        serial_old, [gen_old.generate_batch(cfg.batch)
                     for _ in range(cfg.swap_batches)], window)
    gen_new, serial_new = _make_scorer(cfg)
    # rtfd-lint: allow[lock-order] serial oracle scorer, single-threaded by construction
    serial_new.set_models(new_models)
    swap_new_ref = _run_stream(
        serial_new, [gen_new.generate_batch(cfg.batch)
                     for _ in range(cfg.swap_batches)], window)

    gen_sw, swap_scorer = _make_scorer(cfg)
    swap_exec = MeshExecutor(swap_scorer, devices=devices,
                             model_axis=cfg.model_axis,
                             inflight_depth=cfg.inflight_depth,
                             shard_branches=("bert_text",))
    swap_got = _run_stream(
        swap_scorer, [gen_sw.generate_batch(cfg.batch)
                      for _ in range(cfg.swap_batches)],
        window, swap_at=swap_at, swap_models=new_models)
    fold(_rows(swap_got))

    mixed = matches_old = matches_new = 0
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
    # the swap must PRESERVE the placement: freshly swapped params are
    # still sharded, not silently replicated
    checks["swap_preserves_sharding"] = (
        _bert_frac(swap_exec) <= cfg.max_bert_per_chip_frac)
    summary["hot_swap"] = {
        "swap_at_batch": swap_at,
        "batches_on_old_params": matches_old,
        "batches_on_new_params": matches_new,
        "mixed_batches": mixed,
        "post_swap_bert_per_chip_frac": round(_bert_frac(swap_exec), 4),
    }

    # ------------------------------------------------ phase 4: donation
    # the donated entry must carry the blob-donation annotations into the
    # compiled program (tf.aliasing_output / jax.buffer_donor in the
    # lowering) and the plain entry must not. This is the truthful
    # evidence on every backend: the fused program's one output matches
    # no input shape, so CPU PJRT (strict aliasing only) drops the
    # donation at RUN time — an is_deleted check here would test the CPU
    # runtime, not our wiring — while TPU reuses the donated staging
    # space for temporaries, which is the batch-256 h2d lever the pool
    # plane measured. A donated run must also still score correctly.
    import warnings

    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.scoring import make_example_batch

    gen_d, don_scorer = _make_scorer(cfg)
    don_exec = MeshExecutor(don_scorer, devices=devices,
                            model_axis=cfg.model_axis,
                            inflight_depth=cfg.inflight_depth,
                            shard_branches=("bert_text",), donate=True)
    with warnings.catch_warnings():
        # CPU PJRT warns when a non-aliasable donation is dropped
        warnings.simplefilter("ignore")
        don_rows = _rows(_run_stream(
            don_scorer, [gen_d.generate_batch(cfg.batch)
                         for _ in range(2)], window))
    gen_p, plain_scorer = _make_scorer(cfg)
    MeshExecutor(plain_scorer, devices=devices,
                 model_axis=cfg.model_axis,
                 inflight_depth=cfg.inflight_depth,
                 shard_branches=("bert_text",), donate=False)
    plain_rows = _rows(_run_stream(
        plain_scorer, [gen_p.generate_batch(cfg.batch)
                       for _ in range(2)], window))
    checks["donated_scores_identical"] = don_rows == plain_rows

    ex_batch = make_example_batch(
        max(cfg.batch, don_exec.batch_multiple), don_scorer.sc,
        rng=np.random.default_rng(cfg.seed))
    blobs, pspec = pack_tree(ex_batch)
    mv = don_scorer.effective_model_valid()

    def _donor_args(text: str) -> int:
        return (text.count("jax.buffer_donor")
                + text.count("tf.aliasing_output"))

    donated_n = _donor_args(don_exec.donation_lowering(
        blobs, pspec, don_scorer.ensemble_params, mv, donate=True))
    plain_n = _donor_args(don_exec.donation_lowering(
        blobs, pspec, don_scorer.ensemble_params, mv, donate=False))
    # only non-empty blobs count: the default transfer layout ships a
    # zero-width bf16 blob, and XLA drops the donor annotation on a
    # 0-byte buffer
    n_blobs = sum(1 for v in blobs.values()
                  if v is not None and np.size(v) > 0)
    checks["donation_reaches_compiler"] = (
        donated_n >= n_blobs and plain_n == 0)
    summary["donation"] = {"donor_args": donated_n,
                           "staged_blobs": n_blobs,
                           "plain_donor_args": plain_n}

    checks = {k: bool(v) for k, v in checks.items()}
    summary["checks"] = checks
    summary["passed"] = all(checks.values())
    return summary, digest.hexdigest()


def run_mesh_drill(cfg: Optional[MeshDrillConfig] = None) -> Dict[str, Any]:
    cfg = cfg or MeshDrillConfig()
    summary, digest = _one_pass(cfg)
    summary["digest"] = digest
    if cfg.replay_check:
        # a second full pass from fresh scorers/streams must replay every
        # scored row bit-identically (the house determinism gate)
        _, digest2 = _one_pass(cfg)
        summary["checks"]["replay_bit_identical"] = digest == digest2
        summary["passed"] = all(
            bool(v) for v in summary["checks"].values())
    return summary


def compact_mesh_summary(summary: Dict[str, Any]) -> Dict[str, Any]:
    """<2 KB single-line verdict (the final stdout line)."""
    placements = summary.get("placements") or {}
    return {
        "drill": "mesh",
        "passed": summary.get("passed", False),
        "checks": {k: bool(v)
                   for k, v in (summary.get("checks") or {}).items()},
        "n_devices": summary.get("n_devices"),
        "model_axis": summary.get("model_axis"),
        "bert_per_chip_frac": {
            name: p.get("bert_per_chip_frac")
            for name, p in placements.items() if p.get("shard_branches")},
        "digest": (summary.get("digest") or "")[:16],
    }
