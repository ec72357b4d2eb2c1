"""Deterministic quantization drill: the ``rtfd quant-drill`` score-delta
oracle that makes the quantized scoring plane shippable.

Per the reduced-precision serving result (arXiv:2109.09541), int8 weights
and reshaped tree kernels are free throughput ONLY while quality is gated,
not assumed. This drill is that gate, run the way the other five drills
run (virtual clock, seeded, compact <2 KB JSON verdict as the final
stdout line):

1. **Score-delta oracle.** One seeded transaction stream through TWO real
   scorers — the committed f32 fused program and the fully quantized one
   (weight-only int8 BERT + GEMM-form GBDT/iforest kernels,
   ``QuantSettings.full()``) — driven identically (same generator seed,
   same virtual clock, same state write-back interleaving). The max
   absolute fraud-score divergence must sit BELOW the calibration-noise
   floor: the score movement the committed bf16-compute policy
   (core/precision.py) already accepts, measured in-drill by running the
   SAME f32 weights at bf16 vs f32 compute and scaling the BERT branch
   delta by its blend weight. Quantization may not cost more precision
   than the precision budget production already spends.
2. **Zero decision flips.** At the pinned operating point (the decision
   ladder the reference serves, §2.7), every transaction must take the
   SAME decision under both programs — divergence that crosses an
   operating threshold is a quality regression no throughput buys back.
3. **Quality-protocol AUC.** Trees + isolation forest are trained on a
   stream segment through the PRODUCTION assemble path (the
   blend_eval/feedback-drill recipe, drill-sized) and a held-out labeled
   segment is scored by both programs: |AUC(f32) - AUC(quant)| must be
   ~0 (below the protocol's resolution).
4. **GEMM-vs-gather oracle.** On both the trained and a randomized
   ensemble, the contraction-form tree path must select EXACTLY the same
   leaves as the gather oracle (models/trees.py keeps the split
   convention identical by construction) with logits inside float
   tolerance (summation-order slack only).
5. **Bytes.** The quantized BERT branch must serialize >= ``3.5x``
   smaller than f32 — the HBM headroom the mesh item buys with this PR.
6. **Replay.** A second full run must be bit-identical (sha256 over every
   score, decision, AUC and divergence stat).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["QuantDrillConfig", "run_quant_drill", "compact_quant_summary"]


@dataclasses.dataclass
class QuantDrillConfig:
    seed: int = 11
    num_users: int = 800
    num_merchants: int = 160
    batch: int = 128
    n_train: int = 4_096        # trees/iforest training segment (protocol)
    n_batches: int = 16         # divergence / decision-flip stream
    eval_batches: int = 20      # held-out labeled AUC segment
    n_trees: int = 48
    tree_depth: int = 6
    tps: float = 200.0          # virtual arrival rate (clock advance)
    # gates
    noise_scale: float = 1.0    # quant divergence <= scale * bf16 noise floor
    noise_floor_abs: float = 1e-4   # resolution floor for the noise bound
    max_auc_delta: float = 2e-3
    min_bytes_ratio: float = 3.5
    leaf_logit_tol: float = 1e-4    # documented GEMM summation-order slack
    replay: bool = True

    @classmethod
    def fast(cls) -> "QuantDrillConfig":
        """Tier-1 smoke sizes: every phase runs, compiles stay small."""
        return cls(num_users=400, num_merchants=80, batch=64,
                   n_train=1_536, n_batches=8, eval_batches=10, n_trees=24)


def _make_side(cfg: QuantDrillConfig, quantized: bool):
    """One drill side: seeded generator + scorer (f32 or fully quantized),
    with trees/iforest trained on its own identical stream segment through
    the production assemble path (deterministic, so both sides deploy the
    SAME f32 trees; only the BERT weight form and tree kernels differ)."""
    from realtime_fraud_detection_tpu.models.isolation_forest import (
        IsolationForestTrainer,
    )
    from realtime_fraud_detection_tpu.scoring import (
        FraudScorer,
        ScorerConfig,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.training import GBDTTrainer
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        QuantSettings,
    )

    quant = QuantSettings.full() if quantized else QuantSettings()
    gen = TransactionGenerator(num_users=cfg.num_users,
                               num_merchants=cfg.num_merchants,
                               seed=cfg.seed)
    scorer = FraudScorer(Config(quant=quant), scorer_config=ScorerConfig(),
                         seed=cfg.seed)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())

    xs, ys = [], []
    done, ts = 0, 0.0
    while done < cfg.n_train:
        n = min(cfg.batch, cfg.n_train - done)
        recs = gen.generate_batch(n)
        batch = scorer.assemble(recs, now=ts)
        xs.append(np.asarray(batch.features))
        ys.append(np.asarray([bool(r.get("is_fraud")) for r in recs],
                             np.float32))
        for r in recs:   # serving's write-back: later segments see state
            scorer.velocity.update(str(r.get("user_id", "")),
                                   float(r.get("amount", 0.0)), ts)
        done += n
        ts += n / cfg.tps
    x, y = np.concatenate(xs), np.concatenate(ys)
    trees = GBDTTrainer(n_estimators=cfg.n_trees, max_depth=cfg.tree_depth,
                        seed=cfg.seed).fit(x, y)
    iforest = IsolationForestTrainer(n_estimators=cfg.n_trees,
                                     seed=cfg.seed + 1).fit(
        x[y < 0.5][:4000])
    # rtfd-lint: allow[lock-order] drill is single-threaded (no batch in flight during the swap)
    scorer.set_models(scorer.models.replace(trees=trees, iforest=iforest))
    return gen, scorer, ts


def _score_stream(cfg: QuantDrillConfig, gen, scorer, ts: float,
                  n_batches: int, keep_tokens: int = 0,
                  ) -> Tuple[Dict[str, Any], float]:
    """Drive ``n_batches`` through the scorer on the virtual clock;
    returns host-side probs/decisions/labels (+ the first ``keep_tokens``
    token batches for the noise-floor measurement)."""
    probs: List[float] = []
    decisions: List[str] = []
    labels: List[float] = []
    tokens: List[Tuple[np.ndarray, np.ndarray]] = []
    for i in range(n_batches):
        recs = gen.generate_batch(cfg.batch)
        batch = scorer.assemble(recs, now=ts)
        if i < keep_tokens:
            tokens.append((np.asarray(batch.token_ids),
                           np.asarray(batch.token_mask)))
        results = scorer.finalize(
            scorer.dispatch_assembled(batch, recs), now=ts)
        probs.extend(r["fraud_probability"] for r in results)
        decisions.extend(r["decision"] for r in results)
        labels.extend(float(bool(r.get("is_fraud"))) for r in recs)
        ts += cfg.batch / cfg.tps
    return {
        "probs": np.asarray(probs, np.float64),
        "decisions": decisions,
        "labels": np.asarray(labels, np.float32),
        "tokens": tokens,
    }, ts


def _noise_floor(cfg: QuantDrillConfig, scorer,
                 tokens) -> Dict[str, float]:
    """The calibration-noise bound: how far the committed bf16 compute
    policy already moves the ensemble score vs full f32 compute, measured
    on this drill's own token stream with the f32 weights. Quantization
    must fit inside that accepted budget (scaled by ``noise_scale``)."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.bert import bert_predict

    attn = dict(use_pallas=scorer.effective_use_pallas(),
                kernel_interpret=scorer.kernel_static()["kernel_interpret"])
    bf16 = jax.jit(lambda p, i, m: bert_predict(
        p, i, m, scorer.bert_config, **attn))
    f32 = jax.jit(lambda p, i, m: bert_predict(
        p, i, m, scorer.bert_config, compute_dtype=jnp.float32, **attn))
    branch_delta = 0.0
    for ids, mask in tokens:
        a = bf16(scorer.models.bert, ids, mask)
        b = f32(scorer.models.bert, ids, mask)
        branch_delta = max(branch_delta,
                           float(jnp.max(jnp.abs(a - b))))
    # the branch feeds the blend through its normalized weight — that is
    # the score-level movement the committed policy accepts
    weights = np.asarray(scorer.ensemble_params.weights, np.float64)
    valid = np.asarray(scorer.effective_model_valid(), bool)
    w = weights * valid
    w_bert = float(w[2] / max(w.sum(), 1e-9))      # MODEL_NAMES order
    bound = max(branch_delta * w_bert, cfg.noise_floor_abs)
    return {"bert_branch_bf16_delta": branch_delta,
            "bert_blend_weight": round(w_bert, 4),
            "bound": bound}


def _tree_oracle(cfg: QuantDrillConfig, scorer) -> Dict[str, Any]:
    """GEMM-vs-gather equivalence on the TRAINED ensembles plus a
    randomized one: exact leaf equality, logits inside tolerance."""
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.trees import (
        TreeEnsemble,
        descend_complete_trees,
        gemm_leaf_index,
        tree_ensemble_logits,
    )

    rng = np.random.default_rng(cfg.seed + 7)
    feat_dim = int(scorer.sc.feature_dim)
    x = jnp.asarray(rng.standard_normal((cfg.batch, feat_dim)), jnp.float32)

    out: Dict[str, Any] = {}
    trained = scorer.models.trees
    cases = {"trained_gbdt": (trained.feature, trained.threshold),
             "trained_iforest": (scorer.models.iforest.feature,
                                 scorer.models.iforest.threshold)}
    n_int = int(np.shape(trained.feature)[1])
    depth = int(np.log2(n_int + 1))
    rf = jnp.asarray(rng.integers(0, feat_dim, (8, n_int)), jnp.int32)
    rt = jnp.where(jnp.asarray(rng.random((8, n_int)) < 0.3), jnp.inf,
                   jnp.asarray(rng.standard_normal((8, n_int)), jnp.float32))
    cases["randomized"] = (rf, rt)

    leaves_equal = True
    for name, (feature, threshold) in cases.items():
        gather = descend_complete_trees(feature, threshold, x)
        gemm = gemm_leaf_index(feature, threshold, x)
        eq = bool(jnp.all(gather == gemm))
        out[name] = {"leaves_equal": eq}
        leaves_equal = leaves_equal and eq

    rand_ens = TreeEnsemble(
        feature=rf, threshold=rt,
        leaf=jnp.asarray(rng.standard_normal((8, 2 ** depth)), jnp.float32),
        base_score=jnp.asarray(0.1, jnp.float32))
    logit_delta = 0.0
    for ens in (trained, rand_ens):
        lg = tree_ensemble_logits(ens, x, kernel="gather")
        lm = tree_ensemble_logits(ens, x, kernel="gemm")
        logit_delta = max(logit_delta, float(jnp.max(jnp.abs(lg - lm))))
    out["max_logit_delta"] = logit_delta
    out["leaves_equal"] = leaves_equal
    return out


def _run_once(cfg: QuantDrillConfig) -> Dict[str, Any]:
    from realtime_fraud_detection_tpu.models.quant import (
        bert_param_bytes,
        is_quantized_bert,
        quant_error_bound,
    )
    from realtime_fraud_detection_tpu.training.blend_eval import _auc

    summary: Dict[str, Any] = {
        "drill": "quantization",
        "seed": cfg.seed,
        "batch": cfg.batch,
        "n_batches": cfg.n_batches,
        "checks": {},
    }
    checks = summary["checks"]

    gen_f, scorer_f, ts_f = _make_side(cfg, quantized=False)
    gen_q, scorer_q, ts_q = _make_side(cfg, quantized=True)
    assert ts_f == ts_q

    # param bytes: the HBM/hot-swap payload each replica carries
    bytes_f32 = bert_param_bytes(scorer_f.models.bert)
    bytes_q = bert_param_bytes(scorer_q.models.bert)
    ratio = bytes_f32 / max(bytes_q, 1)
    summary["param_bytes"] = {
        "bert_f32": bytes_f32, "bert_int8": bytes_q,
        "ratio": round(ratio, 3),
        "weight_reconstruction_bound": round(
            quant_error_bound(scorer_q.models.bert), 6),
    }
    checks["bert_is_quantized"] = is_quantized_bert(scorer_q.models.bert)
    checks["bytes_ratio_ge_min"] = ratio >= cfg.min_bytes_ratio

    # ---------------------------------- phase 1: divergence + decision flips
    keep = min(4, cfg.n_batches)
    side_f, ts_f = _score_stream(cfg, gen_f, scorer_f, ts_f, cfg.n_batches,
                                 keep_tokens=keep)
    side_q, ts_q = _score_stream(cfg, gen_q, scorer_q, ts_q, cfg.n_batches)
    div = np.abs(side_f["probs"] - side_q["probs"])
    flips = sum(a != b for a, b in zip(side_f["decisions"],
                                      side_q["decisions"]))
    noise = _noise_floor(cfg, scorer_f, side_f["tokens"])
    summary["divergence"] = {
        "max": float(div.max()),
        "mean": float(div.mean()),
        "p99": float(np.percentile(div, 99)),
        "n_txn": int(div.size),
        "noise_floor": noise,
        "noise_scale": cfg.noise_scale,
        "decision_flips": int(flips),
    }
    checks["divergence_below_noise"] = (
        float(div.max()) <= cfg.noise_scale * noise["bound"])
    checks["zero_decision_flips"] = flips == 0
    scorer_q.record_quant_gate(bool(checks["divergence_below_noise"]
                                    and checks["zero_decision_flips"]))

    # --------------------------------------- phase 2: quality-protocol AUC
    eval_f, _ = _score_stream(cfg, gen_f, scorer_f, ts_f, cfg.eval_batches)
    eval_q, _ = _score_stream(cfg, gen_q, scorer_q, ts_q, cfg.eval_batches)
    auc_f = _auc(eval_f["labels"], eval_f["probs"])
    auc_q = _auc(eval_q["labels"], eval_q["probs"])
    summary["quality"] = {
        "auc_f32": round(auc_f, 6),
        "auc_quant": round(auc_q, 6),
        "auc_delta": round(abs(auc_f - auc_q), 6),
        "eval_txn": int(eval_f["labels"].size),
        "fraud_rate": round(float(eval_f["labels"].mean()), 4),
        "max_auc_delta": cfg.max_auc_delta,
    }
    checks["auc_unchanged"] = abs(auc_f - auc_q) <= cfg.max_auc_delta
    scorer_q.record_quant_gate(bool(checks["auc_unchanged"]))

    # ------------------------------------------ phase 3: GEMM-vs-gather
    oracle = _tree_oracle(cfg, scorer_f)
    summary["tree_oracle"] = oracle
    checks["gemm_leaves_identical"] = oracle["leaves_equal"]
    checks["gemm_logits_within_tol"] = (
        oracle["max_logit_delta"] <= cfg.leaf_logit_tol)

    # served-mode truth (quant_snapshot reads live params, not config)
    summary["modes"] = {"f32": scorer_f.quant_snapshot()["modes"],
                        "quant": scorer_q.quant_snapshot()["modes"]}

    summary["passed"] = all(bool(v) for v in checks.values())
    return summary


def _digest(summary: Dict[str, Any]) -> str:
    """Replay fingerprint over every number the gates read."""
    payload = json.dumps(
        {k: summary.get(k) for k in ("divergence", "quality", "tree_oracle",
                                     "param_bytes", "checks")},
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode()).hexdigest()


def run_quant_drill(cfg: Optional[QuantDrillConfig] = None) -> Dict[str, Any]:
    cfg = cfg or QuantDrillConfig()
    summary = _run_once(cfg)
    summary["digest"] = _digest(summary)
    if cfg.replay:
        second = _run_once(cfg)
        second_digest = _digest(second)
        summary["replay"] = {"digest": second_digest,
                             "bit_identical": second_digest
                             == summary["digest"]}
        summary["checks"]["replay_bit_identical"] = (
            second_digest == summary["digest"])
        summary["passed"] = all(bool(v)
                                for v in summary["checks"].values())
    return summary


def compact_quant_summary(summary: Dict[str, Any]) -> Dict[str, Any]:
    """<2 KB single-line verdict (the final stdout line)."""
    div = summary.get("divergence") or {}
    q = summary.get("quality") or {}
    pb = summary.get("param_bytes") or {}
    return {
        "drill": "quantization",
        "passed": summary.get("passed", False),
        "checks": {k: bool(v)
                   for k, v in (summary.get("checks") or {}).items()},
        "max_divergence": div.get("max"),
        "noise_bound": (div.get("noise_floor") or {}).get("bound"),
        "decision_flips": div.get("decision_flips"),
        "auc_f32": q.get("auc_f32"),
        "auc_quant": q.get("auc_quant"),
        "auc_delta": q.get("auc_delta"),
        "bytes_ratio": pb.get("ratio"),
        "digest": (summary.get("digest") or "")[:16],
    }
