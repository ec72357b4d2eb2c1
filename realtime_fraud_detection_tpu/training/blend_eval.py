"""Production blend selection: train every branch, admit by measurement.

The reference configures a 5-model ensemble with fixed weights
(config.py:126-199) but never trains 3 of the 5 branches and never measures
the blend at all (its 96.8% accuracy claim has no harness behind it,
README.md:203). This module is the missing protocol, run the way the
framework serves:

1. **Stream-matched data.** Train/validation/test segments are consecutive
   windows of one simulated stream pushed through the PRODUCTION assemble
   path (``FraudScorer.assemble`` — live velocity/history/graph/token state),
   so every branch trains and evaluates on exactly the tensors serving
   builds. Training on offline-encoded features instead costs ~2pp
   accuracy / ~0.04 AUC on-stream (round-4 measurement).
2. **Per-branch training.** Trees (histogram GBDT), isolation forest,
   class-weighted LSTM / text / GNN (fraud is ~5% of the stream; unweighted
   BCE under-fits the positives — the round-4 LSTM's 0.74 AUC was exactly
   this, fixed here to ~0.97). Each neural branch is then Platt-calibrated
   on validation, with (a, b) FOLDED INTO the head parameters
   (training/calibrate.py) — class weighting inflates probabilities, and
   the serving combine averages raw probabilities, so an uncalibrated
   branch drags every blend it joins regardless of its ranking quality.
3. **Serving-parity blending.** Candidate blends run through
   ``ensemble.combine.combine_predictions`` itself (weighted average over
   the validity-masked branch set, renormalized — the same math the fused
   device program executes), so an accepted blend IS a deployable
   ``model_valid`` + ``EnsembleParams.weights`` setting, zero recompiles
   (testing/ab.py serves such variants).
4. **A/B-gated admission.** Starting from the round-4 production pair
   (trees + isolation forest), each remaining branch is admitted only if
   validation blend AUC does not regress — candidate weight chosen on
   validation from {config, config/2, config/4} (re-weighting by validation
   instead of trusting the reference's static weights). The held-out test
   segment is scored ONCE, with a paired bootstrap CI on the AUC delta vs
   the baseline pair.
5. **Operating point.** The alert threshold is chosen on validation to
   maximize recall subject to a precision floor (default 0.94, the round-4
   production precision), then reported on test.

``run_blend_eval`` returns the full evidence dict (per-branch AUCs,
admission decisions, ablations, bootstrap CI, operating points);
``rtfd quality-eval`` writes it as the round's quality artifact.
"""

from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Tuple

import numpy as np

if TYPE_CHECKING:  # import-cheap module: jax/models load lazily at run time
    from realtime_fraud_detection_tpu.models.bert import BertConfig

# branch order must match scoring.MODEL_NAMES (the device program's layout)
_BASELINE = ("xgboost_primary", "isolation_forest")


def _default_bert() -> "BertConfig":
    """The artifact's text-branch architecture (small enough to train on
    CPU inside the protocol; the perf benchmarks separately cover the
    full DistilBERT-base dimensions)."""
    from realtime_fraud_detection_tpu.models.bert import BertConfig

    return BertConfig(hidden_size=128, num_layers=2, num_heads=4,
                      intermediate_size=512)


@dataclasses.dataclass
class BlendEvalConfig:
    """Protocol parameters. Defaults reproduce the committed artifact."""

    num_users: int = 2000
    num_merchants: int = 500
    seed: int = 3
    batch_size: int = 256
    train_batches: int = 96
    # validation sizes the admission decisions AND the Platt fits: 24
    # batches ≈ 6k txns / ~350 positives keeps the AUC noise floor near
    # the deltas being judged (12 batches was decided by noise)
    val_batches: int = 24
    test_batches: int = 48
    # branch training
    n_trees: int = 40
    tree_depth: int = 5
    iforest_trees: int = 100
    lstm_epochs: int = 6
    lstm_hidden: int = 128
    text_epochs: int = 2
    gnn_epochs: int = 3
    text_len: int = 32
    tokenizer: str = "wordpiece"
    bert: "BertConfig" = dataclasses.field(default_factory=_default_bert)
    # admission + operating point
    weight_scales: Tuple[float, ...] = (1.0, 0.5, 0.25, 0.125)
    precision_target: float = 0.94
    bootstrap: int = 1000
    # combine-strategy selection: after weight admission, the stacked
    # combiner (ensemble/combine.py STACKING — shipped in the device
    # program but never exercised by this protocol before) competes with
    # weighted_average on validation; the winner is recorded in
    # selected_blend.strategy and deployed by apply_quality_artifact
    try_stacking: bool = True
    # saving into a checkpoint_dir whose latest step records a DIFFERENT
    # text-encoder architecture is refused unless explicitly allowed —
    # mixing architectures across steps makes "restore latest + apply
    # artifact" quietly incoherent
    allow_arch_mismatch: bool = False


def _auc(y: np.ndarray, s: np.ndarray) -> float:
    """Mann-Whitney AUC with tie-averaged ranks (ties get the mean of the
    rank run they occupy — without this, tied scores would be credited in
    arbitrary argsort order and a constant scorer could report AUC 1.0)."""
    _, inv, counts = np.unique(s, return_inverse=True, return_counts=True)
    ends = np.cumsum(counts)
    rank = (ends - (counts - 1) / 2.0)[inv]
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def _prf(y: np.ndarray, flag: np.ndarray) -> Dict[str, float]:
    pos = y > 0.5
    tp = float((flag & pos).sum())
    return {
        "accuracy": round(float((flag == pos).mean()), 4),
        "precision": round(tp / max(float(flag.sum()), 1.0), 4),
        "recall": round(tp / max(float(pos.sum()), 1.0), 4),
    }


def _collect(scorer, gen, n_batches: int, batch_size: int) -> Dict[str, np.ndarray]:
    """One stream segment through the production assemble path."""
    cols: Dict[str, list] = {k: [] for k in (
        "features", "history", "hlen", "ids", "mask", "uf", "mf",
        "unf", "unm", "mnf", "mnm", "y")}
    for _ in range(n_batches):
        recs = gen.generate_batch(batch_size)
        b = scorer.assemble(recs)
        for key, val in (
            ("features", b.features), ("history", b.history),
            ("hlen", b.history_len), ("ids", b.token_ids),
            ("mask", b.token_mask), ("uf", b.user_feat),
            ("mf", b.merchant_feat), ("unf", b.user_neigh_feat),
            ("unm", b.user_neigh_mask), ("mnf", b.merch_neigh_feat),
            ("mnm", b.merch_neigh_mask),
        ):
            cols[key].append(np.asarray(val))
        cols["y"].append(np.asarray(
            [bool(r.get("is_fraud")) for r in recs], np.float32))
        # serving's post-score write-back, applied here so later segments
        # see the velocity state this segment created
        ts = time.time()
        for r in recs:
            scorer.velocity.update(str(r.get("user_id", "")),
                                   float(r.get("amount", 0.0)), ts)
    return {k: np.concatenate(v) for k, v in cols.items()}


def _train_branches(
    cfg: BlendEvalConfig, tr: Dict[str, np.ndarray],
    segments: Dict[str, Dict[str, np.ndarray]],
    log: Callable[[str], None],
) -> Tuple[Dict[str, Dict[str, np.ndarray]], Dict[str, Dict[str, float]],
           Dict[str, object]]:
    """Fit all five branches; return (scores[segment][branch], platt
    calibration constants per neural branch, trained+calibrated params)."""
    import jax
    import jax.numpy as jnp
    import optax

    from realtime_fraud_detection_tpu.models.bert import (
        bert_logits,
        init_bert_params,
    )
    from realtime_fraud_detection_tpu.models.gnn import (
        gnn_logits,
        init_gnn_params,
    )
    from realtime_fraud_detection_tpu.models.isolation_forest import (
        IsolationForestTrainer,
        iforest_predict,
    )
    from realtime_fraud_detection_tpu.models.lstm import (
        init_lstm_params,
        lstm_logits,
    )
    from realtime_fraud_detection_tpu.models.trees import tree_ensemble_predict
    from realtime_fraud_detection_tpu.training import GBDTTrainer
    from realtime_fraud_detection_tpu.training.neural import NeuralTrainer

    pos_w = float((1.0 - tr["y"].mean()) / max(tr["y"].mean(), 1e-6))
    scores: Dict[str, Dict[str, np.ndarray]] = {k: {} for k in segments}

    log("training trees + isolation forest")
    gtr = GBDTTrainer(n_estimators=cfg.n_trees, max_depth=cfg.tree_depth,
                      seed=2)
    trees = gtr.fit(tr["features"], tr["y"])
    ifo = IsolationForestTrainer(n_estimators=cfg.iforest_trees, seed=4).fit(
        tr["features"][tr["y"] < 0.5][:6000])
    tfn = jax.jit(tree_ensemble_predict)
    ifn = jax.jit(iforest_predict)
    for k, d in segments.items():
        scores[k]["xgboost_primary"] = np.asarray(tfn(trees, d["features"]))
        scores[k]["isolation_forest"] = np.asarray(ifn(ifo, d["features"]))

    log("training LSTM (class-weighted)")
    lp = init_lstm_params(jax.random.PRNGKey(0), tr["features"].shape[-1],
                          cfg.lstm_hidden)

    def lstm_loss(p, inputs, y):
        s, l = inputs
        per = optax.sigmoid_binary_cross_entropy(lstm_logits(p, s, l), y)
        return (per * jnp.where(y > 0.5, pos_w, 1.0)).mean()

    lp = NeuralTrainer(epochs=cfg.lstm_epochs, seed=0).train(
        lp, lstm_loss, (np.clip(tr["history"], -10, 10), tr["hlen"]),
        tr["y"])
    lfn = jax.jit(lstm_logits)
    lstm_z = {k: np.asarray(lfn(lp, np.clip(d["history"], -10, 10),
                                d["hlen"]))
              for k, d in segments.items()}

    log("training text branch (class-weighted)")
    bp = init_bert_params(jax.random.PRNGKey(1), cfg.bert)

    def text_loss(p, inputs, y):
        ids, mask = inputs
        lg = bert_logits(p, ids, mask, cfg.bert)
        per = optax.sigmoid_binary_cross_entropy(lg[:, 1] - lg[:, 0], y)
        return (per * jnp.where(y > 0.5, pos_w, 1.0)).mean()

    bp = NeuralTrainer(epochs=cfg.text_epochs, seed=1, batch_size=128,
                       optimizer=optax.adamw(5e-4)).train(
        bp, text_loss, (tr["ids"], tr["mask"]), tr["y"])
    bfn = jax.jit(lambda p, i, m: bert_logits(p, i, m, cfg.bert))
    text_z = {}
    for k, d in segments.items():
        lg = np.asarray(bfn(bp, d["ids"], d["mask"]))
        text_z[k] = lg[:, 1] - lg[:, 0]

    log("training GNN (class-weighted)")
    gp = init_gnn_params(jax.random.PRNGKey(2), tr["uf"].shape[-1],
                         tr["features"].shape[-1], 64)

    def gnn_loss(p, inputs, y):
        per = optax.sigmoid_binary_cross_entropy(gnn_logits(p, *inputs), y)
        return (per * jnp.where(y > 0.5, pos_w, 1.0)).mean()

    gp = NeuralTrainer(epochs=cfg.gnn_epochs, seed=2).train(
        gp, gnn_loss,
        (np.clip(tr["features"], -10, 10), tr["uf"], tr["mf"], tr["unf"],
         tr["unm"], tr["mnf"], tr["mnm"]), tr["y"])
    gfn = jax.jit(gnn_logits)
    gnn_z = {k: np.asarray(gfn(
        gp, np.clip(d["features"], -10, 10), d["uf"], d["mf"],
        d["unf"], d["unm"], d["mnf"], d["mnm"]))
        for k, d in segments.items()}

    # Platt-calibrate the class-weighted branches on VALIDATION, and FOLD
    # (a, b) into the head params (training/calibrate.py — the fold is
    # exact, so these probabilities ARE what the calibrated model serves,
    # and the returned params are the deployable calibrated branches)
    from realtime_fraud_detection_tpu.training.calibrate import (
        calibrate_bert_head,
        calibrate_gnn_head,
        calibrate_lstm_head,
        platt_apply,
        platt_fit,
    )

    y_val = segments["val"]["y"]
    calibration = {}
    folds = {"lstm_sequential": (lstm_z, lambda a, b: calibrate_lstm_head(lp, a, b)),
             "bert_text": (text_z, lambda a, b: calibrate_bert_head(bp, a, b)),
             "graph_neural": (gnn_z, lambda a, b: calibrate_gnn_head(gp, a, b))}
    calibrated_params = {}
    for name, (z, fold) in folds.items():
        a, b = platt_fit(z["val"], y_val)
        calibration[name] = {"a": round(a, 4), "b": round(b, 4)}
        calibrated_params[name] = fold(a, b)
        for k in segments:
            scores[k][name] = platt_apply(z[k], a, b).astype(np.float32)
    log(f"platt calibration (fit on val): {calibration}")
    trained = {
        "trees": trees,
        "iforest": ifo,
        "lstm": calibrated_params["lstm_sequential"],
        "bert": calibrated_params["bert_text"],
        "gnn": calibrated_params["graph_neural"],
    }
    return scores, calibration, trained


def _blend_fn(weights_by_name: Dict[str, float],
              strategy: str = "weighted_average"):
    """Serving-parity blend: the shared ``blend_branch_scores`` recipe
    (ensemble/combine.py — also the continuous-learning gate's combine),
    curried over this protocol's weights + strategy. Returns a callable
    scores_by_branch -> fraud probabilities running the SAME jitted
    combine the fused device program uses — weighted average or the
    stacked combiner."""
    from realtime_fraud_detection_tpu.ensemble.combine import (
        blend_branch_scores,
    )

    def blend(scores_by_branch: Dict[str, np.ndarray]) -> np.ndarray:
        return blend_branch_scores(scores_by_branch, weights_by_name,
                                   strategy)

    return blend


def run_blend_eval(cfg: Optional[BlendEvalConfig] = None,
                   log: Callable[[str], None] = lambda m: None,
                   checkpoint_dir: Optional[str] = None) -> Dict:
    """Execute the full protocol; returns the evidence dict (JSON-able).

    ``checkpoint_dir``: also save the trained + calibrated branches as a
    serving checkpoint (orbax, step 0) with the text-arch recorded in its
    metadata — the artifact + checkpoint pair is a complete deployment:
    ``rtfd serve --checkpoint-dir D --quality-artifact Q.json``."""
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    cfg = cfg or BlendEvalConfig()
    config_weights = Config().normalized_weights()

    gen = TransactionGenerator(num_users=cfg.num_users,
                               num_merchants=cfg.num_merchants,
                               seed=cfg.seed)
    scorer = FraudScorer(
        scorer_config=ScorerConfig(text_len=cfg.text_len,
                                   tokenizer=cfg.tokenizer),
        bert_config=cfg.bert)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())

    log("collecting train/val/test stream segments (production assemble)")
    tr = _collect(scorer, gen, cfg.train_batches, cfg.batch_size)
    va = _collect(scorer, gen, cfg.val_batches, cfg.batch_size)
    te = _collect(scorer, gen, cfg.test_batches, cfg.batch_size)
    segments = {"val": va, "test": te}

    scores, calibration, trained = _train_branches(cfg, tr, segments, log)
    y_va, y_te = va["y"], te["y"]

    branch_auc = {
        name: {"val": round(_auc(y_va, scores["val"][name]), 4),
               "test": round(_auc(y_te, scores["test"][name]), 4)}
        for name in scores["val"]
    }
    log(f"per-branch AUC: {branch_auc}")

    # ---------------- A/B-gated admission (decisions on VALIDATION only)
    weights: Dict[str, float] = {n: config_weights[n] for n in _BASELINE}
    admission: List[Dict] = []
    cur_val_auc = _auc(y_va, _blend_fn(weights)(scores["val"]))
    candidates = sorted(
        (n for n in scores["val"] if n not in _BASELINE),
        key=lambda n: -branch_auc[n]["val"])
    for name in candidates:
        best = None
        for scale in cfg.weight_scales:
            trial = dict(weights)
            trial[name] = config_weights[name] * scale
            a = _auc(y_va, _blend_fn(trial)(scores["val"]))
            if best is None or a > best[0]:
                best = (a, scale, trial)
        a, scale, trial = best
        accepted = a >= cur_val_auc     # non-regression gate
        admission.append({
            "branch": name, "weight_scale": scale,
            "val_auc_before": round(cur_val_auc, 4),
            "val_auc_with": round(a, 4),
            "accepted": bool(accepted),
        })
        log(f"  {'ACCEPT' if accepted else 'reject'} {name} "
            f"(scale {scale}): {cur_val_auc:.4f} -> {a:.4f}")
        if accepted:
            weights, cur_val_auc = trial, a

    # ------------- combine-strategy selection (decided on VALIDATION):
    # the stacked combiner competes with weighted_average over the
    # admitted branch set — same weights, same jitted device combine
    strategy = "weighted_average"
    strategy_selection = {
        "weighted_average": round(cur_val_auc, 4),
    }
    if cfg.try_stacking:
        stack_val = _auc(y_va, _blend_fn(weights, "stacking")(scores["val"]))
        strategy_selection["stacking"] = round(stack_val, 4)
        if not np.isnan(stack_val) and stack_val > cur_val_auc:
            strategy, cur_val_auc = "stacking", stack_val
    strategy_selection["selected"] = strategy
    log(f"combine strategy (val): {strategy_selection}")

    blend = _blend_fn(weights, strategy)
    blend_te = blend(scores["test"])
    blend_va = blend(scores["val"])
    baseline_te = _blend_fn(
        {n: config_weights[n] for n in _BASELINE})(scores["test"])
    test_auc = _auc(y_te, blend_te)
    base_auc = _auc(y_te, baseline_te)

    # paired bootstrap CI on the AUC delta vs the round-4 baseline pair
    rng = np.random.default_rng(7)
    deltas = np.empty(cfg.bootstrap)
    n_te = len(y_te)
    for i in range(cfg.bootstrap):
        idx = rng.integers(0, n_te, n_te)
        deltas[i] = _auc(y_te[idx], blend_te[idx]) - _auc(
            y_te[idx], baseline_te[idx])
    ci = (float(np.percentile(deltas, 2.5)),
          float(np.percentile(deltas, 97.5)))

    # drop-one ablation of the selected blend (test segment)
    ablation = {}
    for name in list(weights):
        if len(weights) <= 1:
            break
        rest = {k: v for k, v in weights.items() if k != name}
        ablation[name] = round(
            test_auc - _auc(y_te, _blend_fn(rest, strategy)(
                scores["test"])), 4)

    # ---------------- operating points (threshold chosen on VALIDATION)
    pos_va = y_va > 0.5
    best_t, best_rec = 0.5, -1.0
    for t in np.linspace(0.05, 0.95, 181):
        flag = blend_va >= t
        tp = float((flag & pos_va).sum())
        prec = tp / max(float(flag.sum()), 1.0)
        rec = tp / max(float(pos_va.sum()), 1.0)
        if prec >= cfg.precision_target and rec > best_rec:
            best_t, best_rec = float(t), rec
    operating = {
        "at_0.5": _prf(y_te, blend_te >= 0.5),
        f"at_precision>={cfg.precision_target}": {
            "threshold": round(best_t, 3),
            **_prf(y_te, blend_te >= best_t),
        },
    }

    checkpoint_info = None
    if checkpoint_dir:
        from realtime_fraud_detection_tpu.checkpoint import CheckpointManager
        from realtime_fraud_detection_tpu.scoring import ScoringModels

        mgr = CheckpointManager(checkpoint_dir)
        latest = mgr.latest_step()
        if latest is not None and not cfg.allow_arch_mismatch:
            prev_tm = (mgr.manifest(latest).get("metadata")
                       or {}).get("text_model")
            this_tm = dataclasses.asdict(cfg.bert)
            if prev_tm is not None and dict(prev_tm) != this_tm:
                # a dir mixing text architectures across steps makes
                # "restore latest" + "apply artifact" quietly incoherent —
                # refuse unless the caller explicitly allows it
                raise ValueError(
                    f"checkpoint dir {checkpoint_dir} step {latest} records "
                    f"text_model {prev_tm}, but this protocol runs "
                    f"{this_tm}; use a fresh directory or set "
                    f"allow_arch_mismatch")

        models = ScoringModels(
            trees=trained["trees"], iforest=trained["iforest"],
            lstm=trained["lstm"], gnn=trained["gnn"], bert=trained["bert"])
        step = 0 if latest is None else latest + 1
        mgr.save(
            step, params=models,
            metadata={
                "source": "blend_eval",
                "text_model": dataclasses.asdict(cfg.bert),
                "text_len": cfg.text_len,
                "tokenizer": cfg.tokenizer,
                "selected_blend": sorted(weights),
                "selected_strategy": strategy,
            })
        checkpoint_info = {"dir": str(checkpoint_dir), "step": step}
        log(f"saved trained+calibrated branches to {checkpoint_dir}")

    return {
        "protocol": {
            "stream": {"users": cfg.num_users,
                       "merchants": cfg.num_merchants, "seed": cfg.seed},
            "segments_txns": {"train": len(tr["y"]), "val": len(y_va),
                              "test": len(y_te)},
            "fraud_rate": {"train": round(float(tr["y"].mean()), 4),
                           "test": round(float(y_te.mean()), 4)},
            "assemble_path": "FraudScorer.assemble (live state)",
            "blend_math": "ensemble.combine.combine_predictions "
                          "(serving parity)",
            "tokenizer": cfg.tokenizer,
            "text_model": dataclasses.asdict(cfg.bert),
            "text_len": cfg.text_len,
            "platt_calibration": calibration,
        },
        "checkpoint": checkpoint_info,
        "branch_auc": branch_auc,
        "admission": admission,
        "strategy_selection": strategy_selection,
        "selected_blend": {
            "branches": sorted(weights),
            "weights": {k: round(v, 4) for k, v in sorted(weights.items())},
            "n_branches": len(weights),
            "strategy": strategy,
        },
        "test": {
            "blend_auc": round(test_auc, 4),
            "baseline_pair_auc": round(base_auc, 4),
            "delta_auc": round(test_auc - base_auc, 4),
            "delta_auc_bootstrap_95ci": [round(ci[0], 4), round(ci[1], 4)],
        },
        "ablation_drop_one_delta_auc": ablation,
        "operating_points": operating,
        "reference_claim": "96.8% accuracy, unmeasured "
                           "(reference README.md:203)",
    }
