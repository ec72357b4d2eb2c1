"""Deterministic kernel drill: the ``rtfd kernel-drill`` parity oracle that
makes the Pallas kernel plane (ops/ + KernelSettings) shippable.

Hand-fused kernels are free throughput ONLY while numerics are gated, not
assumed — the quant-drill contract, applied to the kernel plane. Run the
way the other eleven drills run (virtual clock, seeded, compact <2 KB JSON
verdict as the final stdout line):

1. **Score-delta oracle.** One seeded transaction stream through TWO real
   scorers — both serving the committed quantized plane
   (``QuantSettings.full()``), one on the stock XLA lowering, one with
   every kernel on (``KernelSettings.full()``: fused dequant-matmul +
   fused score-and-blend epilogue + flash attention, through the Pallas
   interpreter on CPU). Max absolute fraud-score divergence must sit
   BELOW the calibration-noise floor: the score movement the committed
   bf16 compute policy already accepts, measured in-drill on this stream.
2. **Zero decision flips.** Every transaction takes the SAME decision
   under both programs at the pinned operating point.
3. **Masked-rung equality.** At every QoS ladder rung (qos/ladder.py) the
   kernel-on side must serve the same decisions/risk levels, probs within
   the noise bound — and the rules_only rung bit-exactly (its ladder is
   pure f32 comparisons, on-chip in the fused epilogue vs host math).
   The fast config pins the two extremes (full blend + rules_only); the
   full drill walks all four rungs.
4. **Per-kernel oracle.** Each kernel, interpret-mode vs its XLA
   reference, on the drill's REAL served params: fused dequant-matmul
   (f32 compute near-exact, bf16 compute within rounding scale), per-row
   embedding dequant exact, fused epilogue exact decisions across all
   three strategies, the fused attention core within the bf16 rounding of
   its softmax weights.
5. **Replay.** A second full run must be bit-identical (sha256 over every
   gate-read number).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["KernelDrillConfig", "run_kernel_drill",
           "compact_kernel_summary"]


@dataclasses.dataclass
class KernelDrillConfig:
    seed: int = 13
    num_users: int = 600
    num_merchants: int = 120
    batch: int = 96
    n_batches: int = 10         # divergence / decision-flip stream
    tps: float = 200.0          # virtual arrival rate (clock advance)
    # gates
    noise_scale: float = 1.0    # kernel divergence <= scale * bf16 noise floor
    noise_floor_abs: float = 1e-4   # resolution floor for the noise bound
    matmul_rel_tol: float = 0.05    # bf16 dequant-matmul: rounding-scale,
    #                                 relative to the reference magnitude
    matmul_f32_tol: float = 1e-5    # f32 compute: summation-order slack only
    rows_tol: float = 0.0           # per-row dequant: one widen+mul, exact
    epilogue_prob_tol: float = 1e-6
    attention_tol: float = 1e-2     # bf16 rounding of the softmax weights
    #                                 (operands bf16-representable, N(0, 1))
    # the shortest text window the fused attention core takes
    # (ops.attention.flash_supported): at ScorerConfig's 64 the attention
    # site would be a counted fallback and the drill would gate nothing
    text_len: int = 128
    replay: bool = True
    # QoS rung subset for phase 2 (None = every LADDER_LEVELS rung). Each
    # non-zero rung is a fresh static config — a full recompile of BOTH
    # sides, and the kernel side pays interpret-mode Pallas tracing per
    # compile on CPU — so the fast config pins the two extremes (full
    # blend, rules_only) and leaves the interior rungs to the full drill.
    rung_levels: Optional[Tuple[int, ...]] = None

    @classmethod
    def fast(cls) -> "KernelDrillConfig":
        """Tier-1 smoke sizes: every phase runs, compiles stay small."""
        return cls(num_users=300, num_merchants=60, batch=32, n_batches=2,
                   rung_levels=(0, 3))


def _make_side(cfg: KernelDrillConfig, kernels_on: bool):
    """One drill side: seeded generator + scorer. Both sides serve the
    committed quantized plane (int8 BERT + GEMM trees) so the ONLY
    difference is the kernel plane — the thing under test."""
    from realtime_fraud_detection_tpu.scoring import (
        FraudScorer,
        ScorerConfig,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        KernelSettings,
        QuantSettings,
    )

    kernels = KernelSettings.full() if kernels_on else KernelSettings()
    gen = TransactionGenerator(num_users=cfg.num_users,
                               num_merchants=cfg.num_merchants,
                               seed=cfg.seed)
    scorer = FraudScorer(Config(quant=QuantSettings.full(), kernels=kernels),
                         scorer_config=ScorerConfig(text_len=cfg.text_len),
                         seed=cfg.seed)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return gen, scorer


def _score_stream(cfg: KernelDrillConfig, gen, scorer, ts: float,
                  n_batches: int, keep_tokens: int = 0,
                  ) -> Tuple[Dict[str, Any], float]:
    """Drive ``n_batches`` through the scorer on the virtual clock."""
    probs: List[float] = []
    decisions: List[str] = []
    risks: List[str] = []
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
        risks.extend(r["risk_level"] for r in results)
        ts += cfg.batch / cfg.tps
    return {
        "probs": np.asarray(probs, np.float64),
        "decisions": decisions,
        "risks": risks,
        "tokens": tokens,
    }, ts


def noise_floor(cfg: KernelDrillConfig, scorer,
                 tokens) -> Dict[str, float]:
    """The calibration-noise bound: how far the committed bf16 compute
    policy already moves the ensemble score vs full f32 compute, measured
    on this drill's own token stream with the SERVED weights, scaled by
    the text branch's blend weight (quant-drill recipe). The f32 side
    runs at "highest" matmul precision — a TPU's default would compute it
    in bf16 passes too, and the floor would be noise against noise."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.bert import bert_predict

    bf16 = jax.jit(lambda p, i, m: bert_predict(
        p, i, m, scorer.bert_config))
    f32 = jax.jit(lambda p, i, m: bert_predict(
        p, i, m, scorer.bert_config, compute_dtype=jnp.float32))
    branch_delta = 0.0
    for ids, mask in tokens:
        a = bf16(scorer.models.bert, ids, mask)
        with jax.default_matmul_precision("highest"):
            b = f32(scorer.models.bert, ids, mask)
        branch_delta = max(branch_delta,
                           float(jnp.max(jnp.abs(a - b))))
    weights = np.asarray(scorer.ensemble_params.weights, np.float64)
    valid = np.asarray(scorer.effective_model_valid(), bool)
    w = weights * valid
    w_bert = float(w[2] / max(w.sum(), 1e-9))      # MODEL_NAMES order
    bound = max(branch_delta * w_bert, cfg.noise_floor_abs)
    return {"bert_branch_bf16_delta": branch_delta,
            "bert_blend_weight": round(w_bert, 4),
            "bound": bound}


def _rung_phase(cfg: KernelDrillConfig, gen_a, scorer_a, gen_b, scorer_b,
                ts: float, bound: float) -> Dict[str, Any]:
    """Masked-blend equality at every QoS ladder rung: one batch per rung
    on both sides, decisions/risk exactly equal, probs within the noise
    bound — and the rules_only rung bit-exact (pure f32 ladder)."""
    from realtime_fraud_detection_tpu.qos.ladder import LADDER_LEVELS
    from realtime_fraud_detection_tpu.scoring import MODEL_NAMES

    rungs: Dict[str, Any] = {}
    for level, rung in enumerate(LADDER_LEVELS):
        if cfg.rung_levels is not None and level not in cfg.rung_levels:
            continue
        mask = np.asarray([n not in rung.dropped_branches
                           for n in MODEL_NAMES], bool)
        for scorer in (scorer_a, scorer_b):
            # rtfd-lint: allow[lock-order] drill is single-threaded (no batch in flight during the rung step)
            scorer.set_degradation(None if level == 0 else mask,
                                   rules_only=rung.rules_only, level=level)
        side_a, _ = _score_stream(cfg, gen_a, scorer_a, ts, 1)
        side_b, ts = _score_stream(cfg, gen_b, scorer_b, ts, 1)
        div = float(np.abs(side_a["probs"] - side_b["probs"]).max())
        flips = sum(x != y for x, y in zip(side_a["decisions"],
                                           side_b["decisions"]))
        risk_flips = sum(x != y for x, y in zip(side_a["risks"],
                                                side_b["risks"]))
        ok = flips == 0 and risk_flips == 0 and (
            div == 0.0 if rung.rules_only else div <= bound)
        rungs[rung.name] = {"max_divergence": div,
                            "decision_flips": int(flips),
                            "risk_flips": int(risk_flips),
                            "exact": div == 0.0, "ok": bool(ok)}
    for scorer in (scorer_a, scorer_b):
        # rtfd-lint: allow[lock-order] drill is single-threaded (no batch in flight during the reset)
        scorer.set_degradation(None, rules_only=False, level=0)
    return rungs


def _kernel_oracle(cfg: KernelDrillConfig, scorer) -> Dict[str, Any]:
    """Per-kernel interpret-vs-XLA-reference parity on the REAL served
    params (plus randomized operands), the numerics section of the gate."""
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.ops import (
        attention_reference,
        dequant_matmul,
        dequant_matmul_reference,
        dequant_rows,
        dequant_rows_reference,
        epilogue_reference,
        flash_attention,
        fused_epilogue,
        merge_heads,
        split_heads,
    )

    rng = np.random.default_rng(cfg.seed + 23)
    out: Dict[str, Any] = {}
    layer = scorer.models.bert["layers"][0]
    h = int(scorer.bert_config.hidden_size)

    # --- fused dequant-matmul on the served int8 q/ffn1 kernels
    x = jnp.asarray(rng.standard_normal((cfg.batch, h)), jnp.float32)
    mm: Dict[str, float] = {}
    for name in ("q", "ffn1"):
        p = layer[name]
        for cd, key in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
            ref = dequant_matmul_reference(x, p["qw"], p["scale"], p["b"],
                                           cd).astype(jnp.float32)
            got = dequant_matmul(x, p["qw"], p["scale"], p["b"],
                                 compute_dtype=cd, interpret=True)
            delta = float(jnp.abs(got - ref).max())
            scale = max(1.0, float(jnp.abs(ref).max()))
            k = f"{key}_rel_delta"
            mm[k] = max(mm.get(k, 0.0), delta / scale)
    mm["ok"] = (mm["bf16_rel_delta"] <= cfg.matmul_rel_tol
                and mm["f32_rel_delta"] <= cfg.matmul_f32_tol)
    out["dequant_matmul"] = mm

    # --- per-row embedding dequant on served word_emb rows
    emb = scorer.models.bert["word_emb"]
    idx = rng.integers(0, emb["qe"].shape[0], (64,))
    q = jnp.asarray(np.asarray(emb["qe"])[idx])
    s = jnp.asarray(np.asarray(emb["scale"])[idx])
    rows_delta = float(jnp.abs(
        dequant_rows(q, s, interpret=True)
        - dequant_rows_reference(q, s)).max())
    out["dequant_rows"] = {"max_delta": rows_delta,
                           "ok": rows_delta <= cfg.rows_tol}

    # --- fused epilogue across all three strategies
    base = scorer.ensemble_params
    preds = jnp.asarray(rng.uniform(0, 1, (cfg.batch, 5)), jnp.float32)
    valid = jnp.asarray(rng.uniform(0, 1, (cfg.batch, 5)) > 0.25)
    rule = jnp.asarray(rng.uniform(0, 1, (cfg.batch,)), jnp.float32)
    ep_delta, ep_exact = 0.0, True
    for strat in range(3):
        params: EnsembleParams = base.replace(strategy=strat)
        ref = epilogue_reference(preds, valid, rule, params)
        got = fused_epilogue(preds, valid, rule, params, interpret=True)
        ep_delta = max(ep_delta, float(jnp.abs(
            got["fraud_probability"] - ref["fraud_probability"]).max()))
        ep_exact = ep_exact and all(
            bool(jnp.all(got[k] == ref[k]))
            for k in ("decision", "risk_level", "rule_decision",
                      "rule_risk"))
    out["epilogue"] = {"max_prob_delta": ep_delta,
                       "ladders_exact": bool(ep_exact),
                       "ok": bool(ep_exact
                                  and ep_delta <= cfg.epilogue_prob_tol)}

    # --- fused attention core vs reference (drill text shape; operands
    # bf16-representable, so both sides multiply the same numbers and what
    # is left is where each rounds the softmax weights)
    b, heads, seq = 4, int(scorer.bert_config.num_heads), int(
        scorer.sc.text_len)
    d = int(scorer.bert_config.head_dim)
    qkv = [jnp.asarray(rng.standard_normal((b, seq, heads * d)),
                       jnp.bfloat16).astype(jnp.float32) for _ in range(3)]
    mask = jnp.asarray(rng.uniform(0, 1, (b, seq)) > 0.1)
    ref = merge_heads(attention_reference(
        *(split_heads(t, heads) for t in qkv), mask))
    att_delta = float(jnp.abs(flash_attention(
        *qkv, mask, num_heads=heads, interpret=True) - ref).max())
    out["attention"] = {"max_delta": att_delta,
                        "ok": att_delta <= cfg.attention_tol}
    return out


def _run_once(cfg: KernelDrillConfig) -> Dict[str, Any]:
    summary: Dict[str, Any] = {
        "drill": "kernels",
        "seed": cfg.seed,
        "batch": cfg.batch,
        "n_batches": cfg.n_batches,
        "checks": {},
    }
    checks = summary["checks"]

    gen_a, scorer_a = _make_side(cfg, kernels_on=False)
    gen_b, scorer_b = _make_side(cfg, kernels_on=True)
    ts = 0.0

    # ---------------------------------- phase 1: divergence + decision flips
    keep = min(4, cfg.n_batches)
    side_a, _ = _score_stream(cfg, gen_a, scorer_a, ts, cfg.n_batches,
                              keep_tokens=keep)
    side_b, ts = _score_stream(cfg, gen_b, scorer_b, ts, cfg.n_batches)
    div = np.abs(side_a["probs"] - side_b["probs"])
    flips = sum(a != b for a, b in zip(side_a["decisions"],
                                       side_b["decisions"]))
    noise = noise_floor(cfg, scorer_a, side_a["tokens"])
    bound = cfg.noise_scale * noise["bound"]
    summary["divergence"] = {
        "max": float(div.max()),
        "mean": float(div.mean()),
        "p99": float(np.percentile(div, 99)),
        "n_txn": int(div.size),
        "noise_floor": noise,
        "noise_scale": cfg.noise_scale,
        "decision_flips": int(flips),
    }
    checks["divergence_below_noise"] = float(div.max()) <= bound
    checks["zero_decision_flips"] = flips == 0

    # --------------------------------- phase 2: masked-rung (QoS) equality
    rungs = _rung_phase(cfg, gen_a, scorer_a, gen_b, scorer_b, ts, bound)
    summary["rungs"] = rungs
    checks["masked_rungs_equal"] = all(r["ok"] for r in rungs.values())
    checks["rules_only_exact"] = bool(rungs["rules_only"]["exact"])

    # ------------------------------------- phase 3: per-kernel oracle
    oracle = _kernel_oracle(cfg, scorer_b)
    summary["kernel_oracle"] = oracle
    checks["dequant_matmul_parity"] = bool(oracle["dequant_matmul"]["ok"])
    checks["dequant_rows_parity"] = bool(oracle["dequant_rows"]["ok"])
    checks["epilogue_parity"] = bool(oracle["epilogue"]["ok"])
    checks["attention_parity"] = bool(oracle["attention"]["ok"])

    # served-mode truth + honest dispatch accounting: every launch on the
    # kernel side must have engaged every site with zero guard fallbacks
    # (the drill's shapes are the production shapes)
    snap = scorer_b.kernel_snapshot()
    summary["kernel_snapshot"] = snap
    summary["modes"] = {"off": scorer_a.kernel_snapshot()["modes"],
                        "on": snap["modes"]}
    checks["all_sites_dispatched"] = all(
        v > 0 for v in snap["dispatch"].values())
    checks["zero_fallbacks"] = all(
        v == 0 for v in snap["fallback"].values())

    summary["passed"] = all(bool(v) for v in checks.values())
    return summary


def _digest(summary: Dict[str, Any]) -> str:
    """Replay fingerprint over every number the gates read."""
    payload = json.dumps(
        {k: summary.get(k) for k in ("divergence", "rungs", "kernel_oracle",
                                     "kernel_snapshot", "checks")},
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode()).hexdigest()


def run_kernel_drill(
        cfg: Optional[KernelDrillConfig] = None) -> Dict[str, Any]:
    cfg = cfg or KernelDrillConfig()
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


def compact_kernel_summary(summary: Dict[str, Any]) -> Dict[str, Any]:
    """<2 KB single-line verdict (the final stdout line)."""
    div = summary.get("divergence") or {}
    oracle = summary.get("kernel_oracle") or {}
    snap = summary.get("kernel_snapshot") or {}
    out = {
        "drill": "kernels",
        "passed": summary.get("passed", False),
        "checks": {k: bool(v)
                   for k, v in (summary.get("checks") or {}).items()},
        "max_divergence": div.get("max"),
        "noise_bound": (div.get("noise_floor") or {}).get("bound"),
        "decision_flips": div.get("decision_flips"),
        "matmul_bf16_rel": (oracle.get("dequant_matmul")
                            or {}).get("bf16_rel_delta"),
        "attention_delta": (oracle.get("attention") or {}).get("max_delta"),
        "fallbacks": snap.get("fallback"),
        "digest": (summary.get("digest") or "")[:16],
    }
    return out
