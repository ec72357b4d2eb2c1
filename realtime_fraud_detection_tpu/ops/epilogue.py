"""Fused score-and-blend epilogue Pallas kernel.

The last stage of the fused program — five branch probabilities + the
branch-validity/QoS mask + blend weights + the decision/risk ladders —
is pure VPU elementwise/reduce work, but the host used to re-derive two
pieces of it per record in ``FraudScorer._build_responses``: the
per-model explanation contributions (weights x preds) and, on the QoS
rules-only rung, the whole decision ladder over the rule score. This
kernel runs the ensemble combine (ensemble/combine.py math, verbatim)
on-chip and emits those derived columns alongside, so finalize becomes
pure column reads: no per-batch host blend math at all.

Layout: one program, whole arrays resident in VMEM — the operands are
[B, M] with M=5 and B bucket-bounded, orders of magnitude under the tile
budget; a grid would only add index arithmetic. The XLA oracle is
``epilogue_reference`` (a composition of the very functions the kernel
replaces), and ``epilogue_supported`` is the shared shape guard.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from realtime_fraud_detection_tpu.ensemble.combine import (
    VOTING,
    WEIGHTED_AVERAGE,
    combine_predictions,
)
from realtime_fraud_detection_tpu.features.rules import (
    APPROVE,
    APPROVE_WITH_MONITORING,
    DECLINE,
    REVIEW,
    RISK_LEVEL_THRESHOLDS,
    risk_level_code,
)

# whole-array kernel: bound B so the [B, M(+6)] operands stay far inside
# VMEM even at the largest batch bucket
_MAX_EPILOGUE_ROWS = 1 << 16


def epilogue_supported(b: int, m: int) -> bool:
    """True when the fused epilogue kernel handles a [b, m] blend. Shared
    by the trace-time guard in scoring/pipeline.py and the host-side
    fallback counting in FraudScorer.dispatch_assembled."""
    return 0 < b <= _MAX_EPILOGUE_ROWS and m >= 1


# decision codes ride the kernel as exact small floats — all four are
# module-level host ints (features/rules.py), never device values
_APPROVE_F = float(APPROVE)                  # rtfd-lint: allow[d2h] host int constant
_MONITOR_F = float(APPROVE_WITH_MONITORING)  # rtfd-lint: allow[d2h] host int constant
_REVIEW_F = float(REVIEW)                    # rtfd-lint: allow[d2h] host int constant
_DECLINE_F = float(DECLINE)                  # rtfd-lint: allow[d2h] host int constant


def _rule_ladder(prob, decline, review, monitor):
    """Probability rungs only (no confidence clause) — exactly the host
    rules-only recompute in FraudScorer._build_responses."""
    return jnp.where(
        prob >= decline, _DECLINE_F,
        jnp.where(prob >= review, _REVIEW_F,
                  jnp.where(prob >= monitor, _MONITOR_F, _APPROVE_F)))


def _risk_code_f32(prob):
    code = jnp.zeros_like(prob)
    for t in RISK_LEVEL_THRESHOLDS:
        code = code + (prob >= t).astype(jnp.float32)
    return code


def epilogue_reference(preds: jax.Array, valid: jax.Array, rule: jax.Array,
                       params) -> Dict[str, jax.Array]:
    """XLA oracle: the exact functions the kernel fuses — ensemble
    combine + explanation contributions + the rules-only ladder."""
    out = dict(combine_predictions(preds, valid, params,
                                   with_confidences=False))
    out["model_contributions"] = params.weights[None, :] * preds
    out["rule_decision"] = _rule_ladder(
        rule, params.decline_threshold, params.review_threshold,
        params.monitor_threshold).astype(jnp.int32)
    out["rule_risk"] = risk_level_code(rule)
    return out


def combine_matrix(preds, vf, rule, wvec, cm, *,
                   strategy, fraud_threshold, confidence_threshold,
                   decline, review, monitor):
    """On-chip ensemble combine -> the [B, M+6] epilogue matrix.

    Operands: preds/vf f32[B, M], rule f32[B, 1], wvec/cm f32[1, M];
    statics are EnsembleParams' pytree_node=False fields.
    """
    # per-model confidence + masked weights (ensemble/combine.py:94-112)
    conf = jnp.minimum(1.0, jnp.abs(preds - 0.5) * 2.0 * cm) * vf
    w = wvec * vf

    # weighted average
    w_total = w.sum(axis=1, keepdims=True)                   # [B, 1]
    wa_prob = jnp.where(w_total > 0,
                        (preds * w).sum(axis=1, keepdims=True)
                        / jnp.maximum(w_total, 1e-12), 0.5)
    wa_conf = jnp.where(w_total > 0,
                        (conf * w).sum(axis=1, keepdims=True)
                        / jnp.maximum(w_total, 1e-12), 0.0)

    # voting
    n_valid = vf.sum(axis=1, keepdims=True)
    votes = (((preds > fraud_threshold).astype(jnp.float32)) * vf).sum(
        axis=1, keepdims=True)
    vote_prob = jnp.where(n_valid > 0,
                          votes / jnp.maximum(n_valid, 1.0), 0.0)
    vote_conf = jnp.where(n_valid > 0,
                          conf.sum(axis=1, keepdims=True)
                          / jnp.maximum(n_valid, 1.0), 0.0)

    # stacking (falls back to weighted average at zero total confidence)
    conf_total = conf.sum(axis=1, keepdims=True)
    stack_prob = jnp.where(conf_total > 0,
                           (preds * conf).sum(axis=1, keepdims=True)
                           / jnp.maximum(conf_total, 1e-12), wa_prob)
    stack_conf = jnp.where(conf_total > 0,
                           conf_total / jnp.maximum(n_valid, 1.0), wa_conf)

    if strategy == WEIGHTED_AVERAGE:
        prob, confidence = wa_prob, wa_conf
    elif strategy == VOTING:
        prob, confidence = vote_prob, vote_conf
    else:
        prob, confidence = stack_prob, stack_conf

    # decision + risk ladders (ints ride as exact small floats)
    by_prob = _rule_ladder(prob, decline, review, monitor)
    decision = jnp.where(confidence < confidence_threshold,
                         _REVIEW_F, by_prob)
    risk = _risk_code_f32(prob)

    contributions = wvec * preds                             # [B, M]
    rule_decision = _rule_ladder(rule, decline, review, monitor)
    rule_risk = _risk_code_f32(rule)

    return jnp.concatenate(
        [prob, confidence, decision, risk, contributions,
         rule_decision, rule_risk], axis=1)


def _epilogue_kernel(preds_ref, vf_ref, rule_ref, w_ref, cm_ref, o_ref, *,
                     strategy, fraud_threshold, confidence_threshold,
                     decline, review, monitor):
    o_ref[...] = combine_matrix(
        preds_ref[...], vf_ref[...], rule_ref[...], w_ref[...], cm_ref[...],
        strategy=strategy, fraud_threshold=fraud_threshold,
        confidence_threshold=confidence_threshold, decline=decline,
        review=review, monitor=monitor)


@functools.partial(jax.jit, static_argnames=(
    "strategy", "fraud_threshold", "confidence_threshold",
    "decline", "review", "monitor", "interpret"))
def _epilogue_call(preds, vf, rule2, w2, cm2, strategy, fraud_threshold,
                   confidence_threshold, decline, review, monitor,
                   interpret):
    b, m = preds.shape
    kernel = functools.partial(
        _epilogue_kernel, strategy=strategy, fraud_threshold=fraud_threshold,
        confidence_threshold=confidence_threshold, decline=decline,
        review=review, monitor=monitor)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, m), lambda i: (0, 0)),
            pl.BlockSpec((b, m), lambda i: (0, 0)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, m + 6), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m + 6), jnp.float32),
        interpret=interpret,
    )(preds, vf, rule2, w2, cm2)


def fused_epilogue(preds: jax.Array, valid: jax.Array, rule: jax.Array,
                   params, interpret: bool = False) -> Dict[str, jax.Array]:
    """Fused on-chip combine -> the epilogue_reference dict.

    ``params`` is an ensemble.combine.EnsembleParams; its static fields
    (strategy + thresholds) close over the kernel as compile-time
    constants, its array fields (weights, confidence multipliers) ride as
    operands. Column layout of the kernel's [B, M+6] output:
    prob, confidence, decision, risk, contributions x M, rule_decision,
    rule_risk. Callers must pre-check ``epilogue_supported``.
    """
    b, m = preds.shape
    if not epilogue_supported(b, m):
        raise ValueError(f"unsupported epilogue shape [{b},{m}]")
    if valid.ndim == 1:
        valid = jnp.broadcast_to(valid[None, :], preds.shape)
    out = _epilogue_call(
        preds.astype(jnp.float32), valid.astype(jnp.float32),
        rule.astype(jnp.float32)[:, None],
        params.weights.astype(jnp.float32)[None, :],
        params.confidence_multipliers.astype(jnp.float32)[None, :],
        strategy=int(params.strategy),
        fraud_threshold=float(params.fraud_threshold),        # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        confidence_threshold=float(params.confidence_threshold),  # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        decline=float(params.decline_threshold),              # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        review=float(params.review_threshold),                # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        monitor=float(params.monitor_threshold),              # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        interpret=interpret,
    )
    return {
        "fraud_probability": out[:, 0],
        "confidence": out[:, 1],
        "decision": out[:, 2].astype(jnp.int32),
        "risk_level": out[:, 3].astype(jnp.int32),
        "model_contributions": out[:, 4:4 + m],
        "rule_decision": out[:, 4 + m].astype(jnp.int32),
        "rule_risk": out[:, 5 + m].astype(jnp.int32),
    }
