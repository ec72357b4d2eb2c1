"""Plain float32 reference of the five-branch ensemble whose text branch is
OLMoE-1B-7B's block: NumPy and SciPy's ``expit``, no JAX.

What ``olmoe-1b-7b-s128`` is held to. From the same weights and the same
assembled inputs it computes what the served program computes — every
branch's probability, the rule score, the blend, its confidence and the
decision — in the textbook form of each model and in float32 throughout,
sharing no line with ``models/``, ``ops/``, ``ensemble/`` or
``features/rules.py``. It reads the weights and the batch by the field names
of the program's containers (``ScoringModels``, ``ScoreBatch``) and the text
branch's by the Hugging Face checkpoint's parameter names: those are the
data format, not the arithmetic.

- text branch: the OLMoE block as ``allenai/OLMoE-1B-7B-0125-Instruct``'s
  ``config.json`` and ``modeling_olmoe.py`` give it. Per layer ``h += o_proj(
  attn(q, k, v))`` with ``x = rms(h, input_layernorm)``, ``q = rms(q_proj(x),
  q_norm)``, ``k = rms(k_proj(x), k_norm)`` (over the whole hidden width),
  rotate-half RoPE per head, causal AND key mask; then ``h += sum over the
  top-8 experts e of p_e * down_e(silu(gate_e(x)) * up_e(x))`` with ``x =
  rms(h, post_attention_layernorm)`` and ``p = softmax(x W_router)`` over all
  64 experts, NOT renormalised over the 8. Every expert's rows by a plain
  loop over experts. After the last layer ``rms(h, norm)`` at the last real
  token (right-padded text), ``Linear(hidden -> 2)``, ``softmax[:, 1]``.
  The stored bfloat16 weights are widened to float32 one layer at a time.
- the four other branches, the rules and the blend as
  ``ensemble_reference.py`` computes them: copied here, not imported, so the
  two configurations' references can part ways.

``text_branch(..., trace=[])`` also appends each layer's chosen experts
(``i64[tokens, 8]``, sorted) for the routing comparison of
``tests/olmoe_control.py``; ``_matmul`` is the one seam that control lowers.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np
from scipy.special import expit           # SciPy comes with JAX

F32 = np.float32
BRANCHES = ("xgboost_primary", "lstm_sequential", "bert_text", "graph_neural",
            "isolation_forest")
DECISIONS = ("APPROVE", "APPROVE_WITH_MONITORING", "REVIEW", "DECLINE")


def _a(x, dtype=F32) -> np.ndarray:
    return np.asarray(x, dtype)


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return expit(x).astype(F32)


def _softmax(x: np.ndarray) -> np.ndarray:
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


# ---------------------------------------------------------------- text branch
def _matmul(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Every projection and expert matmul of the text branch (not the
    router's): float32 here; the control rounds both operands below."""
    return x @ w


def _rms(x: np.ndarray, w, eps: float) -> np.ndarray:
    return x / np.sqrt((x * x).mean(axis=-1, keepdims=True) + F32(eps)) * _a(w)


def _rope(x: np.ndarray, theta: float) -> np.ndarray:
    """Rotary positions 0..T-1 on ``[B, heads, T, D]``, rotate-half pairing
    (i, i + D/2), ``inv_freq_i = theta ** (-2i / D)``."""
    t, d = x.shape[-2:]
    inv_freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = np.cos(angle).astype(F32), np.sin(angle).astype(F32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _experts(layer: Dict[str, Any], x: np.ndarray, top_k: int,
             trace: Optional[List[np.ndarray]]) -> np.ndarray:
    """The sparse block on ``x`` ``[tokens, hidden]``."""
    p = _softmax(x @ _a(layer["router"]))
    chosen = np.argsort(-p, axis=-1, kind="stable")[:, :top_k]
    if trace is not None:
        trace.append(np.sort(chosen, axis=-1))
    y = np.zeros_like(x)
    for e in range(p.shape[1]):
        tokens = np.nonzero((chosen == e).any(axis=-1))[0]
        if not len(tokens):
            continue
        xe = x[tokens]
        gate = _matmul(xe, _a(layer["gate_proj"][e]))
        up = _matmul(xe, _a(layer["up_proj"][e]))
        hidden = gate * _sigmoid(gate) * up                 # silu(gate) * up
        y[tokens] += p[tokens, e][:, None] * _matmul(
            hidden, _a(layer["down_proj"][e]))
    return y


def text_branch(olmoe: Dict[str, Any], token_ids, token_mask, *,
                n_heads: int, top_k: int, eps: float, theta: float,
                trace: Optional[List[np.ndarray]] = None) -> np.ndarray:
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    b, t = ids.shape
    h = _a(olmoe["embed_tokens"])[ids]
    width = h.shape[-1]
    d = width // n_heads
    causal = np.tril(np.ones((t, t), bool))
    visible = causal[None, None] & mask[:, None, None, :]

    def heads(x):
        return x.reshape(b, t, n_heads, d).transpose(0, 2, 1, 3)

    for layer in olmoe["layers"]:
        x = _rms(h, layer["input_layernorm"], eps)
        q = _rms(_matmul(x, _a(layer["q_proj"])), layer["q_norm"], eps)
        k = _rms(_matmul(x, _a(layer["k_proj"])), layer["k_norm"], eps)
        v = _matmul(x, _a(layer["v_proj"]))
        q, k, v = _rope(heads(q), theta), _rope(heads(k), theta), heads(v)
        scores = q @ k.transpose(0, 1, 3, 2) / F32(math.sqrt(d))
        scores = np.where(visible, scores, F32(-1e30))
        ctx = (_softmax(scores) @ v).transpose(0, 2, 1, 3).reshape(b, t, width)
        h = h + _matmul(ctx, _a(layer["o_proj"]))
        x = _rms(h, layer["post_attention_layernorm"], eps)
        h = h + _experts(layer, x.reshape(b * t, width), top_k,
                         trace).reshape(b, t, width)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    pooled = _rms(h[np.arange(b), last], olmoe["norm"], eps)
    return _softmax(pooled @ _a(olmoe["score"]))[:, 1].astype(F32)


# ------------------------------------------- the four other branches (copied)
def sequence_branch(lstm: Dict[str, Any], history, history_len) -> np.ndarray:
    seq, length = _a(history), np.asarray(history_len)
    b, t, _ = seq.shape
    w, bias = _a(lstm["w_gates"]), _a(lstm["b_gates"])
    n = w.shape[1] // 4
    h = np.zeros((b, n), F32)
    c = np.zeros((b, n), F32)
    for step in range(t):
        z = np.concatenate([seq[:, step], h], axis=-1) @ w + bias
        i, f, o = (_sigmoid(z[:, j * n:(j + 1) * n]) for j in (0, 1, 3))
        g = np.tanh(z[:, 2 * n:3 * n])
        c_new = f * c + i * g
        h_new = o * np.tanh(c_new)
        live = (step >= t - length)[:, None]       # front-padded history
        h, c = np.where(live, h_new, h), np.where(live, c_new, c)
    z = np.maximum(h @ _a(lstm["w_head1"]) + _a(lstm["b_head1"]), 0.0)
    return _sigmoid((z @ _a(lstm["w_head2"]) + _a(lstm["b_head2"]))[:, 0])


def _masked_mean(x: np.ndarray, mask: np.ndarray) -> np.ndarray:
    m = mask[..., None].astype(F32)
    return (x * m).sum(axis=-2) / np.maximum(m.sum(axis=-2), 1.0)


def graph_branch(gnn: Dict[str, Any], batch) -> np.ndarray:
    if "w_node_user" in gnn or batch.user_neigh2_feat is not None:
        raise ValueError("the reference covers the bipartite one-hop graph "
                         "branch; this configuration runs the typed one")

    def sage(w, b, own, around):
        return np.maximum(
            np.concatenate([own, around], axis=-1) @ _a(gnn[w]) + _a(gnn[b]),
            0.0)

    def centre(own, neigh, mask):
        neigh, mask = _a(neigh), np.asarray(mask, bool)
        # a one-hop neighbour has no sampled neighbourhood of its own
        frontier = sage("w_sage1", "b_sage1", neigh, np.zeros_like(neigh))
        return sage("w_sage2", "b_sage2", _a(own),
                    _masked_mean(frontier, mask))

    z = np.concatenate([
        centre(batch.user_feat, batch.user_neigh_feat, batch.user_neigh_mask),
        centre(batch.merchant_feat, batch.merch_neigh_feat,
               batch.merch_neigh_mask),
        _a(batch.features)], axis=-1)
    z = np.maximum(z @ _a(gnn["w_head1"]) + _a(gnn["b_head1"]), 0.0)
    return _sigmoid((z @ _a(gnn["w_head2"]) + _a(gnn["b_head2"]))[:, 0])


def _leaf_values(feature, threshold, leaf, x: np.ndarray) -> np.ndarray:
    """Value of the leaf each row reaches in each complete tree: [B, T]."""
    feature, threshold, leaf = (np.asarray(feature), _a(threshold), _a(leaf))
    n_trees, n_internal = feature.shape
    rows = np.arange(len(x))[:, None]
    trees = np.arange(n_trees)[None, :]
    node = np.zeros((len(x), n_trees), np.int64)
    while (node < n_internal).all():
        right = x[rows, feature[trees, node]] >= threshold[trees, node]
        node = 2 * node + 1 + right
    return leaf[trees, node - n_internal]


def trees_branch(trees, features) -> np.ndarray:
    x = _a(features)
    return _sigmoid(_a(trees.base_score) + _leaf_values(
        trees.feature, trees.threshold, trees.leaf, x).sum(axis=1))


def isolation_branch(forest, features) -> np.ndarray:
    x = _a(features)
    path = _leaf_values(forest.feature, forest.threshold,
                        forest.path_length, x).mean(axis=1)
    s = np.exp2(-path / _a(forest.c_psi))
    return (1.0 / (1.0 + np.exp(0.5 - s))).astype(F32)


# ------------------------------------------------------------- rules, blend
def rule_score(t) -> np.ndarray:
    """The reference system's rule table; ``t`` is the encoded transaction
    batch (``TransactionBatch``: profiles already joined)."""
    def f(name, dtype=F32):
        return np.asarray(getattr(t, name), dtype)

    has_user, has_merchant = f("has_user", bool), f("has_merchant", bool)
    hour = f("hour_of_day", np.int64)
    score = 0.5 * f("prior_fraud_score")
    score = score + np.where(
        has_user,
        0.2 * f("user_risk_score") + 0.1 * (f("account_age_days") < 30)
        + 0.15 * ~f("user_verified", bool),
        0.35)                  # unknown user: risk 0.5, new, unverified
    risk, rate = f("merchant_risk_code", np.int64), f("merchant_fraud_rate")
    score = score + np.where(
        has_merchant,
        0.2 * (risk == 2) + 0.1 * (risk == 1)
        + 0.4 * f("merchant_blacklisted", bool)
        + np.where(rate > 0.05, rate * 2.0, 0.0)
        + 0.15 * f("merchant_high_risk_category", bool),
        0.1)                   # unknown merchant: "medium"
    avg = f("user_avg_amount")
    large = has_user & (avg > 0) & (f("amount") / np.maximum(avg, 1e-9) > 5.0)
    new_device = (f("has_txn_fingerprint", bool) & has_user
                  & f("has_device_list", bool) & ~f("known_device", bool))
    odd_hour = (hour <= 5) | (hour >= 23)
    closed = has_merchant & f("has_op_hours", bool) & ~(
        (hour >= f("merchant_op_start", np.int64))
        & (hour <= f("merchant_op_end", np.int64)))
    score = (score + 0.15 * large + 0.1 * new_device + 0.05 * odd_hour
             + 0.1 * closed)
    return np.clip(score, 0.0, 1.0).astype(F32)


def blend(preds: np.ndarray, valid: np.ndarray, params) -> Dict[str, Any]:
    """Weighted average over the valid branches, its confidence, and the
    decision ladder."""
    if params.strategy != 0:
        raise ValueError("the reference covers the weighted-average blend")
    v = valid.astype(F32)
    w = _a(params.weights)[None, :] * v
    conf = np.minimum(1.0, np.abs(preds - 0.5) * 2.0
                      * _a(params.confidence_multipliers)[None, :]) * v
    total = w.sum(axis=1)
    some = total > 0
    prob = np.where(some, (preds * w).sum(axis=1) / np.maximum(total, 1e-12),
                    0.5).astype(F32)
    confidence = np.where(
        some, (conf * w).sum(axis=1) / np.maximum(total, 1e-12),
        0.0).astype(F32)
    rungs = {"decline": params.decline_threshold,
             "review": params.review_threshold,
             "monitor": params.monitor_threshold,
             "confidence": params.confidence_threshold}
    decision = np.where(
        prob >= rungs["decline"], 3,
        np.where(prob >= rungs["review"], 2,
                 np.where(prob >= rungs["monitor"], 1, 0)))
    decision = np.where(confidence < rungs["confidence"], 2, decision)
    return {"fraud_probability": prob, "confidence": confidence,
            "decision": decision, "rungs": rungs}


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: of the sizes the
    weights' shapes do not carry, this architecture needs the head count,
    the experts per token, the norm's epsilon and RoPE's base."""
    preds = np.stack([
        trees_branch(models.trees, batch.features),
        sequence_branch(models.lstm, batch.history, batch.history_len),
        text_branch(models.bert, batch.token_ids, batch.token_mask,
                    n_heads=cfg["num_attention_heads"],
                    top_k=cfg["num_experts_per_tok"],
                    eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"]),
        graph_branch(models.gnn, batch),
        isolation_branch(models.iforest, batch.features),
    ], axis=1)
    valid = (np.asarray(model_valid, bool)[None, :]
             & np.asarray(batch.valid, bool)[:, None])
    out = blend(preds, valid, params)
    out["branches"] = preds
    out["rule_score"] = rule_score(batch.txn)
    return out
