"""Plain float32 reference of the five-branch ensemble whose text branch is
Laguna-S-2.1's block: NumPy and SciPy's ``expit``, no JAX.

What ``laguna-s-2.1-s2048`` is held to. From the same weights and the same
assembled inputs it computes what the served program computes, the text
branch in the textbook form of the equations below and in float32
throughout, sharing no line with ``models/``, ``ops/`` or ``scoring/``. It
reads the weights by the parameter names ``models/laguna.py`` stores them
under and every per-layer shape from the configuration file's own lists:
those are the data format, not the arithmetic. The stored bfloat16 weights
are widened to float32 one matrix (and one expert) at a time.

Per layer ``l``, on the residual ``h`` ``[B, T, hidden]`` (text right-padded),
``H_l = num_attention_heads_per_layer[l]``, ``D = head_dim``:

- ``a = rms(h, input_layernorm)``; ``q = a W_q``, ``k = a W_k``, ``v = a
  W_v`` (no bias, no QK-norm);
- rotate-half RoPE on the first ``partial_rotary_factor x D`` dims by
  ``rope_parameters[layer_types[l]]``: ``default`` with ``inv_freq_i =
  theta^(-2i/d)``; ``yarn`` with ``f_i = theta^(2i/d)``, ``c(n) = d ln(L /
  (2 pi n)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high
  = min(ceil(c(beta_slow)), d - 1)``, ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``, ``inv_freq_i = (1 - ramp_i) / f_i + ramp_i / (factor
  f_i)``, cos and sin times ``attention_factor``;
- query head ``g`` reads key-value head ``g // (H_l / kv)``; ``softmax(q
  k^T / sqrt(D))`` over the keys ``j <= i`` — on a ``sliding_attention``
  layer only ``i - sliding_window < j <= i`` — and never a padded key; one
  key-value head's group of query heads and one block of queries at a time
  (a block sees its keys whole: the plain softmax), so 2,048 positions fit;
- ``gamma = sigmoid(a W_g)`` ``[T, H_l]``, head ``g``'s context times
  ``gamma[:, g]``; ``h += ctx W_o``;
- ``m = rms(h, post_attention_layernorm)``. ``mlp_layer_types[l] ==
  "dense"``: ``h += (silu(m W_gate) * m W_up) W_down``. Else ``p =
  softmax(m W_r)`` over the router's whole width, the ``num_experts_per_tok``
  largest, ``w = moe_routed_scaling_factor * p_e / sum_chosen p``
  (``norm_topk_prob``), ``h += sum over the chosen experts e HELD HERE of
  w_e E_e(m) + S(m)``: the file's ``expert_share`` says which experts this
  chip holds (``index * num_experts`` on), the stacked weights hold exactly
  those, and what the absent experts would add is left out, as in the
  program. Every held expert's rows by a plain loop over experts.
- after the last layer ``rms(h, norm)`` at the last real token,
  ``Linear(hidden -> 2)``, ``softmax[:, 1]``.

The four other branches, the rules and the blend are
``olmoe_reference.py``'s (the same five-branch ensemble around another text
branch): loaded from that file, not copied again.

``text_branch(..., trace=[])`` also appends each sparse layer's chosen
experts (``i64[tokens, k]``, sorted, in the router's numbers) for the routing
comparison of ``tests/laguna_control.py``; ``_matmul`` is the one seam that
control lowers (every projection, both contractions of the core, the dense
MLP, the routed and the shared experts; not the router, which the
configuration states in float32).
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np
from scipy.special import expit           # SciPy comes with JAX

F32 = np.float32
QUERY_BLOCK = 256


def _sibling(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_bench_configs_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ensemble = _sibling("olmoe_reference")
BRANCHES = _ensemble.BRANCHES
DECISIONS = _ensemble.DECISIONS


def _a(x, dtype=F32) -> np.ndarray:
    return np.asarray(x, dtype)


def _softmax(x: np.ndarray) -> np.ndarray:
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


# ---------------------------------------------------------------- text branch
def _matmul(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Every projection, core contraction and MLP / expert matmul of the
    text branch (not the router's): float32 here; the control rounds both
    operands below."""
    return x @ w


def _rms(x: np.ndarray, w, eps: float) -> np.ndarray:
    return x / np.sqrt((x * x).mean(axis=-1, keepdims=True) + F32(eps)) * _a(w)


def _swiglu(x: np.ndarray, gate, up, down) -> np.ndarray:
    g = _matmul(x, _a(gate))
    return _matmul(g * expit(g).astype(F32) * _matmul(x, _a(up)), _a(down))


def inv_freq(rope: Dict[str, Any], d: int) -> np.ndarray:
    """The ``d / 2`` inverse frequencies of one ``rope_parameters`` entry,
    float64."""
    theta = float(rope["rope_theta"])
    f = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rope["rope_type"] == "default":
        return 1.0 / f
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def c(rotations: float) -> float:
        return (d * math.log(rope["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) / f + ramp / (rope["factor"] * f)


def _rope(x: np.ndarray, rope: Dict[str, Any]) -> np.ndarray:
    """Rotary positions 0..T-1 on the first ``partial_rotary_factor x D``
    dims of ``[B, T, heads, D]``, rotate-half pairing (i, i + rot/2); the
    other dims pass through."""
    t, d = x.shape[1], x.shape[-1]
    rot = int(d * rope["partial_rotary_factor"])
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq(rope, rot)[None]
    scale = float(rope.get("attention_factor", 1.0)) \
        if rope["rope_type"] == "yarn" else 1.0
    cos = (np.cos(angle) * scale).astype(F32)[None, :, None, :]
    sin = (np.sin(angle) * scale).astype(F32)[None, :, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., rot:]], axis=-1)


def _core(q: np.ndarray, k: np.ndarray, v: np.ndarray, mask: np.ndarray,
          window: Optional[int]) -> np.ndarray:
    """Causal grouped-key attention on ``q`` ``[B, T, H, D]``, ``k`` and
    ``v`` ``[B, T, kv, D]``: ``[B, T, H, D]``. One row, one key-value head's
    group and one block of queries at a time; a block takes the keys from
    the first its first query sees to its last query's own."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    group = heads // kv
    out = np.zeros_like(q)
    pos = np.arange(t)
    for row in range(b):
        for g in range(kv):
            kg, vg = k[row, :, g], v[row, :, g]               # [T, D]
            for start in range(0, t, QUERY_BLOCK):
                stop = min(start + QUERY_BLOCK, t)
                first = 0 if window is None else max(start - window + 1, 0)
                qi, kj = pos[start:stop, None], pos[None, first:stop]
                visible = (kj <= qi) & mask[row, None, first:stop]
                if window is not None:
                    visible &= kj > qi - window
                # the group's query heads side by side: [G * block, D]
                qs = q[row, start:stop, g * group:(g + 1) * group]
                qs = qs.transpose(1, 0, 2).reshape(-1, d)
                scores = _matmul(qs, kg[first:stop].T) / F32(math.sqrt(d))
                scores = np.where(np.tile(visible, (group, 1)), scores,
                                  F32(-1e30))
                ctx = _matmul(_softmax(scores), vg[first:stop])
                out[row, start:stop, g * group:(g + 1) * group] = \
                    ctx.reshape(group, stop - start, d).transpose(1, 0, 2)
    return out


def _attention(layer: Dict[str, Any], h: np.ndarray, mask: np.ndarray,
               index: int, cfg: Dict[str, Any]) -> np.ndarray:
    b, t, _ = h.shape
    heads = cfg["num_attention_heads_per_layer"][index]
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][index]
    rope = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    a = _rms(h, layer["input_layernorm"], cfg["rms_norm_eps"])
    q = _rope(_matmul(a, _a(layer["q_proj"])).reshape(b, t, heads, d), rope)
    k = _rope(_matmul(a, _a(layer["k_proj"])).reshape(b, t, kv, d), rope)
    v = _matmul(a, _a(layer["v_proj"])).reshape(b, t, kv, d)
    gamma = expit(_matmul(a, _a(layer["g_proj"]))).astype(F32)  # [B, T, H]
    ctx = _core(q, k, v, mask, window) * gamma[..., None]
    return h + _matmul(ctx.reshape(b, t, heads * d), _a(layer["o_proj"]))


def _sparse(layer: Dict[str, Any], x: np.ndarray, cfg: Dict[str, Any],
            trace: Optional[List[np.ndarray]]) -> np.ndarray:
    """The routed experts this chip holds, beside the shared expert, on
    ``x`` ``[tokens, hidden]``."""
    top_k = cfg["num_experts_per_tok"]
    p = _softmax(x @ _a(layer["router"]))             # the router's whole width
    chosen = np.argsort(-p, axis=-1, kind="stable")[:, :top_k]
    if trace is not None:
        trace.append(np.sort(chosen, axis=-1))
    w = np.take_along_axis(p, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(axis=-1, keepdims=True)
    w = w * F32(cfg["moe_routed_scaling_factor"])
    held = layer["gate_proj"].shape[0]
    offset = cfg["expert_share"]["index"] * held
    y = _swiglu(x, layer["shared_gate"], layer["shared_up"],
                layer["shared_down"])
    for e in range(held):
        tokens, slot = np.nonzero(chosen == offset + e)
        if len(tokens):
            y[tokens] += w[tokens, slot][:, None] * _swiglu(
                x[tokens], layer["gate_proj"][e], layer["up_proj"][e],
                layer["down_proj"][e])
    return y


def text_branch(laguna: Dict[str, Any], token_ids, token_mask,
                cfg: Dict[str, Any],
                trace: Optional[List[np.ndarray]] = None) -> np.ndarray:
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    b, t = ids.shape
    h = _a(laguna["embed_tokens"])[ids]
    width, eps = h.shape[-1], cfg["rms_norm_eps"]
    for index, layer in enumerate(laguna["layers"]):
        h = _attention(layer, h, mask, index, cfg)
        m = _rms(h, layer["post_attention_layernorm"], eps)
        if cfg["mlp_layer_types"][index] == "dense":
            h = h + _swiglu(m, layer["mlp_gate"], layer["mlp_up"],
                            layer["mlp_down"])
        else:
            h = h + _sparse(layer, m.reshape(b * t, width), cfg,
                            trace).reshape(b, t, width)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    pooled = _rms(h[np.arange(b), last], laguna["norm"], eps)
    return _softmax(pooled @ _a(laguna["score"]))[:, 1].astype(F32)


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: this
    architecture reads its per-layer lists, its rope groups, its window, its
    routing constants and its share of the experts from it."""
    e = _ensemble
    preds = np.stack([
        e.trees_branch(models.trees, batch.features),
        e.sequence_branch(models.lstm, batch.history, batch.history_len),
        text_branch(models.bert, batch.token_ids, batch.token_mask, cfg),
        e.graph_branch(models.gnn, batch),
        e.isolation_branch(models.iforest, batch.features),
    ], axis=1)
    valid = (np.asarray(model_valid, bool)[None, :]
             & np.asarray(batch.valid, bool)[:, None])
    out = e.blend(preds, valid, params)
    out["branches"] = preds
    out["rule_score"] = e.rule_score(batch.txn)
    return out
