"""Plain float32 reference of the five-branch ensemble whose text branch is
JoyAI-LLM-Flash's block: NumPy and SciPy's ``expit``, no JAX.

What ``joyai-llm-flash-s2048`` is held to. From the same weights and the
same assembled inputs it computes what the served program computes, the text
branch in the textbook form of the equations below and in float32
throughout, sharing no line with ``models/``, ``ops/`` or ``scoring/``. It
reads the weights by the parameter names ``models/joyai.py`` stores them
under (the checkpoint's own: ``q_a_proj``, ``kv_a_proj_with_mqa``,
``kv_b_proj``, ``e_score_correction_bias``...) and every size from the
configuration file's keys: those are the data format, not the arithmetic.
The stored bfloat16 weights are widened to float32 one matrix (and one
expert) at a time.

Per layer ``l``, on the residual ``h`` ``[B, T, hidden]`` (text
right-padded), ``H = num_attention_heads``, ``n = qk_nope_head_dim``, ``r =
qk_rope_head_dim``, ``dv = v_head_dim``:

- ``x = rms(h, input_layernorm)``; ``c_q = x W_qa``; ``q = rms(c_q,
  q_a_layernorm) W_qb`` as ``[B, T, H, n + r]``, a head ``[q_nope | q_pe]``;
- ``[c_kv | k_pe] = x W_kva`` (the last ``r`` columns are ``k_pe``);
  ``rms(c_kv, kv_a_layernorm) W_kvb`` as ``[B, T, H, n + dv]``, a head
  ``[k_nope | v]``; ``k_pe`` is one head for all ``H``;
- RoPE on ``q_pe`` and ``k_pe``, ``inv_freq_i = rope_theta^(-2i/r)``,
  interleaved: the pair ``(2i, 2i + 1)`` of position ``t`` turns by ``t x
  inv_freq_i`` (``rope_scaling`` null: no factor anywhere);
- head ``g``'s score of query ``i`` against key ``j <= i``, never a padded
  key: ``(q_nope_i . k_nope_j + q_pe_i . k_pe_j) / sqrt(n + r)``, softmax,
  times ``v`` — one row, one head and one block of queries at a time (a
  block sees its keys whole: the plain softmax), so 2,048 positions fit;
- ``h += ctx W_o``; ``m = rms(h, post_attention_layernorm)``;
- ``l < first_k_dense_replace``: ``h += (silu(m W_gate) * m W_up) W_down``;
- else ``s = sigmoid(m W_r)`` over all ``n_routed_experts``; the chosen are
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  (``n_group`` 1, ``topk_group`` 1: the group-limited step of ``noaux_tc``
  keeps the one group, the identity); ``w = routed_scaling_factor x s_e /
  (sum over the chosen of s + 1e-20)`` (``norm_topk_prob``; the bias is in
  NO weight); ``h += sum over the chosen e of w_e E_e(m) + S(m)``, every
  expert's rows by a plain loop over experts;
- after the last layer ``rms(h, norm)`` at the last real token,
  ``Linear(hidden -> 2)``, ``softmax[:, 1]``.

The multi-token-prediction module is not part of this forward pass
(``not_run`` in the configuration file).

The four other branches, the rules and the blend are
``olmoe_reference.py``'s (the same five-branch ensemble around another text
branch): loaded from that file, not copied again.

``text_branch(..., trace=[])`` also appends each sparse layer's chosen
experts (``i64[tokens, k]``, sorted) and, under ``"unbiased"``, what the
scores alone would have chosen, for ``tests/joyai_control.py``; ``_matmul``
is the seam that control lowers (every projection, both contractions of a
score, the weighted sum, the dense MLP, the routed and the shared experts;
not the router, which the configuration states in float32), ``_expert`` the
one it lowers to read the routed experts' matmuls ALONE.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np
from scipy.special import expit           # SciPy comes with JAX

F32 = np.float32
QUERY_BLOCK = 512


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


def _expert(x: np.ndarray, gate, up, down) -> np.ndarray:
    """One ROUTED expert on its rows: the seam ``tests/joyai_control.py``
    lowers alone, beside ``_matmul`` for everything."""
    return _swiglu(x, gate, up, down)


def _rope(x: np.ndarray, theta: float) -> np.ndarray:
    """Interleaved rotary positions 0..T-1 on the last axis of ``x`` ``[B,
    T, ..., r]`` (axis 1 is the position): dims ``2i`` and ``2i + 1`` are a
    pair."""
    t, r = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    shape = (1, t) + (1,) * (x.ndim - 3) + (r // 2,)
    cos = np.cos(angle).astype(F32).reshape(shape)
    sin = np.sin(angle).astype(F32).reshape(shape)
    out = np.empty_like(x)
    out[..., 0::2] = x[..., 0::2] * cos - x[..., 1::2] * sin
    out[..., 1::2] = x[..., 1::2] * cos + x[..., 0::2] * sin
    return out


def _core(q_nope: np.ndarray, q_pe: np.ndarray, k_nope: np.ndarray,
          k_pe: np.ndarray, v: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Causal latent attention: ``q_nope``, ``k_nope`` ``[B, T, H, n]``,
    ``q_pe`` ``[B, T, H, r]``, the ONE shared ``k_pe`` ``[B, T, r]``, ``v``
    ``[B, T, H, dv]`` -> ``[B, T, H, dv]``. One row, one head and one block
    of queries at a time; a block takes the keys up to its last query's
    own."""
    b, t, heads, n = q_nope.shape
    scale = F32(1.0 / math.sqrt(n + q_pe.shape[-1]))
    out = np.zeros_like(v)
    pos = np.arange(t)
    for row in range(b):
        for g in range(heads):
            for start in range(0, t, QUERY_BLOCK):
                stop = min(start + QUERY_BLOCK, t)
                visible = ((pos[None, :stop] <= pos[start:stop, None])
                           & mask[row, None, :stop])
                scores = (_matmul(q_nope[row, start:stop, g],
                                  k_nope[row, :stop, g].T)
                          + _matmul(q_pe[row, start:stop, g],
                                    k_pe[row, :stop].T)) * scale
                scores = np.where(visible, scores, F32(-1e30))
                out[row, start:stop, g] = _matmul(_softmax(scores),
                                                  v[row, :stop, g])
    return out


def _attention(layer: Dict[str, Any], h: np.ndarray, mask: np.ndarray,
               cfg: Dict[str, Any]) -> np.ndarray:
    b, t, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    n, r, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    x = _rms(h, layer["input_layernorm"], eps)
    c_q = _rms(_matmul(x, _a(layer["q_a_proj"])), layer["q_a_layernorm"], eps)
    q = _matmul(c_q, _a(layer["q_b_proj"])).reshape(b, t, heads, n + r)
    down = _matmul(x, _a(layer["kv_a_proj_with_mqa"]))
    c_kv = _rms(down[..., :rank], layer["kv_a_layernorm"], eps)
    kv = _matmul(c_kv, _a(layer["kv_b_proj"])).reshape(b, t, heads, n + dv)
    theta = cfg["rope_theta"]
    ctx = _core(q[..., :n], _rope(q[..., n:], theta), kv[..., :n],
                _rope(down[..., rank:], theta), kv[..., n:], mask)
    return h + _matmul(ctx.reshape(b, t, heads * dv), _a(layer["o_proj"]))


def _sparse(layer: Dict[str, Any], x: np.ndarray, cfg: Dict[str, Any],
            trace: Optional[List[Dict[str, np.ndarray]]]) -> np.ndarray:
    """The routed experts, all held, beside the shared expert, on ``x``
    ``[tokens, hidden]``."""
    top_k = cfg["num_experts_per_tok"]
    s = expit(x @ _a(layer["router"])).astype(F32)   # the router's own matmul
    biased = s + _a(layer["e_score_correction_bias"])
    chosen = np.argsort(-biased, axis=-1, kind="stable")[:, :top_k]
    if trace is not None:
        trace.append({
            "chosen": np.sort(chosen, axis=-1),
            "unbiased": np.sort(np.argsort(-s, axis=-1, kind="stable")
                                [:, :top_k], axis=-1)})
    w = np.take_along_axis(s, chosen, axis=-1)       # the score, not biased
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + F32(1e-20))
    w = w * F32(cfg["routed_scaling_factor"])
    y = _swiglu(x, layer["shared_gate"], layer["shared_up"],
                layer["shared_down"])
    for e in range(layer["gate_proj"].shape[0]):
        tokens, slot = np.nonzero(chosen == e)
        if len(tokens):
            y[tokens] += w[tokens, slot][:, None] * _expert(
                x[tokens], layer["gate_proj"][e], layer["up_proj"][e],
                layer["down_proj"][e])
    return y


def text_branch(joyai: Dict[str, Any], token_ids, token_mask,
                cfg: Dict[str, Any],
                trace: Optional[List[Dict[str, np.ndarray]]] = None
                ) -> np.ndarray:
    if (cfg["n_group"], cfg["topk_group"]) != (1, 1) \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or cfg["rope_scaling"] is not None or not cfg["rope_interleave"]:
        raise ValueError("joyai_reference holds a sigmoid noaux_tc router of "
                         "one group and plain interleaved RoPE")
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    b, t = ids.shape
    h = _a(joyai["embed_tokens"])[ids]
    width, eps = h.shape[-1], cfg["rms_norm_eps"]
    for index, layer in enumerate(joyai["layers"]):
        h = _attention(layer, h, mask, cfg)
        m = _rms(h, layer["post_attention_layernorm"], eps)
        if index < cfg["first_k_dense_replace"]:
            h = h + _swiglu(m, layer["mlp_gate"], layer["mlp_up"],
                            layer["mlp_down"])
        else:
            h = h + _sparse(layer, m.reshape(b * t, width), cfg,
                            trace).reshape(b, t, width)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    pooled = _rms(h[np.arange(b), last], joyai["norm"], eps)
    return _softmax(pooled @ _a(joyai["score"]))[:, 1].astype(F32)


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: this
    architecture reads its latent ranks, its head's three widths, its rope
    and its routing constants from it."""
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
