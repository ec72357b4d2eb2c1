"""Plain float32 reference of the five-branch ensemble whose text branch is
ZAYA1-8B's block: NumPy and SciPy's ``expit`` / ``erf``, no JAX.

What ``zaya1-8b-s128`` is held to. From the same weights and the same
assembled inputs it computes what the served program computes, the text
branch in the textbook form of the equations below and in float32
throughout, sharing no line with ``models/``, ``ops/`` or ``scoring/``. It
reads the weights by the parameter names ``models/zaya.py`` stores them
under: those are the data format, not the arithmetic. The stored bfloat16
weights are widened to float32 one layer (and one expert) at a time.

Per layer, on the residual ``h`` ``[B, T, hidden]`` (text right-padded):

- CCA attention, ``x = rms(h, input_layernorm)``: ``q~ = x W_Q``, ``k~ = x
  W_K``; ``c = [q~ ; k~]`` through a causal depthwise convolution (kernel
  ``cca_time0``: ``c'[t] = sum_j w0[j] * c[t - (n - 1) + j]``, zero before
  position 0) and a causal per-head convolution (kernel ``cca_time1``, each
  tap a ``D x D`` matrix per head, by a loop over heads and taps); ``q = q^ +
  (q~ + repeat(k~)) / 2``, ``k = k^ + (mean over the group's query heads of
  q~ + k~) / 2``; per head ``sqrt(D) q / |q|`` and ``tau sqrt(D) k / |k|``
  (``rms_norm_eps`` under the root); rotate-half RoPE on the first
  ``partial_rotary_factor x D`` dims; the first half of the value heads
  from ``x[t]``, the second from ``x[t - 1]``; keys and values REPEATED to
  the query heads; causal AND key mask; ``h += ctx W_O``.
- routed experts, ``x = rms(h, post_attention_layernorm)``: ``r = x
  W_down`` (+ ``gamma * r_previous`` after the first layer, in slot order),
  ``s = softmax(W_3 gelu(W_2 gelu(W_1 rms(r, router_norm))))`` with the
  exact GELU, the expert ``argmax(s + b)``, ``h += s[e] down_e(silu(gate_e
  x) * up_e x)``: every expert's rows by a plain loop over experts.
- after the last layer ``rms(h, norm)`` at the last real token,
  ``Linear(hidden -> 2)``, ``softmax[:, 1]``.

The four other branches, the rules and the blend are
``olmoe_reference.py``'s (the same five-branch ensemble around another text
branch): loaded from that file, not copied a third time.

``text_branch(..., trace=[])`` also appends each layer's chosen expert
(``i64[tokens, 1]``) for the routing comparison of
``tests/zaya1_control.py``; ``_matmul`` is the one seam that control lowers
(every projection, the per-head convolution and the expert matmuls; not the
router, which the configuration states in float32).
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np
from scipy.special import erf, expit      # SciPy comes with JAX

F32 = np.float32


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


def _gelu(x: np.ndarray) -> np.ndarray:
    return (0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))).astype(F32)


# ---------------------------------------------------------------- text branch
def _matmul(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Every projection, per-head convolution tap and expert matmul of the
    text branch (not the router's): float32 here; the control rounds both
    operands below."""
    return x @ w


def _rms(x: np.ndarray, w, eps: float) -> np.ndarray:
    return x / np.sqrt((x * x).mean(axis=-1, keepdims=True) + F32(eps)) * _a(w)


def _before(x: np.ndarray, by: int) -> np.ndarray:
    """``y[:, t] = x[:, t - by]``, zero before position 0."""
    if by == 0:
        return x
    y = np.zeros_like(x)
    y[:, by:] = x[:, :-by]
    return y


def _convolutions(layer: Dict[str, Any], c: np.ndarray, heads: int, d: int
                  ) -> np.ndarray:
    """``c`` ``[B, T, heads * D]`` through the depthwise, then the per-head
    causal convolution."""
    taps = _a(layer["conv_depthwise"])
    n0 = taps.shape[0]
    c = sum(_before(c, n0 - 1 - j) * taps[j] for j in range(n0))
    w = _a(layer["conv_grouped"])                     # [heads, n1 * D, D]
    n1 = w.shape[1] // d
    out = np.zeros_like(c)
    for g in range(heads):
        mine = c[..., g * d:(g + 1) * d]
        for j in range(n1):
            out[..., g * d:(g + 1) * d] += _matmul(
                _before(mine, n1 - 1 - j), w[g, j * d:(j + 1) * d])
    return out


def _rope(x: np.ndarray, theta: float, rot: int) -> np.ndarray:
    """Rotary positions 0..T-1 on the first ``rot`` dims of ``[B, heads, T,
    D]``, rotate-half pairing (i, i + rot/2), ``inv_freq_i = theta ** (-2i /
    rot)``; the other dims pass through."""
    t = x.shape[-2]
    inv_freq = float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = np.cos(angle).astype(F32), np.sin(angle).astype(F32)
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., rot:]], axis=-1)


def _unit_heads(x: np.ndarray, eps: float) -> np.ndarray:
    """``sqrt(D) x / |x|`` per head (last axis)."""
    return x / np.sqrt((x * x).mean(axis=-1, keepdims=True) + F32(eps))


def _attention(layer: Dict[str, Any], h: np.ndarray, visible: np.ndarray,
               cfg: Dict[str, Any]) -> np.ndarray:
    b, t, _ = h.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    group, eps = heads // kv, cfg["rms_norm_eps"]
    theta = cfg["rope_parameters"]["hybrid"]["rope_theta"]
    rot = int(d * cfg["partial_rotary_factor"])
    x = _rms(h, layer["input_layernorm"], eps)
    q_lat = _matmul(x, _a(layer["q_proj"]))
    k_lat = _matmul(x, _a(layer["k_proj"]))
    mixed = _convolutions(layer, np.concatenate([q_lat, k_lat], axis=-1),
                          heads + kv, d)
    q_pre = q_lat.reshape(b, t, heads, d)
    k_pre = k_lat.reshape(b, t, kv, d)
    q = mixed[..., :heads * d].reshape(b, t, heads, d) + F32(0.5) * (
        q_pre + np.repeat(k_pre, group, axis=2))
    k = mixed[..., heads * d:].reshape(b, t, kv, d) + F32(0.5) * (
        q_pre.reshape(b, t, kv, group, d).mean(axis=3) + k_pre)
    q = _unit_heads(q, eps)
    k = _unit_heads(k, eps) * _a(layer["temperature"])[None, None, :, None]
    q = _rope(q.transpose(0, 2, 1, 3), theta, rot)
    k = _rope(k.transpose(0, 2, 1, 3), theta, rot)
    w_v = _a(layer["v_proj"])
    half = w_v.shape[1] // 2
    v = np.concatenate([_matmul(x, w_v[:, :half]),
                        _matmul(_before(x, 1), w_v[:, half:])], axis=-1)
    v = v.reshape(b, t, kv, d).transpose(0, 2, 1, 3)
    k, v = np.repeat(k, group, axis=1), np.repeat(v, group, axis=1)
    scores = q @ k.transpose(0, 1, 3, 2) / F32(math.sqrt(d))
    scores = np.where(visible, scores, F32(-1e30))
    ctx = (_softmax(scores) @ v).transpose(0, 2, 1, 3).reshape(b, t, heads * d)
    return h + _matmul(ctx, _a(layer["o_proj"]))


def _router(layer: Dict[str, Any], x: np.ndarray,
            previous: Optional[np.ndarray], eps: float):
    """``(s [tokens, experts], r [tokens, router_hidden])``."""
    r = x @ _a(layer["router_down"])
    if previous is not None:
        r = r + _a(layer["router_gamma"]) * previous
    z = _rms(r, layer["router_norm"], eps)
    z = _gelu(z @ _a(layer["router_w1"]))
    z = _gelu(z @ _a(layer["router_w2"]))
    return _softmax(z @ _a(layer["router_w3"])), r


def _experts(layer: Dict[str, Any], x: np.ndarray, s: np.ndarray,
             trace: Optional[List[np.ndarray]]) -> np.ndarray:
    """The top-1 sparse block on ``x`` ``[tokens, hidden]``."""
    chosen = np.argmax(s + _a(layer["router_bias"]), axis=-1)
    if trace is not None:
        trace.append(chosen[:, None])
    y = np.zeros_like(x)
    for e in range(s.shape[1]):
        tokens = np.nonzero(chosen == e)[0]
        if not len(tokens):
            continue
        xe = x[tokens]
        gate = _matmul(xe, _a(layer["gate_proj"][e]))
        up = _matmul(xe, _a(layer["up_proj"][e]))
        hidden = gate * expit(gate).astype(F32) * up        # silu(gate) * up
        y[tokens] = s[tokens, e][:, None] * _matmul(
            hidden, _a(layer["down_proj"][e]))
    return y


def text_branch(zaya: Dict[str, Any], token_ids, token_mask,
                cfg: Dict[str, Any],
                trace: Optional[List[np.ndarray]] = None) -> np.ndarray:
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    b, t = ids.shape
    h = _a(zaya["embed_tokens"])[ids]
    width, eps = h.shape[-1], cfg["rms_norm_eps"]
    visible = np.tril(np.ones((t, t), bool))[None, None] \
        & mask[:, None, None, :]
    r = None
    for layer in zaya["layers"]:
        h = _attention(layer, h, visible, cfg)
        x = _rms(h, layer["post_attention_layernorm"], eps).reshape(b * t,
                                                                    width)
        s, r = _router(layer, x, r, eps)
        h = h + _experts(layer, x, s, trace).reshape(b, t, width)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    pooled = _rms(h[np.arange(b), last], zaya["norm"], eps)
    return _softmax(pooled @ _a(zaya["score"]))[:, 1].astype(F32)


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: of the sizes the
    weights' shapes do not carry, this architecture needs the head counts
    and size, the rotary share and base, and the norm's epsilon."""
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
