"""Weight-only int8 quantization for the BERT branch.

The text encoder is by far the largest branch of the fused program (its
analytic matmul FLOPs dwarf the other four branches'), and ``DevicePool`` replicates FULL f32 params onto every chip — so BERT
bytes are both the HBM cap on model size and the bulk of the hot-swap /
replication payload. Per the reduced-precision serving result in the 300M
predictions/sec paper (arXiv:2109.09541) and the repo's own precision
policy (bf16 matmuls / f32 layernorm+softmax, core/precision.py), the
weights can drop to int8 as long as quality is GATED, not assumed:

- **per-output-channel symmetric scales** for every dense kernel
  (``q/k/v/o/ffn1/ffn2``): ``scale[j] = max|w[:, j]| / 127``,
  ``q = round(w / scale)`` clipped to [-127, 127] — symmetric so dequant
  is one multiply, per-channel so one outlier column cannot crush the
  resolution of the rest;
- **per-row scales** for the embedding tables (``word_emb``/``pos_emb``):
  the gather pulls whole rows, so the row is the output channel;
- **dequant-to-bf16 at the matmul seam**: ``models/bert.py`` detects the
  quantized layout structurally and widens ``q * scale`` straight into
  the existing compute-dtype cast, so XLA fuses the dequant into the
  matmul read and the f32 weights never exist in HBM;
- layer norms, biases and the 2-logit classification head stay f32 — they
  are a rounding error in bytes and the head feeds the decision ladder
  directly.

Quantization itself runs HOST-SIDE at model-swap time (set_models /
checkpoint restore), never in the dispatch path: it is calibration work
(one pass over the weights), and the quantized pytree then replicates /
hot-swaps through the exact same score-lock discipline as f32 params.

The quality gate that makes this shippable is ``rtfd quant-drill``
(scoring/quant_drill.py): max quantized-vs-f32 score divergence pinned
below calibration noise, zero operating-point decision flips, AUC
unchanged on the committed quality protocol.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

__all__ = [
    "quantize_dense",
    "quantize_embedding",
    "quantize_bert_params",
    "is_quantized_bert",
    "bert_param_bytes",
    "quant_error_bound",
]

# int8 symmetric range: one code reserved so +/-scale*127 is symmetric
_QMAX = 127.0


def _channel_scales(w: np.ndarray, axis: int) -> np.ndarray:
    """Symmetric per-channel scales over ``axis`` (the reduction axis the
    scale must cover). A zero channel gets scale 1 so dequant stays exact
    zero instead of 0/0."""
    amax = np.max(np.abs(w), axis=axis)
    return np.where(amax > 0.0, amax / _QMAX, 1.0).astype(np.float32)


def quantize_dense(p: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize one dense layer dict ``{"w": f32[in, out], "b": ...}`` to
    ``{"qw": i8[in, out], "scale": f32[out], "b": ...}`` — per-OUTPUT-
    channel symmetric scales, bias untouched."""
    # rtfd-lint: allow[d2h] host-side weight calibration at model-swap time, never in the dispatch path
    w = np.asarray(p["w"], np.float32)
    scale = _channel_scales(w, axis=0)                      # [out]
    q = np.clip(np.rint(w / scale[None, :]), -_QMAX, _QMAX).astype(np.int8)
    return {"qw": q, "scale": scale, "b": p["b"]}


def quantize_embedding(w: Any) -> Dict[str, Any]:
    """Quantize an embedding table f32[rows, h] to ``{"qe": i8[rows, h],
    "scale": f32[rows]}`` — per-ROW scales (the gather's output channel
    is the row)."""
    # rtfd-lint: allow[d2h] host-side weight calibration at model-swap time, never in the dispatch path
    w = np.asarray(w, np.float32)
    scale = _channel_scales(w, axis=1)                      # [rows]
    q = np.clip(np.rint(w / scale[:, None]), -_QMAX, _QMAX).astype(np.int8)
    return {"qe": q, "scale": scale}


def quantize_bert_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize a ``models.bert.init_bert_params``-shaped pytree.

    Every per-layer dense (q/k/v/o/ffn1/ffn2) and both embedding tables go
    int8; layer norms, biases and the classification head (pre_classifier
    + classifier) stay f32. Idempotent: an already-quantized pytree is
    returned unchanged, so a hot-swap path can apply this unconditionally.
    """
    if is_quantized_bert(params):
        return params
    out: Dict[str, Any] = {
        "word_emb": quantize_embedding(params["word_emb"]),
        "pos_emb": quantize_embedding(params["pos_emb"]),
        "emb_ln": params["emb_ln"],
        "pre_classifier": params["pre_classifier"],
        "classifier": params["classifier"],
        "layers": [],
    }
    for layer in params["layers"]:
        out["layers"].append({
            "q": quantize_dense(layer["q"]),
            "k": quantize_dense(layer["k"]),
            "v": quantize_dense(layer["v"]),
            "o": quantize_dense(layer["o"]),
            "attn_ln": layer["attn_ln"],
            "ffn1": quantize_dense(layer["ffn1"]),
            "ffn2": quantize_dense(layer["ffn2"]),
            "ffn_ln": layer["ffn_ln"],
        })
    return out


def is_quantized_bert(params: Any) -> bool:
    """Structural detection of the quantized layout (the same detection
    the compute seam in ``models/bert.py`` uses): the word embedding is a
    ``{"qe", "scale"}`` dict instead of a bare array."""
    try:
        return isinstance(params["word_emb"], dict) \
            and "qe" in params["word_emb"]
    except (TypeError, KeyError, IndexError):
        return False


def bert_param_bytes(params: Any) -> int:
    """Total serialized parameter bytes of a (plain or quantized) BERT
    pytree — the number the ``quant_param_bytes`` Prometheus series and
    ``rtfd quant-drill`` report. Uses leaf ``nbytes``
    metadata only; never pulls device buffers."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            nbytes = np.dtype(np.float32).itemsize * int(np.size(leaf))
        total += int(nbytes)
    return total


def quant_error_bound(params: Dict[str, Any]) -> float:
    """Max absolute weight reconstruction error across quantized leaves —
    half an LSB per channel by construction; reported (not gated) by
    ``rtfd quant-drill`` as a sanity number."""
    if not is_quantized_bert(params):
        return 0.0
    scales = [params["word_emb"]["scale"], params["pos_emb"]["scale"]]
    for layer in params["layers"]:
        scales.extend(layer[key]["scale"]
                      for key in ("q", "k", "v", "o", "ffn1", "ffn2"))
    # rtfd-lint: allow[d2h] host-side calibration report over weight scales
    worsts = [float(np.max(np.asarray(s))) for s in scales]
    return 0.5 * max(worsts)
