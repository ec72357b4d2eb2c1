"""DistilBERT-style text encoder in pure JAX with blockwise attention.

The reference's ``bert_text`` branch is a DistilBERT sequence classifier
(config.py:165-170: distilbert-base-uncased, 2 labels; served path stubbed
random at model_manager.py:332-336; the real torch path lives in
bert_text_analyzer.py:179-226). This is the architecture rebuilt TPU-first:

- standard DistilBERT shape: 6 post-LN layers, 12 heads, hidden 768,
  GELU FFN 3072, learned positions, LayerNorm'd embeddings;
- attention runs through the Pallas blockwise kernel (ops/attention.py) on
  TPU, falling back to the XLA reference implementation elsewhere;
- classification head = pre_classifier(768->768, ReLU) -> classifier(768->2)
  on the [CLS] token, exactly DistilBertForSequenceClassification's head;
- bf16 matmuls / f32 layernorm+softmax per the precision policy.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from realtime_fraud_detection_tpu.models.text_encoder import (
    EVERY_PLANE,
    KernelSite,
    TextEncoder,
)
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    flash_supported,
    merge_heads,
    narrowest_supported_len,
    split_heads,
)
from realtime_fraud_detection_tpu.ops.dequant_matmul import (
    dequant_matmul,
    dequant_rows,
    matmul_supported,
    rows_supported,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 6
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    layer_norm_eps: float = 1e-12
    num_labels: int = 2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


TINY_CONFIG = BertConfig(hidden_size=128, num_layers=2, num_heads=2,
                         intermediate_size=256, vocab_size=30522)


def init_bert_params(key: jax.Array, config: BertConfig) -> Dict:
    """Truncated-normal(0.02) init, matching BERT convention."""
    h, ffn = config.hidden_size, config.intermediate_size

    def dense(k, shape):
        return {
            "w": jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * 0.02,
            "b": jnp.zeros((shape[-1],), jnp.float32),
        }

    def ln():
        return {"scale": jnp.ones((h,), jnp.float32), "bias": jnp.zeros((h,), jnp.float32)}

    keys = jax.random.split(key, 3 + 6 * config.num_layers)
    params: Dict = {
        "word_emb": jax.random.truncated_normal(
            keys[0], -2, 2, (config.vocab_size, h), jnp.float32) * 0.02,
        "pos_emb": jax.random.truncated_normal(
            keys[1], -2, 2, (config.max_position_embeddings, h), jnp.float32) * 0.02,
        "emb_ln": ln(),
        "layers": [],
        "pre_classifier": dense(keys[2], (h, h)),
    }
    for i in range(config.num_layers):
        k = keys[3 + 6 * i : 9 + 6 * i]
        params["layers"].append({
            "q": dense(k[0], (h, h)),
            "k": dense(k[1], (h, h)),
            "v": dense(k[2], (h, h)),
            "o": dense(k[3], (h, h)),
            "attn_ln": ln(),
            "ffn1": dense(k[4], (h, ffn)),
            "ffn2": dense(k[5], (ffn, h)),
            "ffn_ln": ln(),
        })
    params["classifier"] = dense(
        jax.random.fold_in(keys[2], 7), (h, config.num_labels)
    )
    return params


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(axis=-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"])


def _dense(x, p, compute_dtype, dequant_kernel="off", kernel_interpret=False):
    if "qw" in p:
        if dequant_kernel == "pallas":
            # hand-fused Pallas path (ops/dequant_matmul.py): the i8 weight
            # block dequantizes in VMEM right before the MXU dot, guarded
            # by the SAME supports() predicate the scorer's fallback
            # counters consult
            lead = x.shape[:-1]
            k, n = p["qw"].shape
            m = int(np.prod(lead)) if lead else 1
            if matmul_supported(m, k, n):
                y = dequant_matmul(
                    x.reshape(m, k), p["qw"], p["scale"], p["b"],
                    compute_dtype=compute_dtype, interpret=kernel_interpret)
                return y.reshape(*lead, n)
        # weight-only int8 (models/quant.py): dequantize per-output-channel
        # right at the compute-dtype seam — XLA fuses the (i8 -> bf16) *
        # scale widen into the matmul's weight read, so the full-precision
        # kernel never materializes in HBM
        w = p["qw"].astype(compute_dtype) * p["scale"].astype(compute_dtype)
        return x.astype(compute_dtype) @ w + p["b"]
    return x.astype(compute_dtype) @ p["w"].astype(compute_dtype) + p["b"]


def _embedding_rows(table, idx=None, length=None, dequant_kernel="off",
                    kernel_interpret=False):
    """Embedding lookup that understands both layouts: a bare f32 table,
    or the quantized ``{"qe": i8[rows, h], "scale": f32[rows]}`` form
    (per-row scales — the gather's output channel is the row). Returns
    f32 rows either way; ``idx`` gathers, ``length`` slices a prefix."""
    if isinstance(table, dict) and "qe" in table:
        if idx is not None:
            q, s = table["qe"][idx], table["scale"][idx]
        else:
            q, s = table["qe"][:length], table["scale"][:length]
        if dequant_kernel == "pallas":
            # the arbitrary-index gather stays an XLA i8 gather; the
            # per-row widen x scale runs through the Pallas kernel so only
            # i8 rows cross HBM at full width
            h = q.shape[-1]
            rows = int(np.prod(q.shape[:-1]))
            if rows_supported(rows, h):
                out = dequant_rows(q.reshape(rows, h), s.reshape(rows),
                                   interpret=kernel_interpret)
                return out.reshape(*q.shape[:-1], h)
        return q.astype(jnp.float32) * s[..., None]
    return table[idx] if idx is not None else table[:length]


def bert_encode(
    params: Dict,
    input_ids: jax.Array,       # i32[B, S]
    attention_mask: jax.Array,  # bool[B, S]
    config: BertConfig,
    use_pallas: bool = False,
    compute_dtype=jnp.bfloat16,
    attention_fn=None,
    dequant_kernel: str = "off",
    kernel_interpret: bool = False,
) -> jax.Array:
    """Hidden states f32[B, S, H].

    ``attention_fn(q, k, v, key_mask) -> ctx`` overrides the attention
    implementation — the hook context parallelism plugs into
    (``parallel.context.bert_context_parallel_predict`` passes ring
    attention here; everything else in the layer is per-token and shards
    along S for free).

    ``dequant_kernel``/``kernel_interpret`` select the hand-fused Pallas
    dequant path for int8 params (ops/dequant_matmul.py, KernelSettings);
    both are static and only consulted where the quantized layout is
    structurally present.
    """
    x = bert_embed(params, input_ids, config,
                   dequant_kernel=dequant_kernel,
                   kernel_interpret=kernel_interpret)
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            x = bert_layer(layer, x, attention_mask, config,
                           use_pallas=use_pallas,
                           compute_dtype=compute_dtype,
                           attention_fn=attention_fn,
                           dequant_kernel=dequant_kernel,
                           kernel_interpret=kernel_interpret)
    return x


def bert_embed(params: Dict, input_ids: jax.Array,
               config: BertConfig, dequant_kernel: str = "off",
               kernel_interpret: bool = False) -> jax.Array:
    """Token + position embeddings with the embedding layer norm — shared
    by the sequential and pipeline-parallel encoders."""
    s = input_ids.shape[1]
    with jax.named_scope(scopes.EMBED):
        x = (_embedding_rows(params["word_emb"], idx=input_ids,
                             dequant_kernel=dequant_kernel,
                             kernel_interpret=kernel_interpret)
             + _embedding_rows(params["pos_emb"], length=s,
                               dequant_kernel=dequant_kernel,
                               kernel_interpret=kernel_interpret)[None, :, :])
        return _layer_norm(x, params["emb_ln"], config.layer_norm_eps)


def bert_layer(
    layer: Dict,
    x: jax.Array,               # f32[B, S, H]
    attention_mask: jax.Array,  # bool[B, S]
    config: BertConfig,
    use_pallas: bool = False,
    compute_dtype=jnp.bfloat16,
    attention_fn=None,
    dequant_kernel: str = "off",
    kernel_interpret: bool = False,
) -> jax.Array:
    """One post-LN transformer block — the unit the pipeline-parallel
    schedule (parallel/pipeline.bert_pipeline_encode) spans over stages."""
    s = x.shape[1]
    dk = dict(dequant_kernel=dequant_kernel, kernel_interpret=kernel_interpret)

    # the fused core takes the projections' own [B, S, H*D] layout (the head
    # split happens in VMEM) at the shapes flash_supported admits; every
    # other shape, and every attention_fn, runs on the split layout
    fused = (use_pallas and attention_fn is None
             and flash_supported(s, config.head_dim, config.num_heads))

    # the four kernel scopes of a layer (obs/scopes.py): metadata only
    with jax.named_scope(scopes.ATTN_PROJ):
        q = _dense(x, layer["q"], compute_dtype, **dk)
        k = _dense(x, layer["k"], compute_dtype, **dk)
        v = _dense(x, layer["v"], compute_dtype, **dk)
        if fused:
            # the MXU rounds its operands to compute_dtype either way;
            # rounding here halves what crosses HBM
            q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
        else:
            qh, kh, vh = (split_heads(t, config.num_heads)
                          for t in (q, k, v))
    with jax.named_scope(scopes.ATTN_CORE):
        if fused:
            ctx = flash_attention(q, k, v, attention_mask,
                                  num_heads=config.num_heads,
                                  interpret=kernel_interpret)
        elif attention_fn is not None:
            ctx = attention_fn(qh, kh, vh, attention_mask)
        else:
            ctx = attention_reference(qh, kh, vh, attention_mask)
    with jax.named_scope(scopes.ATTN_PROJ):
        if not fused:
            ctx = merge_heads(ctx)
        attn_out = _dense(ctx, layer["o"], compute_dtype, **dk)
    with jax.named_scope(scopes.LN):
        x = _layer_norm(x + attn_out, layer["attn_ln"],
                        config.layer_norm_eps)
    with jax.named_scope(scopes.FFN):
        ffn = _dense(
            jax.nn.gelu(_dense(x, layer["ffn1"], compute_dtype, **dk)),
            layer["ffn2"], compute_dtype, **dk)
    with jax.named_scope(scopes.LN):
        return _layer_norm(x + ffn, layer["ffn_ln"], config.layer_norm_eps)


def bert_logits(
    params: Dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config: BertConfig,
    use_pallas: bool = False,
    compute_dtype=jnp.bfloat16,
    attention_fn=None,
    dequant_kernel: str = "off",
    kernel_interpret: bool = False,
) -> jax.Array:
    """Sequence-classification logits f32[B, num_labels] from [CLS]."""
    hidden = bert_encode(params, input_ids, attention_mask, config,
                         use_pallas, compute_dtype=compute_dtype,
                         attention_fn=attention_fn,
                         dequant_kernel=dequant_kernel,
                         kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.HEAD):
        cls = hidden[:, 0, :]
        z = jax.nn.relu(cls @ params["pre_classifier"]["w"]
                        + params["pre_classifier"]["b"])
        return z @ params["classifier"]["w"] + params["classifier"]["b"]


def bert_predict(
    params: Dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config: BertConfig,
    use_pallas: bool = False,
    compute_dtype=jnp.bfloat16,
    attention_fn=None,
    dequant_kernel: str = "off",
    kernel_interpret: bool = False,
) -> jax.Array:
    """Fraud probability f32[B] = softmax(logits)[:, 1]
    (bert_text_analyzer.py:216-222).

    ``compute_dtype`` widens the matmul seam (core/precision.py); the
    quant drill uses f32 here to measure the calibration-noise floor the
    committed bf16 policy already accepts."""
    logits = bert_logits(params, input_ids, attention_mask, config,
                         use_pallas, compute_dtype=compute_dtype,
                         attention_fn=attention_fn,
                         dequant_kernel=dequant_kernel,
                         kernel_interpret=kernel_interpret)
    return jax.nn.softmax(logits, axis=-1)[:, 1]


def _attention_refusal(config: BertConfig, width: int, slots: int):
    if flash_supported(width, config.head_dim, config.num_heads):
        return None
    return (f"flash_attention takes seq_len a multiple of 128 and pairs of "
            f"64-wide heads: seq_len {width}, head_dim {config.head_dim}")


def _text_predict(params, input_ids, attention_mask, config, *, use_pallas,
                  kernel_interpret, capacity, dequant_kernel):
    return bert_predict(params, input_ids, attention_mask, config,
                        use_pallas=use_pallas, dequant_kernel=dequant_kernel,
                        kernel_interpret=kernel_interpret), None


# the dense bidirectional encoder: the planes were written for its
# parameter layout, and the narrow width of a split batch is its attention
# kernel's (models/text_encoder.py)
TEXT_ENCODER = TextEncoder(
    config_class=BertConfig, init=init_bert_params, predict=_text_predict,
    depth=lambda config: config.num_layers,
    sites=(KernelSite("attention", _attention_refusal),),
    planes=EVERY_PLANE,
    narrow_width=lambda config: narrowest_supported_len(config.head_dim,
                                                        config.num_heads))
