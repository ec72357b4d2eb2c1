"""ZAYA1-8B's transformer block as a text encoder, in pure JAX.

The sizes are ``Zyphra/ZAYA1-8B``'s ``config.json`` (``ZayaConfig`` keeps
the source's key names); the structure is that of Zyphra's CCA paper
(arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127). Every layer is
``hybrid``: a compressed-convolutional-attention sublayer, then a
routed-expert sublayer, both pre-norm on a float32 residual stream. Text is
right-padded (``models/tokenizer.py``).

**CCA attention**, ``x = RMSNorm(h)``. All of attention runs in a latent
narrower than the hidden size: ``num_attention_heads`` query heads and
``num_key_value_heads`` key heads of ``head_dim`` (8 + 2 heads of 128 = 1280
channels against a hidden size of 2048).

- latent projections, no bias: ``q~ = x W_Q``, ``k~ = x W_K``;
- convolutional mixing of ``c = [q~ ; k~]`` along the sequence, causal,
  positions before 0 zero, a row never sees another row: a depthwise
  convolution of kernel ``cca_time0`` (one weight per channel and tap), then
  a convolution of kernel ``cca_time1`` grouped by head (each tap a
  ``head_dim x head_dim`` matrix per head); split back into ``q^``, ``k^``.
  Tap ``j`` of a kernel of ``n`` weighs position ``t - (n - 1) + j``;
- q-k mean, with G query heads per key head: ``q = q^ + (q~ +
  repeat_G(k~)) / 2``, ``k = k^ + (mean over its G query heads of q~ + k~)
  / 2``: the latents from before the convolutions, added after them;
- per head ``q <- sqrt(head_dim) q / |q|``, ``k <- tau_g sqrt(head_dim) k /
  |k|`` (``rms_norm_eps`` under the root), one learned temperature a key
  head;
- rotate-half RoPE on the first ``partial_rotary_factor`` of each head's
  dims, the rest pass through;
- value shift: the first half of the key-value heads are projected from
  this token, the second half from the previous one (``x_{-1} = 0``);
- causal grouped-query attention under the key mask, ``softmax(q k^T /
  sqrt(head_dim)) v``; ``h <- h + ctx W_O``.

**Routed experts**, ``x = RMSNorm(h)``, per token, top-1 of
``num_experts``: the router is an MLP on a ``router_hidden_size`` latent
that carries state from layer to layer — ``r = x W_down``; after the first
layer ``r <- r + gamma * r_previous``; ``s = softmax(W_3 gelu(W_2 gelu(W_1
RMSNorm(r)))))``; the expert is ``argmax(s + b)`` (``b`` the checkpoint's
balancing bias: it moves the choice alone), its weight ``s[e]``, not
renormalised; ``y = s[e] down_e(silu(gate_e(x)) * up_e(x))``, no shared
expert, no token dropped. The sort, the row gatherings, the grouped matmuls
and the capacity are ``models/olmoe.py``'s (``routed_block``): under a
capacity C the router, its state and the experts run on the launch's ``[C,
...]`` real slots, which every layer shares, so the carried ``r`` never
goes home to slot order.

The head is ``models/olmoe.py``'s convention (final RMSNorm, last real
token, bias-free ``Linear(hidden -> 2)``, ``softmax[:, 1]``; the tied LM
head is not held). The report's learned residual scaling (two vectors a
sublayer that a random initialisation sets to one and zero) is left out.

The steps from the convolutions to the value shift (scope ``attn_mix``) have
two lowerings of one algorithm: the XLA form below (``cca_mix`` and the shift
beside it: every platform, and the numerics oracle) and, where ``use_pallas``
asks and ``ZayaConfig.mix_refusal`` has nothing against the shape, one Pallas
kernel (``ops/cca_mix.py``) that reads the latents and the values once and
writes q, k and v once.

Precision: weights stored bfloat16, bfloat16 matmul operands with float32
accumulation in the projections, the grouped convolution and the expert
matmuls; float32 norms, softmaxes, RoPE, residual stream and depthwise
taps; the router from ``W_down`` on in float32 at ``Precision.HIGHEST``
(top-1 is a discrete choice that rounding flips, and a flip costs the token
a whole expert's output).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from realtime_fraud_detection_tpu.models.olmoe import (
    ExpertLoad,
    _proj,
    apply_rope,
    choose_experts,
    last_token_logits,
    launch_stats,
    rms_norm,
    rope_tables,
    routed_block,
    token_slots,
)
from realtime_fraud_detection_tpu.models.text_encoder import routed_encoder
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    merge_heads,
    split_heads,
)
from realtime_fraud_detection_tpu.ops.cca_mix import (
    cca_mix_fused,
    cca_mix_refusal,
)

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """``config.json`` of ZAYA1-8B, under its own keys."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                  # depthwise kernel
    cca_time1: int = 2                  # per-head grouped kernel
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048   # width of ONE expert
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    num_labels: int = 2

    def __post_init__(self) -> None:
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("ZayaConfig: the query heads must divide into "
                             "the key-value heads")
        if self.num_key_value_heads % 2:
            raise ValueError("ZayaConfig: the value shift halves the "
                             "key-value heads (this token, the previous)")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("ZayaConfig: partial_rotary_factor must leave "
                             "an even number of rotated dims a head")

    @property
    def intermediate_size(self) -> int:
        """One expert's width, under the name the routed-encoder seam
        reads (``models/text_encoder.py``)."""
        return self.moe_intermediate_size

    @property
    def num_sparse_layers(self) -> int:
        """Layers with a routed block (``models/text_encoder.py``)."""
        return self.num_hidden_layers

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def latent_heads(self) -> int:
        """Heads the convolutions mix: query and key heads side by side."""
        return self.num_attention_heads + self.num_key_value_heads

    def mix_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA form of the
        mixing where the fused kernel is asked for, or None where it holds
        the kernel (``ops.cca_mix.cca_mix_refusal``: shapes alone)."""
        return cca_mix_refusal(seq_len, self.head_dim,
                               self.num_key_value_heads,
                               (self.cca_time0, self.cca_time1))


TINY_ZAYA = ZayaConfig(
    vocab_size=30522, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_experts=4, moe_intermediate_size=128, router_hidden_size=32)


def init_zaya_params(key: jax.Array, config: ZayaConfig) -> Dict:
    """Normal(``initializer_range``) matrices drawn directly in bfloat16,
    one tensor at a time (no float32 copy of the expert weights ever
    exists); convolution taps normal(1 / sqrt(fan_in)), so that the
    convolved path carries about as much of q and k as the mean path does
    (at 0.02 it would carry a hundredth, and a wrong convolution would hide
    inside any tolerance); norm weights, temperatures and the router's
    ``gamma`` ones, the balancing bias zeros (float32); the head float32."""
    h, i_, e = (config.hidden_size, config.moe_intermediate_size,
                config.num_experts)
    d, r = config.head_dim, config.router_hidden_size
    q_w = config.num_attention_heads * d
    kv_w = config.num_key_value_heads * d
    std = config.initializer_range

    def w(k, shape, dtype=jnp.bfloat16, std=std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(k_layers, config.num_hidden_layers):
        k = jax.random.split(lk, 13)
        layers.append({
            "input_layernorm": ones(h),
            "q_proj": w(k[0], (h, q_w)), "k_proj": w(k[1], (h, kv_w)),
            # columns: the heads read from this token, then those read
            # from the previous one
            "v_proj": w(k[2], (h, kv_w)), "o_proj": w(k[3], (q_w, h)),
            "conv_depthwise": w(
                k[4], (config.cca_time0, q_w + kv_w), jnp.float32,
                std=1.0 / math.sqrt(config.cca_time0)),
            "conv_grouped": w(
                k[5], (config.latent_heads, config.cca_time1 * d, d),
                std=1.0 / math.sqrt(config.cca_time1 * d)),
            "temperature": ones(config.num_key_value_heads),
            "post_attention_layernorm": ones(h),
            "router_down": w(k[6], (h, r)),
            "router_gamma": ones(r),
            "router_norm": ones(r),
            "router_w1": w(k[7], (r, r)), "router_w2": w(k[8], (r, r)),
            "router_w3": w(k[9], (r, e)),
            "router_bias": jnp.zeros((e,), jnp.float32),
            "gate_proj": w(k[10], (e, h, i_)),
            "up_proj": w(k[11], (e, h, i_)),
            "down_proj": w(k[12], (e, i_, h)),
        })
    return {
        "embed_tokens": w(k_emb, (config.vocab_size, h)),
        "layers": layers,
        "norm": ones(h),
        "score": w(k_head, (h, config.num_labels), jnp.float32),
    }


def shift_tokens(x: jax.Array, by: int = 1, axis: int = 1) -> jax.Array:
    """``y[.., t, ..] = x[.., t - by, ..]`` along the sequence ``axis``,
    zero before position 0: a row's own past, never another row's."""
    if by == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (by, 0)
    return jax.lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[axis], axis=axis)


def cca_convolve(layer: Dict, c: jax.Array, config: ZayaConfig) -> jax.Array:
    """Both causal convolutions on the latents ``c`` ``f32[heads, B, T,
    head_dim]`` (query heads, then key heads): depthwise in float32, then
    per head with bfloat16 operands and float32 accumulation. A tap is
    linear and has no bias, so weighing a shifted input is shifting the
    weighed input: each tap is a contraction on the unshifted latents and
    the shift is taken on its result (no shifted or concatenated copy of
    the operand is made)."""
    heads, b, t, d = c.shape
    taps = layer["conv_depthwise"]               # [n0, heads * D]
    n0, n1 = taps.shape[0], config.cca_time1
    taps = taps.reshape(n0, heads, 1, 1, d)
    c = sum(shift_tokens(c, n0 - 1 - j, axis=2) * taps[j] for j in range(n0))
    w = layer["conv_grouped"].reshape(heads, n1, d, d)
    c = c.astype(w.dtype)
    # the head leads both operands (the CPU backend has no bfloat16
    # contraction with a batch dimension anywhere else)
    return sum(shift_tokens(
        jnp.einsum("gbti,gio->gbto", c, w[:, j],
                   preferred_element_type=jnp.float32), n1 - 1 - j, axis=2)
        for j in range(n1))


def l2_heads(x: jax.Array, eps: float) -> jax.Array:
    """``sqrt(D) x / |x|`` over the last axis: an RMSNorm with no weight."""
    return rms_norm(x, 1.0, eps)


def cca_mix(layer: Dict, latents: jax.Array, cos, sin, config: ZayaConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """Everything between the latent projections and the core: ``(q f32[B,
    heads, T, D], k f32[B, kv_heads, T, D])`` from ``[q~ ; k~]`` head-major,
    ``f32[heads + kv_heads, B, T, D]`` (``zaya_attention``'s projection
    writes them so): a head is a leading index, its convolution a batched
    matmul, its norm and its rotation run along the minor axis, and nothing
    is transposed until the core."""
    _, b, t, _ = latents.shape
    heads, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
    mixed = cca_convolve(layer, latents, config)
    # [kv_heads, query heads of the group (1 for the key itself), B, T, D]
    q_pre = latents[:heads].reshape(kv, heads // kv, b, t, d)
    k_pre = latents[heads:].reshape(kv, 1, b, t, d)
    q = mixed[:heads].reshape(q_pre.shape) + 0.5 * (q_pre + k_pre)
    k = mixed[heads:].reshape(k_pre.shape) + 0.5 * (
        jnp.mean(q_pre, axis=1, keepdims=True) + k_pre)
    eps = config.rms_norm_eps
    q = l2_heads(q, eps)
    k = l2_heads(k, eps) * layer["temperature"][:, None, None, None, None]
    rot = config.rotary_dim

    def rope(x, n):         # [.., B, T, D] -> [B, n, T, D]
        x = jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
        return x.reshape(n, b, t, d).transpose(1, 0, 2, 3)

    return rope(q, heads), rope(k, kv)


def zaya_attention(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                   config: ZayaConfig, cos, sin, *, use_pallas: bool = False,
                   kernel_interpret: bool = False) -> jax.Array:
    """``h + o_proj(cca(...))`` on ``h`` ``f32[B, T, hidden]``: the first
    sublayer of a block. ``use_pallas`` asks for the fused mixing
    (``ops/cca_mix.py``); a shape it does not take (``ZayaConfig.
    mix_refusal``) runs the XLA form."""
    heads, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
    with jax.named_scope(scopes.LN):
        x = rms_norm(h, layer["input_layernorm"], config.rms_norm_eps)
    with jax.named_scope(scopes.ATTN_PROJ):
        # q~ and k~ side by side and head-major, as the mixing takes them:
        # the contraction writes [heads, B, T, D] itself, no transpose
        w_lat = jnp.concatenate([layer["q_proj"], layer["k_proj"]], axis=1)
        latents = jnp.einsum(
            "btk,kgd->gbtd", x.astype(w_lat.dtype),
            w_lat.reshape(w_lat.shape[0], heads + kv, d),
            preferred_element_type=jnp.float32)
        v = _proj(x, layer["v_proj"])
    with jax.named_scope(scopes.ATTN_MIX):
        if use_pallas and config.mix_refusal(h.shape[1]) is None:
            qh, kh, vh = cca_mix_fused(
                latents, v, layer["conv_depthwise"], layer["conv_grouped"],
                layer["temperature"], cos, sin, num_heads=heads,
                num_kv_heads=kv, eps=config.rms_norm_eps,
                interpret=kernel_interpret)
        else:
            qh, kh = cca_mix(layer, latents, cos, sin, config)
            # no bias, so projecting the previous token is shifting its
            # projection
            now, before = jnp.split(v, 2, axis=-1)
            vh = split_heads(
                jnp.concatenate([now, shift_tokens(before)], axis=-1), kv)
    with jax.named_scope(scopes.ATTN_CORE):
        ctx = attention_reference(qh, kh, vh, attention_mask, causal=True)
    with jax.named_scope(scopes.ATTN_PROJ):
        attn_out = _proj(merge_heads(ctx), layer["o_proj"])
    with jax.named_scope(scopes.LN):
        return h + attn_out


def router_state(layer: Dict, x: jax.Array,
                 previous: Optional[jax.Array]) -> jax.Array:
    """The router's latent ``f32[N, router_hidden]``: ``x W_down``, plus
    ``gamma`` times the previous layer's (None in the first layer)."""
    r = jnp.dot(x.astype(jnp.float32),
                layer["router_down"].astype(jnp.float32), precision=HIGHEST)
    return r if previous is None else r + layer["router_gamma"] * previous


def router_probs(layer: Dict, r: jax.Array, eps: float) -> jax.Array:
    """``softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r))))`` over all experts,
    float32 at the highest matmul precision; the exact (erf) GELU."""
    z = rms_norm(r, layer["router_norm"], eps)
    for name in ("router_w1", "router_w2"):
        z = jax.nn.gelu(jnp.dot(z, layer[name].astype(jnp.float32),
                                precision=HIGHEST), approximate=False)
    logits = jnp.dot(z, layer["router_w3"].astype(jnp.float32),
                     precision=HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


def zaya_route(layer: Dict, x: jax.Array, previous: Optional[jax.Array],
               config: ZayaConfig
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(experts i32[N, 1], weights f32[N, 1], r f32[N, router_hidden])``
    for the normed rows ``x``; ``r`` is what the next layer is handed."""
    r = router_state(layer, x, previous)
    experts, weights = choose_experts(
        router_probs(layer, r, config.rms_norm_eps),
        config.num_experts_per_tok, layer["router_bias"])
    return experts, weights, r


def zaya_layer(layer: Dict, h: jax.Array, r: Optional[jax.Array],
               attention_mask: jax.Array, config: ZayaConfig, cos, sin, *,
               slots: Optional[Tuple[Optional[jax.Array], jax.Array]] = None,
               use_pallas: bool = False, kernel_interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array, ExpertLoad]:
    """One block on ``h`` ``f32[B, T, hidden]`` and the previous layer's
    router state ``r`` (None in the first layer; on the launch's routed
    slots): ``(h, r, the layer's ExpertLoad)``."""
    b, t, width = h.shape
    if slots is None:
        slots = token_slots(attention_mask, None)
    h = zaya_attention(layer, h, attention_mask, config, cos, sin,
                       use_pallas=use_pallas,
                       kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        x = rms_norm(h, layer["post_attention_layernorm"],
                     config.rms_norm_eps).reshape(b * t, width)
    y, load, r = routed_block(
        layer, x, slots, lambda rows: zaya_route(layer, rows, r, config),
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        h = h + y.reshape(b, t, width)
    return h, r, load


def zaya_encode(params: Dict, input_ids: jax.Array,
                attention_mask: jax.Array, config: ZayaConfig, *,
                capacity: Optional[int] = None,
                use_pallas: bool = False, kernel_interpret: bool = False
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Hidden states before the final norm ``f32[B, T, hidden]``, the last
    layer's router state on the routed slots ``f32[C, router_hidden]``, and
    the launch's statistics ``i32[3, layers]`` (``olmoe.launch_stats``).
    ``capacity``: the token slots the routed blocks are compiled for
    (``models/olmoe.py``)."""
    cos, sin = rope_tables(input_ids.shape[1], config.rotary_dim,
                           config.rope_theta)
    slots = token_slots(attention_mask, capacity)
    with jax.named_scope(scopes.EMBED):
        h = params["embed_tokens"][input_ids].astype(jnp.float32)
    r, loads = None, []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h, r, load = zaya_layer(
                layer, h, r, attention_mask, config, cos, sin, slots=slots,
                use_pallas=use_pallas, kernel_interpret=kernel_interpret)
        loads.append(load)
    return h, r, launch_stats(loads)


def zaya_logits(params: Dict, input_ids: jax.Array,
                attention_mask: jax.Array, config: ZayaConfig, *,
                capacity: Optional[int] = None,
                use_pallas: bool = False, kernel_interpret: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
    """Sequence-classification logits ``f32[B, num_labels]`` from the last
    real token, and the launch's statistics ``i32[3, layers]``."""
    hidden, _, stats = zaya_encode(
        params, input_ids, attention_mask, config, capacity=capacity,
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    return last_token_logits(params, hidden, attention_mask,
                             config.rms_norm_eps), stats


def zaya_predict(params: Dict, input_ids: jax.Array,
                 attention_mask: jax.Array, config: ZayaConfig, *,
                 capacity: Optional[int] = None,
                 use_pallas: bool = False, kernel_interpret: bool = False,
                 with_stats: bool = False):
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]``; with
    ``with_stats`` also the launch's statistics ``i32[3, layers]``
    (``olmoe_predict``'s second output)."""
    logits, stats = zaya_logits(params, input_ids, attention_mask, config,
                                capacity=capacity, use_pallas=use_pallas,
                                kernel_interpret=kernel_interpret)
    p = jax.nn.softmax(logits, axis=-1)[:, 1]
    return (p, stats) if with_stats else p


TEXT_ENCODER = routed_encoder(ZayaConfig, init_zaya_params, zaya_predict,
                              ZayaConfig.mix_refusal)
