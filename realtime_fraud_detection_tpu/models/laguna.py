"""Laguna-S-2.1's transformer block as a text encoder, in pure JAX.

The sizes are ``poolside/Laguna-S-2.1``'s ``config.json`` (``LagunaConfig``
keeps the source's key names where the routed-encoder seam does not claim
them, below); the layer equations are written down from that file, each
assumption listed in the benchmark's configuration file. The layers of one
encoder are NOT alike — every per-layer shape is read from the config's own
lists, and the encoder is one Python loop over unlike layers:

- ``layer_types[l]`` is ``full_attention`` or ``sliding_attention`` (1 : 3);
  ``num_attention_heads_per_layer[l]`` query heads (48 full, 72 sliding) of
  ``head_dim`` 128 over ``num_key_value_heads`` 8, so q and o are 6,144 or
  9,216 wide beside a 3,072 residual;
- ``mlp_layer_types[l]`` is ``dense`` (layer 0: a SwiGLU MLP of
  ``intermediate_size``) or ``sparse`` (routed experts beside a shared one).

Per layer, pre-norm on a float32 residual ``h`` (text right-padded):

1. ``a = RMSNorm(h)``; ``q = a W_q``, ``k = a W_k``, ``v = a W_v``, no
   biases, no QK-norm.
2. Rotate-half RoPE by the layer's kind (``rope_parameters``): sliding
   layers the default form on the whole head; full layers on the first
   ``partial_rotary_factor`` of a head with static YaRN frequencies
   (``yarn_inv_freq``) and cos and sin scaled by ``attention_factor``.
3. Query head ``g`` reads key-value head ``g // (H_l / 8)``; causal
   ``softmax(q k^T / sqrt(128))`` in float32 over the keys ``j <= i``, on a
   sliding layer only ``i - sliding_window < j <= i``; padded keys never.
4. A per-head output gate: ``gamma = sigmoid(a W_g)`` (``[T, H_l]``), head
   ``g``'s context times ``gamma[:, g]`` before ``W_o``; ``h += ctx W_o``.
5. ``m = RMSNorm(h)``. Dense: ``h += (silu(m W_gate) * m W_up) W_down``.
6. Sparse: ``p = softmax(m W_r)`` over all ``router_experts`` in float32;
   the ``num_experts_per_tok`` largest, renormalised over the chosen
   (``norm_topk_prob``) and scaled by ``moe_routed_scaling_factor``; ``h +=
   sum_e w_e E_e(m) + S(m)``, every ``E_e`` and the shared ``S`` a SwiGLU.

**This chip's share of the experts.** ``num_experts`` is how many routed
experts a layer HOLDS here (the stacked weights' leading dimension: the
groups of the grouped matmul, the name the routed-encoder seam reads),
``router_experts`` the router's published width, ``expert_offset`` the
first held one's number. The router runs whole; step 6's sum runs over the
chosen experts this chip holds, with ``w`` normalised over all of a
token's experts; what the absent ones would add is left out and the
partial result goes on (``models/olmoe.apply_experts``). The attention,
the router, the shared expert and the dense layer are held whole.

Likewise ``intermediate_size`` answers the seam with ONE expert's width
(``moe_intermediate_size``); the dense MLP's width, which the source
spells ``intermediate_size``, is ``dense_intermediate_size`` here.

The head is ``models/olmoe.py``'s (final RMSNorm, last real token,
bias-free ``Linear(hidden -> 2)``, ``softmax[:, 1]``).

Precision: weights stored bfloat16; bfloat16 matmul operands with float32
accumulation in the projections, the core and the expert matmuls (q, k and
v are rounded to bfloat16 once, after RoPE; the gated context once, as
``W_o``'s operand); float32 norms, softmaxes, RoPE, gate sigmoid, gating
and residual; the router in float32 at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from realtime_fraud_detection_tpu.models.olmoe import (
    _proj,
    apply_rope,
    choose_experts,
    ExpertLoad,
    last_token_logits,
    launch_stats,
    rms_norm,
    rope_tables,
    routed_block,
    router_probs,
    token_slots,
)
from realtime_fraud_detection_tpu.models.text_encoder import routed_encoder
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    merge_heads,
    rope_lane_tables,
    split_heads,
    windowed_attention,
    windowed_refusal,
)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class LagunaRope:
    """One entry of ``rope_parameters``, under its own keys."""

    rope_type: str = "default"
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    # ``yarn`` only
    factor: float = 1.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


LAGUNA_ROPE_FULL = LagunaRope(
    rope_type="yarn", rope_theta=500000.0, partial_rotary_factor=0.5,
    factor=128.0, original_max_position_embeddings=8192, beta_fast=32.0,
    beta_slow=1.0, attention_factor=1.4852030263919618)
LAGUNA_ROPE_SLIDING = LagunaRope()


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """``config.json`` of Laguna-S-2.1: the source's keys, but for the two
    names the routed-encoder seam reads its own way (module docstring):
    ``num_experts`` (held here) beside ``router_experts`` (published), and
    ``dense_intermediate_size`` for the source's ``intermediate_size``."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    dense_intermediate_size: int = 12288
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 12
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 47
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_full: LagunaRope = LAGUNA_ROPE_FULL
    rope_sliding: LagunaRope = LAGUNA_ROPE_SLIDING
    router_experts: int = 256           # the router's width, as published
    num_experts: int = 256              # routed experts a layer holds HERE
    expert_offset: int = 0              # the first held expert's number
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024   # width of ONE routed expert
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    embedding_range: float = 1.0        # init_laguna_params says why
    router_range: float = 0.08          # likewise
    num_labels: int = 2

    def __post_init__(self) -> None:
        n = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(
                    f"LagunaConfig: {name} holds {len(getattr(self, name))} "
                    f"entries for {n} layers")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"LagunaConfig: layer_types {self.layer_types}")
        if set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(
                f"LagunaConfig: mlp_layer_types {self.mlp_layer_types}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("LagunaConfig: every layer's query heads must "
                             "divide into the key-value heads")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.num_experts
                <= self.router_experts):
            raise ValueError(
                f"LagunaConfig: experts {self.expert_offset}.."
                f"{self.expert_offset + self.num_experts} of a router "
                f"{self.router_experts} wide")
        for rope in (self.rope_full, self.rope_sliding):
            rot = self.head_dim * rope.partial_rotary_factor
            if rot != int(rot) or int(rot) % 2 or not 0 < rot <= self.head_dim:
                raise ValueError("LagunaConfig: partial_rotary_factor must "
                                 "leave an even number of rotated dims")

    @property
    def intermediate_size(self) -> int:
        """One routed expert's width, under the name the routed-encoder
        seam reads (``models/text_encoder.py``)."""
        return self.moe_intermediate_size

    @property
    def num_sparse_layers(self) -> int:
        return sum(kind == SPARSE for kind in self.mlp_layer_types)

    def rope_of(self, layer: int) -> LagunaRope:
        return (self.rope_full if self.layer_types[layer] == FULL
                else self.rope_sliding)

    def window_of(self, layer: int) -> Optional[int]:
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    def core_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA core where
        the fused one is asked for, or None where every layer holds the
        kernel (``ops.attention.windowed_refusal``: shapes alone)."""
        for i, heads in enumerate(self.num_attention_heads_per_layer):
            refusal = windowed_refusal(seq_len, self.head_dim, heads,
                                       self.num_key_value_heads,
                                       self.window_of(i))
            if refusal:
                return refusal
        return None


TINY_LAGUNA = LagunaConfig(
    vocab_size=30522, hidden_size=128, dense_intermediate_size=256,
    num_hidden_layers=5,
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    mlp_layer_types=(DENSE,) + (SPARSE,) * 4,
    num_attention_heads_per_layer=(4, 6, 6, 6, 4), num_key_value_heads=2,
    head_dim=16, sliding_window=8, router_experts=16, num_experts=4,
    expert_offset=4, num_experts_per_tok=4, moe_intermediate_size=64,
    shared_expert_intermediate_size=64)


def init_laguna_params(key: jax.Array, config: LagunaConfig) -> Dict:
    """Normal(``initializer_range``) matrices drawn directly in bfloat16,
    one tensor at a time (no float32 copy of the expert weights ever
    exists); the embedding at ``embedding_range`` (unit scale: a token's
    own vector, not the attention's running mean over its row, then decides
    its route, as a trained router's balancing does — at 0.02 the residual
    after the first attention is mostly a component all tokens of a row
    share, and one seed's held experts catch most of a batch, the next
    seed's none); the router at ``router_range`` (four times the rest: a
    peaked router, as a trained one is. At 0.02 a token's ten chosen
    experts weigh nearly alike, so each of the rank-10 / rank-11 swaps that
    bfloat16 rounding makes on ~6% of the (token, layer) pairs moves a
    tenth of a routed sum, and a comparison with a float32 reference reads
    those swaps — 2e-4 to 5e-3 by the seed — and not the arithmetic; peaked,
    the swapped experts weigh a few thousandths. The ranking, so the routes,
    the share and the load, are the same at any scale); norm weights ones
    (float32); the head float32. A layer holds the shapes its own entries
    of the config's lists give."""
    h, d = config.hidden_size, config.head_dim
    kv_w = config.num_key_value_heads * d
    e, i_, s_ = (config.num_experts, config.moe_intermediate_size,
                 config.shared_expert_intermediate_size)

    def w(k, shape, dtype=jnp.bfloat16, std=config.initializer_range):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def ones():
        return jnp.ones((h,), jnp.float32)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for li, lk in enumerate(jax.random.split(k_layers,
                                             config.num_hidden_layers)):
        k = jax.random.split(lk, 15)
        heads = config.num_attention_heads_per_layer[li]
        layer = {
            "input_layernorm": ones(),
            "q_proj": w(k[0], (h, heads * d)), "k_proj": w(k[1], (h, kv_w)),
            "v_proj": w(k[2], (h, kv_w)), "g_proj": w(k[3], (h, heads)),
            "o_proj": w(k[4], (heads * d, h)),
            "post_attention_layernorm": ones(),
        }
        if config.mlp_layer_types[li] == DENSE:
            f = config.dense_intermediate_size
            layer.update({"mlp_gate": w(k[5], (h, f)),
                          "mlp_up": w(k[6], (h, f)),
                          "mlp_down": w(k[7], (f, h))})
        else:
            layer.update({
                "router": w(k[8], (h, config.router_experts),
                            std=config.router_range),
                "gate_proj": w(k[9], (e, h, i_)),
                "up_proj": w(k[10], (e, h, i_)),
                "down_proj": w(k[11], (e, i_, h)),
                "shared_gate": w(k[12], (h, s_)),
                "shared_up": w(k[13], (h, s_)),
                "shared_down": w(k[14], (s_, h)),
            })
        layers.append(layer)
    return {
        "embed_tokens": w(k_emb, (config.vocab_size, h),
                          std=config.embedding_range),
        "layers": layers,
        "norm": ones(),
        "score": w(k_head, (h, config.num_labels), jnp.float32),
    }


def yarn_inv_freq(rope: LagunaRope, rotary_dim: int) -> np.ndarray:
    """The ``rotary_dim / 2`` inverse frequencies of static YaRN (the
    Hugging Face ``_compute_yarn_parameters`` convention), float64: with
    ``f_i = theta^(2i/d)`` and ``c(n) = d ln(L / (2 pi n)) / (2 ln theta)``
    (``L`` the original context), ``low = max(floor(c(beta_fast)), 0)``,
    ``high = min(ceil(c(beta_slow)), d - 1)``, ``ramp_i = clip((i - low) /
    (high - low), 0, 1)``: ``(1 - ramp_i) / f_i + ramp_i / (factor f_i)`` —
    the fast dims as they are, the slow ones stretched by ``factor``."""
    d, theta = rotary_dim, float(rope.rope_theta)
    f = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def c(rotations: float) -> float:
        return (d * math.log(rope.original_max_position_embeddings
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(c(rope.beta_fast)), 0)
    high = min(math.ceil(c(rope.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 - ramp) / f + ramp / (rope.factor * f)


def laguna_rope_tables(seq_len: int, head_dim: int, rope: LagunaRope
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """cos and sin ``f32[T, rotary_dim]`` of positions 0..T-1 for one kind
    of layer (rotate-half layout; constants of the program)."""
    rot = int(head_dim * rope.partial_rotary_factor)
    if rope.rope_type == "default":
        return rope_tables(seq_len, rot, rope.rope_theta)
    if rope.rope_type != "yarn":
        raise ValueError(f"LagunaRope: rope_type {rope.rope_type!r}")
    angles = (np.arange(seq_len, dtype=np.float64)[:, None]
              * yarn_inv_freq(rope, rot)[None])
    angles = np.concatenate([angles, angles], axis=-1)
    return ((np.cos(angles) * rope.attention_factor).astype(np.float32),
            (np.sin(angles) * rope.attention_factor).astype(np.float32))


def _rotate(x: jax.Array, cos, sin) -> jax.Array:
    """RoPE on the first ``cos.shape[-1]`` dims of a head (the last axis;
    ``cos`` and ``sin`` broadcast against ``x``); the rest pass through."""
    rot = cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def laguna_attention(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                     lengths: jax.Array, config: LagunaConfig, index: int,
                     cos, sin, *, use_pallas: bool = False,
                     kernel_interpret: bool = False) -> jax.Array:
    """``h + o_proj(gate * attn(...))`` on ``h`` ``f32[B, T, hidden]``: the
    first sublayer of layer ``index``. ``use_pallas`` asks for the fused
    core (``ops.attention.windowed_attention``); a shape it does not take
    (``LagunaConfig.core_refusal``) runs the XLA form."""
    heads = config.num_attention_heads_per_layer[index]
    kv, window = config.num_key_value_heads, config.window_of(index)
    operand = layer["q_proj"].dtype
    with jax.named_scope(scopes.LN):
        a = rms_norm(h, layer["input_layernorm"], config.rms_norm_eps)
    b, t, _ = h.shape
    d = config.head_dim
    fused = use_pallas and config.core_refusal(t) is None
    with jax.named_scope(scopes.ATTN_PROJ):
        gate = jax.nn.sigmoid(_proj(a, layer["g_proj"]))       # [B, T, H_l]
        q = _proj(a, layer["q_proj"])                          # [B, T, H*D]
        k = _proj(a, layer["k_proj"])                          # [B, T, kv*D]
        v = _proj(a, layer["v_proj"]).astype(operand)
    if fused:
        # q and k are rotated and the context gated inside the kernel: no
        # pass stands between the projections and it
        *tables, shift = rope_lane_tables(cos, sin, d)
        with jax.named_scope(scopes.ATTN_CORE):
            gated = windowed_attention(
                q, k, v, lengths, num_heads=heads, num_kv_heads=kv,
                window=window, rope=tuple(tables), rope_shift=shift,
                gate=gate, out_dtype=operand,
                interpret=kernel_interpret)                    # [B, T, H*D]
    else:
        with jax.named_scope(scopes.ATTN_PROJ):
            # RoPE in float32 against [B, T, heads, D]; q and k then take
            # the operands' dtype, as the kernel rounds them
            cos_, sin_ = cos[:, None, :], sin[:, None, :]
            q = _rotate(q.reshape(b, t, heads, d), cos_, sin_).astype(operand)
            k = _rotate(k.reshape(b, t, kv, d), cos_, sin_).astype(operand)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = merge_heads(attention_reference(
                q.transpose(0, 2, 1, 3).astype(jnp.float32),
                k.transpose(0, 2, 1, 3).astype(jnp.float32),
                split_heads(v, kv).astype(jnp.float32), attention_mask,
                causal=True, window=window))
        with jax.named_scope(scopes.ATTN_PROJ):
            gated = (ctx.reshape(b, t, heads, d)
                     * gate[..., None]).reshape(b, t, heads * d)
    with jax.named_scope(scopes.ATTN_PROJ):
        attn_out = _proj(gated, layer["o_proj"])
    with jax.named_scope(scopes.LN):
        return h + attn_out


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """``(silu(x W_gate) * x W_up) W_down``: bf16 operands, f32 results."""
    return _proj(jax.nn.silu(_proj(x, w_gate)) * _proj(x, w_up), w_down)


def laguna_route(layer: Dict, x: jax.Array, config: LagunaConfig
                 ) -> Tuple[jax.Array, jax.Array, None]:
    """``(experts i32[N, k] in the router's numbers, weights f32[N, k],
    None)`` for the normed rows ``x``: softmax over every published expert,
    the k largest renormalised over the chosen and scaled."""
    experts, weights = choose_experts(
        router_probs(x, layer["router"]), config.num_experts_per_tok,
        renormalise=config.norm_topk_prob,
        scale=config.moe_routed_scaling_factor)
    return experts, weights, None


def laguna_layer(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                 lengths: jax.Array, config: LagunaConfig, index: int,
                 cos, sin, *,
                 slots: Optional[Tuple[Optional[jax.Array], jax.Array]] = None,
                 use_pallas: bool = False, kernel_interpret: bool = False
                 ) -> Tuple[jax.Array, Optional[ExpertLoad]]:
    """Layer ``index`` on ``h`` ``f32[B, T, hidden]``: ``(h, load)``, the
    ``ExpertLoad`` of a sparse layer's held experts, None of a dense
    one."""
    b, t, width = h.shape
    h = laguna_attention(layer, h, attention_mask, lengths, config, index,
                         cos, sin, use_pallas=use_pallas,
                         kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        m = rms_norm(h, layer["post_attention_layernorm"],
                     config.rms_norm_eps)
    if config.mlp_layer_types[index] == DENSE:
        with jax.named_scope(scopes.FFN):
            y = swiglu(m, layer["mlp_gate"], layer["mlp_up"],
                       layer["mlp_down"])
        with jax.named_scope(scopes.LN):
            return h + y, None
    if slots is None:
        slots = token_slots(attention_mask, None)
    y, load, _ = routed_block(
        layer, m.reshape(b * t, width), slots,
        lambda rows: laguna_route(layer, rows, config),
        shared=lambda rows: swiglu(rows, layer["shared_gate"],
                                   layer["shared_up"], layer["shared_down"]),
        router_width=config.router_experts,
        expert_offset=config.expert_offset,
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        return h + y.reshape(b, t, width), load


def laguna_encode(params: Dict, input_ids: jax.Array,
                  attention_mask: jax.Array, config: LagunaConfig, *,
                  capacity: Optional[int] = None,
                  use_pallas: bool = False, kernel_interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Hidden states before the final norm ``f32[B, T, hidden]`` and the
    sparse layers' statistics ``i32[3, sparse layers]``
    (``olmoe.launch_stats``): the largest held expert's group, under it
    the pairs that entered a held expert's group at all (what this chip's
    share of the routing came to), and the rows the fused kernel's grid
    visited for them.
    ``capacity``: the token slots the routed blocks are compiled for
    (``models/olmoe.py``)."""
    t = input_ids.shape[1]
    tables = {kind: laguna_rope_tables(t, config.head_dim, rope)
              for kind, rope in ((FULL, config.rope_full),
                                 (SLIDING, config.rope_sliding))}
    slots = token_slots(attention_mask, capacity)
    lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
    with jax.named_scope(scopes.EMBED):
        h = params["embed_tokens"][input_ids].astype(jnp.float32)
    loads = []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h, load = laguna_layer(
                layer, h, attention_mask, lengths, config, i,
                *tables[config.layer_types[i]], slots=slots,
                use_pallas=use_pallas, kernel_interpret=kernel_interpret)
        if load is not None:
            loads.append(load)
    return h, launch_stats(loads)


def laguna_logits(params: Dict, input_ids: jax.Array,
                  attention_mask: jax.Array, config: LagunaConfig, *,
                  capacity: Optional[int] = None,
                  use_pallas: bool = False, kernel_interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Sequence-classification logits ``f32[B, num_labels]`` from the last
    real token, and ``laguna_encode``'s statistics."""
    hidden, stats = laguna_encode(
        params, input_ids, attention_mask, config, capacity=capacity,
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    return last_token_logits(params, hidden, attention_mask,
                             config.rms_norm_eps), stats


def laguna_predict(params: Dict, input_ids: jax.Array,
                   attention_mask: jax.Array, config: LagunaConfig, *,
                   capacity: Optional[int] = None,
                   use_pallas: bool = False, kernel_interpret: bool = False,
                   with_stats: bool = False):
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]``; with
    ``with_stats`` also ``i32[3, sparse layers]``: the largest held group,
    the held pairs and the visited rows of each sparse layer
    (``StreamJob.counters``' ``expert_peak_rows``, ``expert_rows`` and
    ``expert_tile_rows``)."""
    logits, stats = laguna_logits(params, input_ids, attention_mask, config,
                                  capacity=capacity, use_pallas=use_pallas,
                                  kernel_interpret=kernel_interpret)
    p = jax.nn.softmax(logits, axis=-1)[:, 1]
    return (p, stats) if with_stats else p


TEXT_ENCODER = routed_encoder(LagunaConfig, init_laguna_params, laguna_predict,
                              LagunaConfig.core_refusal)
