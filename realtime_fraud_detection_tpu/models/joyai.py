"""JoyAI-LLM-Flash's transformer block as a text encoder, in pure JAX.

The sizes are ``jdopensource/JoyAI-LLM-Flash``'s ``config.json``
(``JoyaiConfig`` keeps the source's key names — DeepSeek-V3's, letter for
letter — but for the three the routed-encoder seam reads its own way, below);
the layer equations are written down from that file, each assumption listed
in the benchmark's configuration file.

Per layer, pre-norm on a float32 residual ``h`` (text right-padded), ``H``
heads, no biases:

1. ``x = RMSNorm(h)``. **Latent attention (MLA)**: the query through a
   low-rank latent with a norm in the middle, ``c_q = x W_qa`` (``hidden ->
   q_lora_rank``), ``q = RMSNorm(c_q) W_qb`` (``-> H x (qk_nope_head_dim +
   qk_rope_head_dim)``), per head ``[q_nope | q_pe]``; keys and values
   through another, ``[c_kv | k_pe] = x W_kva`` (``hidden -> kv_lora_rank +
   qk_rope_head_dim``), ``RMSNorm(c_kv) W_kvb`` (``-> H x (qk_nope_head_dim
   + v_head_dim)``), per head ``[k_nope | v]``. ``k_pe`` is ONE head,
   shared by all ``H``: ``k = [k_nope | k_pe]``.
2. RoPE on ``q_pe`` and ``k_pe`` alone, ``rope_theta`` over
   ``qk_rope_head_dim`` dims, INTERLEAVED (``rope_interleave``): the rotated
   pairs are the adjacent dims ``(2i, 2i + 1)``. ``rope_scaling`` is null:
   no YaRN factor, no ``mscale`` on the softmax scale.
3. Causal ``softmax(q k^T (qk_nope_head_dim + qk_rope_head_dim)^-1/2)`` in
   float32 over the keys ``j <= i``, padded keys never, times ``v``
   (``v_head_dim`` a head: narrower than a score); the ``H`` contexts side
   by side through ``W_o``; ``h += out``.
4. ``m = RMSNorm(h)``. The first ``first_k_dense_replace`` layers: ``h +=
   (silu(m W_gate) * m W_up) W_down`` at ``intermediate_size``.
5. The others: ``s = sigmoid(m W_r)`` over all ``n_routed_experts`` in
   float32; the chosen are the ``num_experts_per_tok`` largest of ``s + b``
   (``b`` the layer's ``e_score_correction_bias``; ``topk_method``
   ``noaux_tc``. With ``n_group`` 1 and ``topk_group`` 1 the group-limited
   step keeps the one group there is: the identity, and it is not built);
   the weights are ``s`` at the chosen, WITHOUT ``b``, over their sum + 1e-20
   (``norm_topk_prob``), times ``routed_scaling_factor``; ``h += sum_e w_e
   E_e(m) + S(m)``, every ``E_e`` a SwiGLU of ``moe_intermediate_size``, the
   shared ``S`` one of ``moe_intermediate_size x n_shared_experts``.

The head is ``models/olmoe.py``'s (final RMSNorm, last real token,
bias-free ``Linear(hidden -> 2)``, ``softmax[:, 1]``). The multi-token
prediction module (``num_nextn_predict_layers``) is a further block behind
the last layer that predicts token ``i + 2`` for a training loss and for
speculative decoding; this path emits one probability a row and no token,
and holds none of it.

What the routed-encoder seam reads (``models/text_encoder.py``):
``num_experts`` = ``n_routed_experts`` (every expert is held here),
``intermediate_size`` = ONE expert's width ``moe_intermediate_size`` (the
source's ``intermediate_size``, the dense layers' MLP, is
``dense_intermediate_size`` here), ``num_sparse_layers``.

Precision: weights stored bfloat16; bfloat16 matmul operands with float32
accumulation in the six attention projections, both contractions of a score
and the weighted sum, the dense MLP and the routed and shared experts
(``q_nope``, ``k_nope`` and ``v`` leave their projections bfloat16; ``q_pe``
and ``k_pe`` are rotated in float32 and rounded once); float32 norms (the two
latent ones too), softmax, RoPE and residual; the router's matmul, sigmoid,
bias, top-k and weights in float32 at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from realtime_fraud_detection_tpu.models.laguna import swiglu
from realtime_fraud_detection_tpu.models.olmoe import (
    _proj,
    ExpertLoad,
    choose_experts,
    last_token_logits,
    launch_stats,
    rms_norm,
    routed_block,
    token_slots,
)
from realtime_fraud_detection_tpu.models.text_encoder import routed_encoder
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    merge_heads,
    rope_pair_tables,
    windowed_attention,
    windowed_refusal,
)


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    """``config.json`` of JoyAI-LLM-Flash under its own keys, but for
    ``dense_intermediate_size`` (the source's ``intermediate_size``: the
    module docstring says what the seam reads under that name)."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    dense_intermediate_size: int = 7168
    moe_intermediate_size: int = 768    # width of ONE routed expert
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    embedding_range: float = 1.0        # init_joyai_params says why
    bias_range: float = 0.003           # likewise
    expert_spread: float = 0.015625      # likewise
    num_labels: int = 2

    def __post_init__(self) -> None:
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("JoyaiConfig: latent attention up-projects one "
                             "key-value head a query head")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                "JoyaiConfig: group-limited routing is not built (n_group "
                f"{self.n_group}, topk_group {self.topk_group}: with one "
                "group it is the identity)")
        if (self.scoring_func, self.topk_method) != ("sigmoid", "noaux_tc"):
            raise ValueError(
                f"JoyaiConfig: a {self.scoring_func!r} router chosen by "
                f"{self.topk_method!r} is not what the equations hold")
        if self.rope_scaling is not None or not self.rope_interleave:
            raise ValueError("JoyaiConfig: plain interleaved RoPE alone "
                             "(rope_scaling null, rope_interleave true)")
        if self.moe_layer_freq != 1 or not (
                0 <= self.first_k_dense_replace <= self.num_hidden_layers):
            raise ValueError("JoyaiConfig: leading dense layers, then every "
                             "layer sparse")
        if self.qk_rope_head_dim % 2:
            raise ValueError("JoyaiConfig: RoPE rotates pairs of dims")

    @property
    def num_experts(self) -> int:
        """The routed experts a layer holds, all of them, under the name
        the routed-encoder seam reads."""
        return self.n_routed_experts

    @property
    def intermediate_size(self) -> int:
        """One routed expert's width, under the seam's name."""
        return self.moe_intermediate_size

    @property
    def num_sparse_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def core_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA core where
        the fused one is asked for, or None where it holds the kernel
        (``ops.attention.windowed_refusal`` with the shared key riding it:
        shapes alone)."""
        return windowed_refusal(
            seq_len, self.qk_nope_head_dim, self.num_attention_heads,
            self.num_key_value_heads, None,
            shared_key_dim=self.qk_rope_head_dim, value_dim=self.v_head_dim)


TINY_JOYAI = JoyaiConfig(
    vocab_size=30522, hidden_size=128, dense_intermediate_size=256,
    moe_intermediate_size=64, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=96, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, bias_range=0.01,
    expert_spread=1.0)


def init_joyai_params(key: jax.Array, config: JoyaiConfig) -> Dict:
    """Normal(``initializer_range``) matrices drawn directly in bfloat16,
    one tensor at a time (no float32 copy of the expert weights ever
    exists), under the source's parameter names; norm weights ones
    (float32); the head float32. Three departures from independent 0.02
    draws, each with its reason. The embedding at ``embedding_range`` (unit
    scale: ``models/laguna.init_laguna_params`` says why — a token's own
    vector, not the attention's running mean over its row, then decides its
    route).
    ``e_score_correction_bias`` normal(``bias_range``), float32: a trained
    checkpoint holds what its balancing left there, zeros would make a
    program that drops it, or weighs by ``s + b``, pass every comparison,
    and a scale of the order of the scores' own spread would choose by the
    bias alone (the same few experts for every token). ``bias_range`` is a
    few gaps between neighbouring scores at the eighth rank, so that the
    chosen set differs from the scores' own on a minority of the (token,
    layer) pairs; the configuration file has the share measured. The router
    stays at 0.02: a sigmoid saturates under a peaked router, the eight
    largest scores are then all 1 - 1e-3 and the bias alone ranks them.
    The routed experts of a layer are CORRELATED: each of their matrices
    is ``sqrt(1 - r^2) C + r N_e`` with ``r`` = ``expert_spread`` (a
    sixty-fourth), ``C`` one draw the layer's experts share and ``N_e``
    the expert's own, so every matrix is still normal(0.02) and every one
    of the 256 is stored, streamed and multiplied as its own. A sigmoid's
    eight chosen scores lie within a few per cent of one another at ANY
    router scale, so each chosen expert weighs an eighth of 2.5, and a
    rank-8 / rank-9 swap (bfloat16 rounding makes one on 2-4% of the
    (token, layer) pairs: the gap at the eighth rank is as scale-free as the
    rounding, so no seeded router or bias widens it) replaces 0.31 of an
    expert's output by another's. With independent experts that moves the
    token's residual by 7-11% and, where the token is a row's LAST, the
    answer by 1e-2 to 4e-2 — what float8 in every expert matmul costs, so no
    limit tells the stated precision from the one below it (about one seed
    in seven has such a swap). Laguna's cure, a peaked softmax whose eighth
    weight is small, is not open to a sigmoid. Correlated experts take the
    cost out of the SWAP (two experts differ by ~1.7 r of an expert's
    output) and leave it in the ARITHMETIC: the routed sum is 2.5 times one
    expert's output at full scale, the largest term a sparse layer adds, so
    float8 operands in the grouped matmuls read over the limit and a dropped
    group or a wrong tile far over it (the configuration file has the
    readings). What it costs: bfloat16's own rounding in that term is seen
    as well, so the sound program's readings reach further up than with
    small experts and the limit has less room; and on the chip the
    comparison with the reference no longer tells WHICH experts a token was
    sent to beyond ~r of the answer — the CPU tests hold that with
    independent experts (``TINY_JOYAI``: ``expert_spread`` 1) in float32 at
    every capacity."""
    h, heads = config.hidden_size, config.num_attention_heads
    e, i_ = config.n_routed_experts, config.moe_intermediate_size
    s_ = i_ * config.n_shared_experts

    def w(k, shape, dtype=jnp.bfloat16, std=config.initializer_range):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def ones(n=h):
        return jnp.ones((n,), jnp.float32)

    def experts(k, shape):
        k_common, k_own = jax.random.split(k)
        r = config.expert_spread
        common = jax.random.normal(k_common, shape[1:], jnp.float32)
        own = jax.random.normal(k_own, shape, jnp.float32)
        return ((float(np.sqrt(1.0 - r * r)) * common + r * own)
                * config.initializer_range).astype(jnp.bfloat16)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for li, lk in enumerate(jax.random.split(k_layers,
                                             config.num_hidden_layers)):
        k = jax.random.split(lk, 16)
        layer = {
            "input_layernorm": ones(),
            "q_a_proj": w(k[0], (h, config.q_lora_rank)),
            "q_a_layernorm": ones(config.q_lora_rank),
            "q_b_proj": w(k[1], (config.q_lora_rank,
                                 heads * config.qk_head_dim)),
            "kv_a_proj_with_mqa": w(k[2], (h, config.kv_lora_rank
                                           + config.qk_rope_head_dim)),
            "kv_a_layernorm": ones(config.kv_lora_rank),
            "kv_b_proj": w(k[3], (config.kv_lora_rank, heads * (
                config.qk_nope_head_dim + config.v_head_dim))),
            "o_proj": w(k[4], (heads * config.v_head_dim, h)),
            "post_attention_layernorm": ones(),
        }
        if li < config.first_k_dense_replace:
            f = config.dense_intermediate_size
            layer.update({"mlp_gate": w(k[5], (h, f)),
                          "mlp_up": w(k[6], (h, f)),
                          "mlp_down": w(k[7], (f, h))})
        else:
            layer.update({
                "router": w(k[8], (h, e)),
                "e_score_correction_bias": w(k[9], (e,), jnp.float32,
                                             std=config.bias_range),
                "gate_proj": experts(k[10], (e, h, i_)),
                "up_proj": experts(k[11], (e, h, i_)),
                "down_proj": experts(k[12], (e, i_, h)),
                "shared_gate": w(k[13], (h, s_)),
                "shared_up": w(k[14], (h, s_)),
                "shared_down": w(k[15], (s_, h)),
            })
        layers.append(layer)
    return {
        "embed_tokens": w(k_emb, (config.vocab_size, h),
                          std=config.embedding_range),
        "layers": layers,
        "norm": ones(),
        "score": w(k_head, (h, config.num_labels), jnp.float32),
    }


def joyai_rope_tables(seq_len: int, rotary_dim: int, theta: float
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """cos and sin ``f32[T, rotary_dim / 2]`` of positions 0..T-1, one
    column a rotated pair. Constants of the program, computed on the host
    in float64."""
    inv_freq = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64)
                         / rotary_dim)
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    return (np.cos(angles).astype(np.float32),
            np.sin(angles).astype(np.float32))


def rotate_pairs(x: jax.Array, cos, sin) -> jax.Array:
    """Interleaved RoPE on the last axis of ``x``: the pair ``(x[2i],
    x[2i+1])`` turned by the angle of column ``i`` (``cos`` and ``sin``
    broadcast against ``x[..., 0::2]``)."""
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def joyai_attention(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                    lengths: jax.Array, config: JoyaiConfig, cos, sin, *,
                    use_pallas: bool = False,
                    kernel_interpret: bool = False) -> jax.Array:
    """``h + o_proj(attn(...))`` on ``h`` ``f32[B, T, hidden]``: the first
    sublayer. ``use_pallas`` asks for the fused core
    (``ops.attention.windowed_attention`` in its latent form, which rotates
    the shared score term in VMEM); a shape it does not take
    (``JoyaiConfig.core_refusal``) runs the XLA form."""
    heads, eps = config.num_attention_heads, config.rms_norm_eps
    nope, pe, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                    config.v_head_dim)
    operand = layer["q_b_proj"].dtype
    b, t, _ = h.shape
    with jax.named_scope(scopes.LN):
        x = rms_norm(h, layer["input_layernorm"], eps)
    with jax.named_scope(scopes.ATTN_LATENT):
        c_q = rms_norm(_proj(x, layer["q_a_proj"]), layer["q_a_layernorm"],
                       eps)
        kv = _proj(x, layer["kv_a_proj_with_mqa"])
        c_kv = rms_norm(kv[..., :config.kv_lora_rank],
                        layer["kv_a_layernorm"], eps)
        k_pe = kv[..., config.kv_lora_rank:]                   # [B, T, pe]
    with jax.named_scope(scopes.ATTN_PROJ):
        # the checkpoint keeps a head's two parts side by side; each part
        # is projected by its own columns, so no pass splits the result
        w_q = layer["q_b_proj"].reshape(-1, heads, nope + pe)
        w_kv = layer["kv_b_proj"].reshape(-1, heads, nope + vd)

        def part(latent, w):
            return _proj(latent, w.reshape(w.shape[0], -1))

        q_nope = part(c_q, w_q[..., :nope]).astype(operand)    # [B, T, H*n]
        q_pe = part(c_q, w_q[..., nope:])                      # [B, T, H*pe]
        k_nope = part(c_kv, w_kv[..., :nope]).astype(operand)
        v = part(c_kv, w_kv[..., nope:]).astype(operand)       # [B, T, H*vd]
    if use_pallas and config.core_refusal(t) is None:
        # the shared term is rotated inside the kernel: no pass stands
        # between the projections and it
        *tables, shift = rope_pair_tables(cos, sin)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = windowed_attention(
                q_nope, k_nope, v, lengths, num_heads=heads,
                num_kv_heads=heads, rope=tuple(tables), rope_shift=shift,
                shared_key=(q_pe, k_pe), out_dtype=operand,
                interpret=kernel_interpret)                    # [B, T, H*vd]
    else:
        with jax.named_scope(scopes.ATTN_PROJ):
            # RoPE in float32; the rotated parts then take the operands'
            # dtype, as the kernel rounds them, and every head reads the
            # one shared key beside its own
            q_pe = rotate_pairs(q_pe.reshape(b, t, heads, pe),
                                cos[:, None], sin[:, None]).astype(operand)
            k_pe = rotate_pairs(k_pe, cos, sin).astype(operand)
            q = jnp.concatenate(
                [q_nope.reshape(b, t, heads, nope), q_pe], axis=-1)
            k = jnp.concatenate(
                [k_nope.reshape(b, t, heads, nope),
                 jnp.broadcast_to(k_pe[:, :, None], (b, t, heads, pe))],
                axis=-1)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = merge_heads(attention_reference(
                q.transpose(0, 2, 1, 3).astype(jnp.float32),
                k.transpose(0, 2, 1, 3).astype(jnp.float32),
                v.reshape(b, t, heads, vd).transpose(0, 2, 1, 3).astype(
                    jnp.float32), attention_mask, causal=True))
    with jax.named_scope(scopes.ATTN_PROJ):
        attn_out = _proj(ctx, layer["o_proj"])
    with jax.named_scope(scopes.LN):
        return h + attn_out


def joyai_route(layer: Dict, x: jax.Array, config: JoyaiConfig
                ) -> Tuple[jax.Array, jax.Array, None]:
    """``(experts i32[N, k], weights f32[N, k], None)`` for the normed rows
    ``x``: sigmoid scores over every expert, the k largest of ``score +
    bias`` weighted by the score alone, normalised over the chosen and
    scaled."""
    logits = jnp.dot(x.astype(jnp.float32), layer["router"].astype(
        jnp.float32), precision=jax.lax.Precision.HIGHEST)
    experts, weights = choose_experts(
        jax.nn.sigmoid(logits), config.num_experts_per_tok,
        bias=layer["e_score_correction_bias"],
        renormalise=config.norm_topk_prob, renormalise_eps=1e-20,
        scale=config.routed_scaling_factor)
    return experts, weights, None


def joyai_layer(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                lengths: jax.Array, config: JoyaiConfig, index: int,
                cos, sin, *,
                slots: Optional[Tuple[Optional[jax.Array], jax.Array]] = None,
                use_pallas: bool = False, kernel_interpret: bool = False
                ) -> Tuple[jax.Array, Optional[ExpertLoad]]:
    """Layer ``index`` on ``h`` ``f32[B, T, hidden]``: ``(h, load)``, the
    ``ExpertLoad`` of a sparse layer, None of a dense one."""
    b, t, width = h.shape
    h = joyai_attention(layer, h, attention_mask, lengths, config, cos, sin,
                        use_pallas=use_pallas,
                        kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        m = rms_norm(h, layer["post_attention_layernorm"],
                     config.rms_norm_eps)
    if index < config.first_k_dense_replace:
        with jax.named_scope(scopes.FFN):
            y = swiglu(m, layer["mlp_gate"], layer["mlp_up"],
                       layer["mlp_down"])
        with jax.named_scope(scopes.LN):
            return h + y, None
    if slots is None:
        slots = token_slots(attention_mask, None)
    y, load, _ = routed_block(
        layer, m.reshape(b * t, width), slots,
        lambda rows: joyai_route(layer, rows, config),
        shared=lambda rows: swiglu(rows, layer["shared_gate"],
                                   layer["shared_up"], layer["shared_down"]),
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        return h + y.reshape(b, t, width), load


def joyai_encode(params: Dict, input_ids: jax.Array,
                 attention_mask: jax.Array, config: JoyaiConfig, *,
                 capacity: Optional[int] = None,
                 use_pallas: bool = False, kernel_interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """Hidden states before the final norm ``f32[B, T, hidden]`` and the
    sparse layers' statistics ``i32[3, sparse layers]``
    (``olmoe.launch_stats``). ``capacity``: the token slots the routed
    blocks are compiled for (``models/olmoe.py``)."""
    cos, sin = joyai_rope_tables(input_ids.shape[1], config.qk_rope_head_dim,
                                 config.rope_theta)
    slots = token_slots(attention_mask, capacity)
    lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
    with jax.named_scope(scopes.EMBED):
        h = params["embed_tokens"][input_ids].astype(jnp.float32)
    loads = []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h, load = joyai_layer(
                layer, h, attention_mask, lengths, config, i, cos, sin,
                slots=slots, use_pallas=use_pallas,
                kernel_interpret=kernel_interpret)
        if load is not None:
            loads.append(load)
    return h, launch_stats(loads)


def joyai_predict(params: Dict, input_ids: jax.Array,
                  attention_mask: jax.Array, config: JoyaiConfig, *,
                  capacity: Optional[int] = None,
                  use_pallas: bool = False, kernel_interpret: bool = False,
                  with_stats: bool = False):
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]`` from the
    last real token; with ``with_stats`` also the sparse layers' statistics
    ``i32[3, sparse layers]`` (``olmoe_predict``'s second output; every
    expert is held, so the held pairs are the routers' pairs)."""
    hidden, stats = joyai_encode(
        params, input_ids, attention_mask, config, capacity=capacity,
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    logits = last_token_logits(params, hidden, attention_mask,
                               config.rms_norm_eps)
    p = jax.nn.softmax(logits, axis=-1)[:, 1]
    return (p, stats) if with_stats else p


TEXT_ENCODER = routed_encoder(JoyaiConfig, init_joyai_params, joyai_predict,
                              JoyaiConfig.core_refusal)
