"""NVIDIA-Nemotron-3-Nano-30B-A3B's stack as a text encoder, in pure JAX.

The sizes are ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s
``config.json`` (``NemotronHConfig`` holds every key of it under its own
name); the layer equations are written down from that file and Hugging
Face's ``modeling_nemotron_h.py`` conventions, each assumption listed in the
benchmark's configuration file. **A layer is ONE mixer**, and
``hybrid_override_pattern`` says which — ``M`` a Mamba-2 state-space mixer,
``E`` routed experts beside a shared one, ``*`` grouped-query attention. No
layer has an attention mixer AND a feed-forward block; the encoder is one
Python loop over unlike layers.

On a float32 residual ``h`` ``[T, hidden]`` (text right-padded; every mixer
is causal or pointwise, so no real position reads a padded one, nothing
masks the state-space mixer, and the answer is read at the last REAL
token):

0. ``h = Emb[ids]``. For layer ``i``: ``h += Mixer_i(RMSNorm(h; w_i,
   layer_norm_epsilon))`` — one norm a layer, no biases but the
   convolution's.
1. ``M``: ``p = u W_in``, split ``z`` (``d_inner`` = ``mamba_num_heads x
   mamba_head_dim``, NOT ``expand x hidden_size``) | ``xBC`` (``d_inner + 2
   n_groups ssm_state_size``) | ``dt`` (heads). ``xBC <- SiLU(conv(xBC))``,
   depthwise causal, ``conv_kernel`` taps with bias. ``dt <- softplus(dt +
   dt_bias)``, ``a = -exp(A_log)``. The recurrence (``ops/ssd_scan.py``),
   head ``j`` reading group ``j // (heads / n_groups)`` of ``B`` and ``C``:
   ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
   x_t``. Then the gate FIRST and a grouped norm, ``y <-
   GroupRMSNorm_{n_groups}(y * SiLU(z)) * w``; the output is ``y W_out``.
   (``models/falcon_h1.mamba2_mix``: the mixer Falcon-H1 runs beside its
   attention, here a layer of its own and without multipliers.)
2. ``*``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; query head ``g``
   reads key-value head ``g // (num_attention_heads /
   num_key_value_heads)``; causal ``softmax(q k^T / sqrt(head_dim)) v``;
   ``W_o``. **No rotary embedding**: the family's attention carries no
   positions (the mixers' recurrence does); ``rope_theta`` and
   ``partial_rotary_factor`` are held and read by nothing.
3. ``E``: ``s = sigmoid(u W_g)`` over all ``n_routed_experts`` in float32;
   the chosen are the ``num_experts_per_tok`` largest of ``s + b`` (``b``
   the layer's ``e_score_correction_bias``); the weights are ``s`` at the
   chosen, WITHOUT ``b``, over their sum + 1e-20 (``norm_topk_prob``), times
   ``routed_scaling_factor``. An expert is ``down(relu(up(u))^2)`` — two
   matrices, NO gate (``mlp_hidden_act`` ``relu2``) — of
   ``moe_intermediate_size``; the shared expert the same form at
   ``moe_shared_expert_intermediate_size``, added for every token.

The head is ``models/olmoe.py``'s (final RMSNorm, last real token, bias-free
``Linear(hidden -> 2)``, ``softmax[:, 1]``); the untied language-model head
is not held (no token is emitted).

What the routed-encoder seam reads (``models/text_encoder.py``):
``num_experts`` = ``n_routed_experts`` (every expert is held here),
``intermediate_size`` = ONE expert's width (the source's own meaning here)
and ``num_sparse_layers`` = the ``E`` layers; ``num_ssm_layers`` the ``M``.

Precision: weights stored bfloat16; bfloat16 matmul operands with float32
accumulation in ``W_in`` / ``W_out``, q / k / v / o, both contractions of
the attention core, both contractions of the scan and the routed and shared
experts (``x``, ``B``, ``C`` rounded to bfloat16 once, after the
convolution's SiLU; q, k and v as they leave their projections); float32
norms, softmax, convolution, softplus, decays, state, gate, ReLU and square,
and residual (the source's ``residual_in_fp32`` is false: this is more
precise, not less); the router in float32 at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from realtime_fraud_detection_tpu.models.falcon_h1 import mamba2_mix
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
from realtime_fraud_detection_tpu.models.text_encoder import (
    KernelSite,
    routed_encoder,
)
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    merge_heads,
    split_heads,
    windowed_attention,
    windowed_refusal,
)
from realtime_fraud_detection_tpu.ops.causal_conv import conv_refusal
from realtime_fraud_detection_tpu.ops.ssd_scan import ssd_refusal

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """``config.json`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, every key
    under its own name at its published value. What the equations above do
    not hold is refused by value (``__post_init__``), never ignored. Read
    by nothing, each with its reason in the benchmark's configuration file
    (``not_run``): ``num_logits_to_keep`` (the language-model head's),
    ``rope_theta`` / ``partial_rotary_factor`` (no rotation is applied),
    ``use_mamba_kernels``, ``rescale_prenorm_residual`` and the
    ``time_step_*`` keys (a checkpoint's initialisation), ``expand``,
    ``norm_eps`` and ``max_position_embeddings``."""

    attention_bias: bool = False
    chunk_size: int = 128
    conv_kernel: int = 4
    expand: int = 2
    head_dim: int = 128
    hidden_size: int = 2688
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    intermediate_size: int = 1856       # the source's: ONE expert's width
    layer_norm_epsilon: float = 1e-5
    mamba_head_dim: int = 64
    mamba_hidden_act: str = "silu"
    mamba_num_heads: int = 64
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    mlp_bias: bool = False
    mlp_hidden_act: str = "relu2"
    model_type: str = "nemotron_h"
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_group: int = 1
    n_groups: int = 8
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 6
    num_hidden_layers: int = 52
    num_key_value_heads: int = 2
    num_logits_to_keep: int = 1
    partial_rotary_factor: float = 1.0
    rescale_prenorm_residual: bool = True
    residual_in_fp32: bool = False
    rope_theta: float = 10000.0
    routed_scaling_factor: float = 2.5
    sliding_window: None = None
    ssm_state_size: int = 128
    tie_word_embeddings: bool = False
    time_step_floor: float = 1e-4
    time_step_max: float = 0.1
    time_step_min: float = 0.001
    topk_group: int = 1
    use_bias: bool = False
    use_conv_bias: bool = True
    use_mamba_kernels: bool = True
    vocab_size: int = 131072
    # not config.json keys: how the seeded weights are drawn
    # (``init_nemotron_h_params`` says why) and the classifier's width
    embedding_range: float = 1.0
    bias_range: float = 0.005
    expert_spread: float = 0.015625
    update_rms: float = 0.5
    context_rms: float = 0.24
    num_labels: int = 2

    def __post_init__(self) -> None:
        held = {"attention_bias": False, "mamba_proj_bias": False,
                "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
                "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
                "sliding_window": None, "model_type": "nemotron_h",
                "norm_topk_prob": True, "n_shared_experts": 1}
        for key, value in held.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"NemotronHConfig: {key} {getattr(self, key)!r} is not "
                    f"what the equations hold ({value!r})")
        pattern = self.hybrid_override_pattern
        unknown = set(pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown:
            raise ValueError(
                f"NemotronHConfig: hybrid_override_pattern {pattern!r} has "
                f"layers of kinds {sorted(unknown)}; M, E and * are what "
                "the equations hold ('-', a dense MLP layer, is in no "
                "published pattern of this model and is not built)")
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(
                f"NemotronHConfig: hybrid_override_pattern names "
                f"{len(pattern)} layers, num_hidden_layers "
                f"{self.num_hidden_layers}")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                "NemotronHConfig: group-limited routing is not built "
                f"(n_group {self.n_group}, topk_group {self.topk_group}: "
                "with one group it is the identity)")
        if self.d_inner == self.expand * self.hidden_size:
            raise ValueError(
                "NemotronHConfig: mamba_num_heads x mamba_head_dim equals "
                "expand x hidden_size, so which of the two readings of the "
                "mixer's width the equations take would go untested")
        if self.moe_intermediate_size != self.intermediate_size:
            raise ValueError(
                "NemotronHConfig: intermediate_size and "
                "moe_intermediate_size are both ONE expert's width")
        if self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("NemotronHConfig: the heads must divide into "
                             "their groups, the mixer's and attention's")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """What layer ``i`` is: ``M``, ``E`` or ``*``."""
        return tuple(self.hybrid_override_pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolved channels: ``x`` beside ``B`` and ``C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_num_heads

    @property
    def num_experts(self) -> int:
        """The routed experts an ``E`` layer holds, all of them, under the
        name the routed-encoder seam reads."""
        return self.n_routed_experts

    @property
    def num_sparse_layers(self) -> int:
        return self.hybrid_override_pattern.count(EXPERTS)

    @property
    def num_ssm_layers(self) -> int:
        return self.hybrid_override_pattern.count(MAMBA)

    def core_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA attention
        core where the fused one is asked for, or None where it holds the
        kernel (``ops.attention.windowed_refusal``: shapes alone)."""
        return windowed_refusal(seq_len, self.head_dim,
                                self.num_attention_heads,
                                self.num_key_value_heads, None)

    def scan_refusal(self, seq_len: int) -> Optional[str]:
        """The same of the mixer's scan (``ops.ssd_scan.ssd_refusal``)."""
        return ssd_refusal(seq_len, self.mamba_head_dim, self.ssm_state_size,
                           self.chunk_size, self.mamba_num_heads,
                           self.n_groups)

    def conv_refusal(self, seq_len: int) -> Optional[str]:
        """The same of the mixer's convolution
        (``ops.causal_conv.conv_refusal``) over ``x | B | C``."""
        gn = self.n_groups * self.ssm_state_size
        return conv_refusal(seq_len, (self.d_inner, gn, gn),
                            self.conv_kernel, offset=self.d_inner)


# the odd shapes kept: heads of 64 in groups of 8 over a state of 128, an
# expert width that is no whole number of lane tiles, hidden / 128 no whole
# number of sublane tiles, sixteen query heads a key-value head; every kind
# of layer, ``E`` twice
TINY_NEMOTRON_H = NemotronHConfig(
    vocab_size=30522, hidden_size=384, num_hidden_layers=5,
    hybrid_override_pattern="MEM*E", intermediate_size=144,
    moe_intermediate_size=144, moe_shared_expert_intermediate_size=288,
    n_routed_experts=16, num_experts_per_tok=4, mamba_num_heads=16,
    mamba_head_dim=64, n_groups=2, ssm_state_size=128, chunk_size=128,
    num_attention_heads=32, num_key_value_heads=2, head_dim=16,
    bias_range=0.01, expert_spread=1.0)


def init_nemotron_h_params(key: jax.Array, config: NemotronHConfig) -> Dict:
    """Seeded weights drawn directly in bfloat16, one tensor at a time (no
    float32 copy of an ``E`` layer's 1.3 B parameters ever exists), norm
    weights ones (float32), the head float32 at normal(0.02) as the other
    encoders'. A layer holds what its kind needs and nothing else.

    **A layer is one path**, so a layer whose update is small beside the
    residual is a layer no comparison checks. Every matrix INTO a mixer is
    drawn at ``1 / sqrt(fan-in)`` on a normed input (what it computes has
    RMS ~1: pre-activations where a SiLU, a softplus, a softmax or a
    squared ReLU has something to bend), and every matrix OUT of one so
    that the update has RMS ``update_rms`` (a half) where it is added: the
    residual starts at 1 (the embedding at ``embedding_range``, unit scale:
    ``models/laguna.init_laguna_params`` says why) and grows to ~2 over
    nine layers, so each layer's update is between a quarter and a half of
    it. By kind:

    - ``M``: ``W_in`` ``1 / sqrt(hidden)``; the gated, normed ``y`` has RMS
      1, so ``W_out`` is ``update_rms / sqrt(d_inner)``. The mixer's own
      parameters as Mamba-2's reference initialisation (``A_log = log U(1,
      16)``, ``dt_bias`` the inverse softplus of a log-uniform
      ``time_step_min..time_step_max``, ``D`` ones, the gated norm's weight
      ones), the convolution's taps normal(``1 / sqrt(conv_kernel)``) and
      its bias uniform in +-that (``models/falcon_h1.py`` says why).
    - ``*``: ``W_q``, ``W_k``, ``W_v`` ``1 / sqrt(hidden)`` (scores of RMS
      ~1: a softmax neither uniform nor one-hot). A context is an AVERAGE
      of values, of RMS ``context_rms`` at a row's last token, not 1 (a
      quarter, and the same at 675 tokens as at 1,917: what survives the
      average is what a row's positions share. Measured in the float32
      reference at the published widths on the cell's own text: the
      configuration file, ``assumed.weights``), so ``W_o`` is ``update_rms
      / (context_rms sqrt(q width))``.
    - ``E``: the router at ``1 / sqrt(hidden)`` (logits of RMS ~1: a
      sigmoid neither flat nor saturated); ``e_score_correction_bias``
      normal(``bias_range``), float32 — zeros would let a program that
      drops it, or weighs by ``s + b``, pass every comparison; a few gaps
      between neighbouring scores at the sixth rank, so the chosen set
      differs from the scores' own on a minority of the tokens. ``up`` at
      ``1 / sqrt(hidden)``: ``relu(z)^2`` of a unit normal has RMS
      ``sqrt(3 / 2)``. The routed experts of a layer are CORRELATED, each
      matrix ``sqrt(1 - r^2) C + r N_e`` with ``r`` = ``expert_spread``
      (``models/joyai.init_joyai_params`` says why: a sigmoid's chosen
      scores lie within a few per cent of one another, and a rank swap
      between independent experts reads like float8), so the routed sum is
      ``routed_scaling_factor`` times one expert's output and ``down`` is
      ``update_rms / (routed_scaling_factor sqrt(3 / 2) sqrt(width))``; the
      shared expert's ``down`` the same without the factor, at its own
      width: the two halves of an ``E`` layer's update weigh alike."""
    h, d = config.hidden_size, config.head_dim
    q_w, kv_w = config.num_attention_heads * d, config.num_key_value_heads * d
    e, i_ = config.n_routed_experts, config.moe_intermediate_size
    s_ = config.moe_shared_expert_intermediate_size
    heads, taps = config.mamba_num_heads, config.conv_kernel
    unit, out = 1.0 / math.sqrt(h), config.update_rms
    squared = math.sqrt(1.5)            # RMS of relu(z)^2, z a unit normal

    def w(k, shape, std, dtype=jnp.bfloat16):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def ones(n=h):
        return jnp.ones((n,), jnp.float32)

    def experts(k, shape, std):
        k_common, k_own = jax.random.split(k)
        r = config.expert_spread
        common = jax.random.normal(k_common, shape[1:], jnp.float32)
        own = jax.random.normal(k_own, shape, jnp.float32)
        return ((float(np.sqrt(1.0 - r * r)) * common + r * own)
                * std).astype(jnp.bfloat16)

    def mamba(k):
        dt = jnp.exp(jax.random.uniform(
            k[3], (heads,), jnp.float32, math.log(config.time_step_min),
            math.log(config.time_step_max)))
        return {
            "in_proj": w(k[0], (h, config.in_proj_dim), unit),
            "conv_weight": w(k[1], (taps, config.conv_dim),
                             1.0 / math.sqrt(taps), jnp.float32),
            "conv_bias": jax.random.uniform(
                k[2], (config.conv_dim,), jnp.float32,
                -1.0 / math.sqrt(taps), 1.0 / math.sqrt(taps)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k[4], (heads,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "mixer_norm": ones(config.d_inner),
            "out_proj": w(k[5], (config.d_inner, h),
                          out / math.sqrt(config.d_inner)),
        }

    def attention(k):
        return {
            "q_proj": w(k[0], (h, q_w), unit),
            "k_proj": w(k[1], (h, kv_w), unit),
            "v_proj": w(k[2], (h, kv_w), unit),
            "o_proj": w(k[3], (q_w, h),
                        out / (config.context_rms * math.sqrt(q_w))),
        }

    def routed(k):
        return {
            "router": w(k[0], (h, e), unit),
            "e_score_correction_bias": w(k[1], (e,), config.bias_range,
                                         jnp.float32),
            "up_proj": experts(k[2], (e, h, i_), unit),
            "down_proj": experts(k[3], (e, i_, h), out / (
                config.routed_scaling_factor * squared * math.sqrt(i_))),
            "shared_up": w(k[4], (h, s_), unit),
            "shared_down": w(k[5], (s_, h),
                             out / (squared * math.sqrt(s_))),
        }

    kinds = {MAMBA: mamba, ATTENTION: attention, EXPERTS: routed}
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = [
        {"norm": ones(), **kinds[kind](jax.random.split(lk, 6))}
        for kind, lk in zip(config.layer_kinds, jax.random.split(
            k_layers, config.num_hidden_layers))]
    return {
        "embed_tokens": w(k_emb, (config.vocab_size, h),
                          config.embedding_range),
        "layers": layers,
        "norm": ones(),
        "score": w(k_head, (h, config.num_labels), 0.02, jnp.float32),
    }


def nemotron_mixer(layer: Dict, u: jax.Array, config: NemotronHConfig, *,
                   use_pallas: bool = False, kernel_interpret: bool = False
                   ) -> jax.Array:
    """An ``M`` layer's mixer on the normed ``u`` ``f32[B, T, hidden]``.
    ``use_pallas`` asks for the scan's and the convolution's kernels; a
    shape one does not take (``NemotronHConfig.scan_refusal`` /
    ``conv_refusal``) runs its XLA form."""
    with jax.named_scope(scopes.SSM_PROJ), \
            jax.named_scope(scopes.SSM_IN_PROJ):
        p = _proj(u, layer["in_proj"])
    t = u.shape[1]
    return mamba2_mix(
        layer, p, heads=config.mamba_num_heads,
        head_dim=config.mamba_head_dim, groups=config.n_groups,
        state=config.ssm_state_size, chunk=config.chunk_size,
        eps=config.layer_norm_epsilon,
        scan_kernel=use_pallas and config.scan_refusal(t) is None,
        conv_kernel=use_pallas and config.conv_refusal(t) is None,
        kernel_interpret=kernel_interpret)


def nemotron_attention(layer: Dict, u: jax.Array, attention_mask: jax.Array,
                       lengths: jax.Array, config: NemotronHConfig, *,
                       use_pallas: bool = False,
                       kernel_interpret: bool = False) -> jax.Array:
    """A ``*`` layer's mixer on the normed ``u``: grouped-query causal
    attention with NO rotation. ``use_pallas`` asks for the fused core
    (``ops.attention.windowed_attention`` handed no tables: q and k go to
    the contraction as their projections wrote them)."""
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    operand = layer["q_proj"].dtype
    with jax.named_scope(scopes.ATTN_PROJ):
        q = _proj(u, layer["q_proj"]).astype(operand)          # [B, T, H*D]
        k = _proj(u, layer["k_proj"]).astype(operand)
        v = _proj(u, layer["v_proj"]).astype(operand)
    if use_pallas and config.core_refusal(u.shape[1]) is None:
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = windowed_attention(
                q, k, v, lengths, num_heads=heads, num_kv_heads=kv,
                out_dtype=operand, interpret=kernel_interpret)
    else:
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = merge_heads(attention_reference(
                split_heads(q, heads).astype(jnp.float32),
                split_heads(k, kv).astype(jnp.float32),
                split_heads(v, kv).astype(jnp.float32), attention_mask,
                causal=True))
    with jax.named_scope(scopes.ATTN_PROJ):
        return _proj(ctx, layer["o_proj"])


def relu2_mlp(x: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``relu(x W_up)^2 W_down``: bf16 operands, f32 results."""
    return _proj(jnp.square(jax.nn.relu(_proj(x, w_up))), w_down)


def nemotron_route(layer: Dict, x: jax.Array, config: NemotronHConfig
                   ) -> Tuple[jax.Array, jax.Array, None]:
    """``(experts i32[N, k], weights f32[N, k], None)`` for the normed rows
    ``x``: sigmoid scores over every expert, the k largest of ``score +
    bias`` weighted by the score alone, normalised over the chosen and
    scaled (``models/joyai.joyai_route``'s rule under this source's
    keys)."""
    logits = jnp.dot(x.astype(jnp.float32), layer["router"].astype(
        jnp.float32), precision=jax.lax.Precision.HIGHEST)
    experts, weights = choose_experts(
        jax.nn.sigmoid(logits), config.num_experts_per_tok,
        bias=layer["e_score_correction_bias"],
        renormalise=config.norm_topk_prob, renormalise_eps=1e-20,
        scale=config.routed_scaling_factor)
    return experts, weights, None


def nemotron_layer(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                   lengths: jax.Array, config: NemotronHConfig, index: int,
                   *, slots: Optional[Tuple[Optional[jax.Array],
                                            jax.Array]] = None,
                   use_pallas: bool = False, kernel_interpret: bool = False
                   ) -> Tuple[jax.Array, Optional[ExpertLoad]]:
    """Layer ``index`` on ``h`` ``f32[B, T, hidden]``: ``(h, load)``, the
    ``ExpertLoad`` of an ``E`` layer, None of the others."""
    b, t, width = h.shape
    kind = config.layer_kinds[index]
    kernels = dict(use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        u = rms_norm(h, layer["norm"], config.layer_norm_epsilon)
    load = None
    if kind == MAMBA:
        y = nemotron_mixer(layer, u, config, **kernels)
    elif kind == ATTENTION:
        y = nemotron_attention(layer, u, attention_mask, lengths, config,
                               **kernels)
    else:
        if slots is None:
            slots = token_slots(attention_mask, None)
        y, load, _ = routed_block(
            layer, u.reshape(b * t, width), slots,
            lambda rows: nemotron_route(layer, rows, config),
            shared=lambda rows: relu2_mlp(rows, layer["shared_up"],
                                          layer["shared_down"]),
            **kernels)
        y = y.reshape(b, t, width)
    with jax.named_scope(scopes.LN):
        return h + y, load


def nemotron_h_encode(params: Dict, input_ids: jax.Array,
                      attention_mask: jax.Array, config: NemotronHConfig, *,
                      capacity: Optional[int] = None,
                      use_pallas: bool = False, kernel_interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """Hidden states before the final norm ``f32[B, T, hidden]`` and the
    ``E`` layers' statistics ``i32[3, E layers]`` (``olmoe.launch_stats``).
    ``capacity``: the token slots the routed blocks are compiled for
    (``models/olmoe.py``); the ``M`` and ``*`` layers run every slot."""
    slots = token_slots(attention_mask, capacity)
    lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
    with jax.named_scope(scopes.EMBED):
        h = params["embed_tokens"][input_ids].astype(jnp.float32)
    loads = []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h, load = nemotron_layer(
                layer, h, attention_mask, lengths, config, i, slots=slots,
                use_pallas=use_pallas, kernel_interpret=kernel_interpret)
        if load is not None:
            loads.append(load)
    return h, launch_stats(loads)


def nemotron_h_predict(params: Dict, input_ids: jax.Array,
                       attention_mask: jax.Array, config: NemotronHConfig, *,
                       capacity: Optional[int] = None,
                       use_pallas: bool = False,
                       kernel_interpret: bool = False,
                       with_stats: bool = False):
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]`` from the
    last real token; with ``with_stats`` also the ``E`` layers' statistics
    ``i32[3, E layers]`` (``olmoe_predict``'s second output; every expert
    is held, so the held pairs are the routers' pairs)."""
    hidden, stats = nemotron_h_encode(
        params, input_ids, attention_mask, config, capacity=capacity,
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    logits = last_token_logits(params, hidden, attention_mask,
                               config.layer_norm_epsilon)
    p = jax.nn.softmax(logits, axis=-1)[:, 1]
    return (p, stats) if with_stats else p


def _ssm_chunks(config, launches, lengths):
    slots = sum(la.size * la.width for la in launches)
    return {"ssm_chunks": slots // config.chunk_size * config.num_ssm_layers}


# routed AND state-space: the routed encoders' row (capacity rungs, the
# second output, the experts' two sites) with the scan's site and its
# counter beside them (models/text_encoder.py)
TEXT_ENCODER = routed_encoder(
    NemotronHConfig, init_nemotron_h_params, nemotron_h_predict,
    NemotronHConfig.core_refusal,
    sites=(KernelSite("ssm_scan",
                      lambda c, width, slots: c.scan_refusal(width)),
           KernelSite("causal_conv",
                      lambda c, width, slots: c.conv_refusal(width))),
    dispatch_counters=_ssm_chunks, expert_matrices=1)
