"""Qwen3-Next-80B-A3B-Instruct's stack as a text encoder, in pure JAX.

The sizes are ``Qwen/Qwen3-Next-80B-A3B-Instruct``'s ``config.json``
(``Qwen3NextConfig`` holds every key of it under its own name, beside the
three the routed-encoder seam reads its own way, below); the layer equations
are written down from that file and Hugging Face's ``modeling_qwen3_next.py``
conventions, each assumption listed in the benchmark's configuration file.
**Three layers in four mix by a linear recurrence** (``L``: Gated DeltaNet,
``ops/delta_scan.py``) **and the fourth by gated softmax attention**
(``F``): layer ``i`` is ``F`` where ``(i + 1) % full_attention_interval ==
0``. Every layer's second half is routed experts beside a shared one under
a scalar gate.

On a float32 residual ``h`` ``[T, hidden]`` (text right-padded; every mixer
is causal or pointwise, so no real position reads a padded one, nothing
masks the recurrence, and the answer is read at the last REAL token), with
``ZNorm(x; w) = x rsqrt(mean(x^2) + rms_norm_eps) (1 + w)`` — the weight is
ZERO-centred:

0. ``h = Emb[ids]``. For layer ``i``: ``h += Mixer_i(ZNorm(h))``; ``h +=
   MoE_i(ZNorm(h))``; no biases anywhere.
1. ``L``: ``in_proj_qkvz`` (``hidden -> 2 key_dim + 2 value_dim``, the
   checkpoint's columns grouped by KEY head: ``[q | k | v x ratio | z x
   ratio]`` a key head — the parts are taken by slicing the WEIGHT, so no
   pass splits the result) and ``in_proj_ba`` (``hidden -> 2 value heads``,
   ``[b x ratio | a x ratio]`` a key head). ``q | k | v <- SiLU(conv(q | k |
   v))``, depthwise causal, ``linear_conv_kernel_dim`` taps, NO bias (``z``
   is not convolved). Per head ``q <- q / sqrt(sum q^2 + 1e-6)``, ``k``
   likewise, ``q <- q key_head_dim^-1/2``; value head ``j`` reads key head
   ``j // ratio``. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
   dt_bias)``. The recurrence (``ops.delta_scan.gated_delta_scan``): ``S_t =
   e^g S_{t-1} + beta k_t (v_t - e^g S_{t-1}^T k_t)^T``, ``o_t = S_t^T
   q_t``. Then per head the norm FIRST and the gate after, ``o <-
   RMSNorm(o; w) SiLU(z)`` (a plain weight, not zero-centred; the reverse
   of Mamba-2's order in ``falcon_h1.mamba2_mix``); ``out_proj``.
2. ``F``: ``q_proj`` is twice as wide as the heads (a head's columns
   ``query | gate``, parted in the WEIGHT likewise); ``query <-
   ZNorm_head(query; w_q)``, ``key <- ZNorm_head(key; w_k)`` (one weight of
   ``head_dim`` the heads share); rotate-half RoPE on a head's first
   ``partial_rotary_factor head_dim`` dims; query head ``g`` reads key-value
   head ``g // (heads / kv heads)``; causal ``softmax(q k^T head_dim^-1/2)
   v``; ``ctx <- ctx sigmoid(gate)`` ELEMENTWISE; ``o_proj``.
3. MoE: ``p = softmax(x W_g)`` over all ``router_experts`` in float32; the
   ``num_experts_per_tok`` largest, renormalised over the chosen
   (``norm_topk_prob``); ``sum_e w_e E_e(x) + sigmoid(x w_sg) S(x)``, every
   ``E_e`` and the shared ``S`` a SwiGLU.

**This chip's share of the experts** is ``models/laguna.py``'s:
``num_experts`` is how many routed experts a layer HOLDS here,
``router_experts`` the router's published width, ``expert_offset`` the
first held one's number (``models/olmoe.apply_experts``). The mixers, the
router and the shared expert are held whole.

The head is ``models/olmoe.py``'s on a final ``ZNorm`` (last real token,
bias-free ``Linear(hidden -> 2)``, ``softmax[:, 1]``); the untied
language-model head is not held (no token is emitted).

Precision: weights stored bfloat16; bfloat16 matmul operands with float32
accumulation in every projection, both contractions of the attention core,
the scan's products (``ops/delta_scan.py`` says which) and the routed and
shared experts (``q``, ``k`` and ``v`` of an ``L`` layer rounded once, after
the convolution's SiLU and the L2 norm; of an ``F`` layer after norm and
rotation); float32 norms, softmaxes, convolution, softplus, decays, the
solve, the state, gates and residual; the router in float32 at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from realtime_fraud_detection_tpu.models.falcon_h1 import conv_silu_parts
from realtime_fraud_detection_tpu.models.laguna import _rotate, swiglu
from realtime_fraud_detection_tpu.models.olmoe import (
    _proj,
    ExpertLoad,
    choose_experts,
    last_token_logits,
    launch_stats,
    rms_norm,
    rope_tables,
    routed_block,
    router_probs,
    token_slots,
)
from realtime_fraud_detection_tpu.models.text_encoder import (
    KernelSite,
    routed_encoder,
)
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    LANES,
    attention_reference,
    merge_heads,
    rope_lane_tables,
    split_heads,
    windowed_attention,
    windowed_refusal,
)
from realtime_fraud_detection_tpu.ops.causal_conv import conv_refusal
from realtime_fraud_detection_tpu.ops.delta_scan import (
    delta_refusal,
    gated_delta_scan,
)

LINEAR, FULL = "L", "F"
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """``config.json`` of Qwen3-Next-80B-A3B-Instruct, every key under its
    own name at its published value, but for ``num_experts``: the routed
    experts a layer HOLDS here (the name the routed-encoder seam reads),
    beside ``router_experts``, the published count, and ``expert_offset``.
    What the equations above do not hold is refused by value
    (``__post_init__``), never ignored. Read by nothing, each with its
    reason in the benchmark's configuration file (``not_run``):
    ``intermediate_size`` (no dense layer exists), ``max_position_embeddings``,
    ``tie_word_embeddings`` (no language-model head is held)."""

    decoder_sparse_step: int = 1
    full_attention_interval: int = 4
    head_dim: int = 256
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 5120
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    max_position_embeddings: int = 262144
    mlp_only_layers: Tuple[int, ...] = ()
    model_type: str = "qwen3_next"
    moe_intermediate_size: int = 512    # width of ONE routed expert
    norm_topk_prob: bool = True
    num_attention_heads: int = 16
    num_experts: int = 512              # routed experts a layer holds HERE
    num_experts_per_tok: int = 10
    num_hidden_layers: int = 48
    num_key_value_heads: int = 2
    partial_rotary_factor: float = 0.25
    rms_norm_eps: float = 1e-6
    rope_scaling: None = None
    rope_theta: float = 10000000.0
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    vocab_size: int = 151936
    # not config.json keys: the share of the experts (``models/laguna.py``),
    # the scan's chunk, how the seeded weights are drawn
    # (``init_qwen3_next_params`` says why) and the classifier's width
    router_experts: int = 512           # the router's width, as published
    expert_offset: int = 0              # the first held expert's number
    delta_chunk: int = 64
    embedding_range: float = 1.0
    norm_range: float = 0.1
    router_logit_rms: float = 2.0
    expert_spread: float = 0.015625
    update_rms: float = 0.5
    context_rms: float = 0.13
    num_labels: int = 2

    def __post_init__(self) -> None:
        held = {"decoder_sparse_step": 1, "mlp_only_layers": (),
                "hidden_act": "silu", "model_type": "qwen3_next",
                "norm_topk_prob": True, "rope_scaling": None,
                "use_sliding_window": False}
        for key, value in held.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"Qwen3NextConfig: {key} {getattr(self, key)!r} is not "
                    f"what the equations hold ({value!r}: every layer "
                    "sparse, SiLU, weights renormalised over the chosen, "
                    "unscaled rotation, no window)")
        if self.full_attention_interval < 1:
            raise ValueError("Qwen3NextConfig: full_attention_interval "
                             f"{self.full_attention_interval}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("Qwen3NextConfig: the heads must divide into "
                             "their key heads, attention's and the mixer's")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.num_experts
                <= self.router_experts):
            raise ValueError(
                f"Qwen3NextConfig: experts {self.expert_offset}.."
                f"{self.expert_offset + self.num_experts} of a router "
                f"{self.router_experts} wide")
        rot = self.head_dim * self.partial_rotary_factor
        if rot != int(rot) or int(rot) % 2 or not 0 < rot <= self.head_dim:
            raise ValueError("Qwen3NextConfig: partial_rotary_factor must "
                             "leave an even number of rotated dims")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """What layer ``i``'s mixer is: ``L`` or ``F``."""
        return tuple(
            FULL if (i + 1) % self.full_attention_interval == 0 else LINEAR
            for i in range(self.num_hidden_layers))

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolved channels: ``q | k | v``."""
        return 2 * self.key_dim + self.value_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def num_sparse_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_delta_layers(self) -> int:
        return self.layer_kinds.count(LINEAR)

    def core_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA attention
        core where the fused one is asked for, or None where it holds the
        kernel (``ops.attention.windowed_refusal``: shapes alone)."""
        if self.rotary_dim > LANES:
            return (f"windowed_attention rotates inside a head's first lane "
                    f"tile: {self.rotary_dim} rotated dims")
        return windowed_refusal(seq_len, self.head_dim,
                                self.num_attention_heads,
                                self.num_key_value_heads, None,
                                head_norm=True)

    def scan_refusal(self, seq_len: int) -> Optional[str]:
        """The same of the mixer's scan (``ops.delta_scan.delta_refusal``)."""
        return delta_refusal(seq_len, self.linear_key_head_dim,
                             self.linear_value_head_dim, self.delta_chunk,
                             self.linear_num_key_heads,
                             self.linear_num_value_heads)

    def conv_refusal(self, seq_len: int) -> Optional[str]:
        """The same of the mixer's convolution
        (``ops.causal_conv.conv_refusal``) over ``q | k | v``."""
        return conv_refusal(seq_len,
                            (self.key_dim, self.key_dim, self.value_dim),
                            self.linear_conv_kernel_dim)


# a whole period and one layer more (LLLFL), a quarter of the router's
# experts held from the second quarter on, two value heads a key head, eight
# query heads over two key-value heads, a quarter of a head rotated, a chunk
# that a test's 32 positions cross three times
TINY_QWEN3_NEXT = Qwen3NextConfig(
    vocab_size=30522, hidden_size=128, intermediate_size=256,
    num_hidden_layers=5, full_attention_interval=4, head_dim=32,
    num_attention_heads=8, num_key_value_heads=2, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, moe_intermediate_size=64,
    shared_expert_intermediate_size=64, router_experts=32, num_experts=8,
    expert_offset=8, num_experts_per_tok=4, delta_chunk=8,
    expert_spread=1.0)


def init_qwen3_next_params(key: jax.Array, config: Qwen3NextConfig) -> Dict:
    """Seeded weights drawn directly in bfloat16, one tensor at a time (no
    float32 copy of a layer's 0.8 B expert parameters ever exists), the head
    float32 at normal(0.02) as the other encoders'. A layer holds what its
    kind needs and nothing else.

    **Every path in sight of the comparison** (``models/nemotron_h.
    init_nemotron_h_params`` says why): every matrix INTO a mixer or an
    expert at ``1 / sqrt(fan-in)`` on a normed input (pre-activations of RMS
    ~1), every matrix OUT so that the update has RMS ``update_rms`` (a half)
    where it is added to a residual that starts at 1 (``embedding_range``)
    and grows to ~2 over twelve updates. Departures, each with its reason:

    - every zero-centred norm weight normal(``norm_range``) — at the
      source's zeros a program that reads ``1 + w`` as ``w`` would put out
      nothing, but one that drops the ``1 +`` from a SINGLE norm of many
      would pass at a scale the next norm takes back; the mixer's gated
      norm keeps its plain ones;
    - ``A_log = log U(1, 2)`` and ``dt_bias`` the inverse softplus of a
      log-uniform 1e-3..5e-2, so that a head's decay ``exp(g)`` lies between
      ~0.9 and ~0.999 a token and the heads differ: the source's ``A ~ U(0,
      16)`` forgets within a token, and a scan that carried no state over a
      chunk boundary would then pass;
    - the convolution's taps normal(``1 / sqrt(taps)``) (a sum of four of
      RMS 1: ``models/falcon_h1.py``);
    - an ``L`` layer's gated, normed ``o`` has RMS ``rms(SiLU(z))`` = 0.6,
      so ``out_proj`` is ``update_rms / (0.6 sqrt(value_dim))``; an ``F``
      layer's gated context has RMS ``context_rms`` at a row's last token
      (an AVERAGE of values under a sigmoid: ``models/nemotron_h.py``'s
      measured 0.24 times the gate's 0.54), so ``o_proj`` is ``update_rms /
      (context_rms sqrt(q width))``;
    - the router at ``router_logit_rms / sqrt(hidden)``, logits of RMS 2,
      and the routed experts of a layer CORRELATED, each matrix ``sqrt(1 -
      r^2) C + r N_e`` with ``r`` = ``expert_spread`` (a sixty-fourth:
      ``models/joyai.init_joyai_params``). This chip holds half the experts,
      so a swap at the tenth rank between a held and an absent expert adds
      or drops a whole expert's row: the more peaked the softmax, the less
      that row weighs (``models/laguna.init_laguna_params``) — but a
      softmax's weights follow its input ``router_logit_rms`` times as
      steeply, and with independent experts every layer then multiplied the
      rounding it was handed (at logits of RMS 4, TINY widths, one row in
      eight read ten times its neighbours). 2 and correlated experts read
      the steadiest of six draws tried, with float8 operands fifteen times
      the sound program's reading (``PERF.md``, PR 54). The routed sum is
      then one expert's output times the HELD MASS of a token's weights
      (RMS ~0.58) beside ``r`` times its own part (root-sum-square ~0.4); a
      SwiGLU's product has RMS 0.6 and the shared expert's gate 0.54:
      ``down_proj`` and ``shared_down`` are scaled so that each half of the
      block's update is ``update_rms / sqrt(2)``. ``TINY_QWEN3_NEXT`` keeps
      the experts independent, so that a routing fault is in sight."""
    h, d = config.hidden_size, config.head_dim
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    hk, hv = config.linear_num_key_heads, config.linear_num_value_heads
    dv, taps = config.linear_value_head_dim, config.linear_conv_kernel_dim
    e, i_ = config.num_experts, config.moe_intermediate_size
    s_ = config.shared_expert_intermediate_size
    unit, out = 1.0 / math.sqrt(h), config.update_rms
    half = out / math.sqrt(2.0)
    silu_rms, gate_rms, held_rss, held_mass = 0.6, 0.54, 0.4, 0.58

    def w(k, shape, std, dtype=jnp.bfloat16):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def centred(k, n=h):
        return w(k, (n,), config.norm_range, jnp.float32)

    def linear(k):
        dt = jnp.exp(jax.random.uniform(
            k[3], (hv,), jnp.float32, math.log(1e-3), math.log(5e-2)))
        return {
            "in_proj_qkvz": w(k[0], (h, 2 * config.key_dim
                                     + 2 * config.value_dim), unit),
            "in_proj_ba": w(k[1], (h, 2 * hv), unit),
            "conv_weight": w(k[2], (taps, config.conv_dim),
                             1.0 / math.sqrt(taps), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k[4], (hv,), jnp.float32, 1.0, 2.0)),
            "delta_norm": jnp.ones((dv,), jnp.float32),
            "out_proj": w(k[5], (config.value_dim, h),
                          out / (silu_rms * math.sqrt(config.value_dim))),
        }

    def full(k):
        return {
            "q_proj": w(k[0], (h, 2 * heads * d), unit),
            "k_proj": w(k[1], (h, kv * d), unit),
            "v_proj": w(k[2], (h, kv * d), unit),
            "q_norm": centred(k[3], d), "k_norm": centred(k[4], d),
            "o_proj": w(k[5], (heads * d, h),
                        out / (config.context_rms * math.sqrt(heads * d))),
        }

    def experts(k, shape, std):
        r = config.expert_spread
        if r == 1.0:
            return w(k, shape, std)
        k_common, k_own = jax.random.split(k)
        common = jax.random.normal(k_common, shape[1:], jnp.float32)
        own = jax.random.normal(k_own, shape, jnp.float32)
        return ((math.sqrt(1.0 - r * r) * common + r * own)
                * std).astype(jnp.bfloat16)

    r2 = config.expert_spread ** 2
    routed_rms = math.sqrt((1.0 - r2) * held_mass ** 2 + r2 * held_rss ** 2)

    def sparse(k):
        return {
            "router": w(k[0], (h, config.router_experts),
                        config.router_logit_rms * unit),
            "gate_proj": experts(k[1], (e, h, i_), unit),
            "up_proj": experts(k[2], (e, h, i_), unit),
            "down_proj": experts(k[3], (e, i_, h),
                                 half / (routed_rms * silu_rms
                                         * math.sqrt(i_))),
            "shared_gate": w(k[4], (h, s_), unit),
            "shared_up": w(k[5], (h, s_), unit),
            "shared_down": w(k[6], (s_, h),
                             half / (gate_rms * silu_rms * math.sqrt(s_))),
            "shared_expert_gate": w(k[7], (h, 1), unit),
        }

    mixers = {LINEAR: linear, FULL: full}
    k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
    layers = []
    for kind, lk in zip(config.layer_kinds, jax.random.split(
            k_layers, config.num_hidden_layers)):
        k = jax.random.split(lk, 16)
        layers.append({
            "input_layernorm": centred(k[0]),
            **mixers[kind](k[1:7]),
            "post_attention_layernorm": centred(k[7]),
            **sparse(k[8:16]),
        })
    return {
        "embed_tokens": w(k_emb, (config.vocab_size, h),
                          config.embedding_range),
        "layers": layers,
        "norm": centred(k_norm),
        "score": w(k_head, (h, config.num_labels), 0.02, jnp.float32),
    }


def znorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMSNorm: ``x rsqrt(mean(x^2) + eps) (1 + w)``."""
    return rms_norm(x, 1.0 + weight, eps)


def l2_norm(x: jax.Array) -> jax.Array:
    """``x / sqrt(sum(x^2) + 1e-6)`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gated_head_norm(o: jax.Array, z: jax.Array, weight: jax.Array,
                    eps: float) -> jax.Array:
    """The mixer's output norm, a head at a time over the last axis: the
    norm FIRST, under a plain weight, the gate after — ``RMSNorm(o; w)
    SiLU(z)`` (Mamba-2's ``falcon_h1.mamba2_mix`` gates first)."""
    return rms_norm(o, weight, eps) * jax.nn.silu(z)


def delta_mixer(layer: Dict, u: jax.Array, config: Qwen3NextConfig, *,
                use_pallas: bool = False, kernel_interpret: bool = False
                ) -> jax.Array:
    """An ``L`` layer's mixer on the normed ``u`` ``f32[B, T, hidden]``.
    ``use_pallas`` asks for the scan's and the convolution's kernels; a
    shape one does not take (``Qwen3NextConfig.scan_refusal`` /
    ``conv_refusal``) runs its XLA form."""
    b, t, h = u.shape
    hk, hv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    ratio, operand = hv // hk, layer["in_proj_qkvz"].dtype
    with jax.named_scope(scopes.DELTA_PROJ):
        # the checkpoint keeps a key head's parts side by side; each part
        # is projected by its own columns, so no pass splits the result
        grouped = layer["in_proj_qkvz"].reshape(h, hk, -1)

        def part(lo, hi):
            return grouped[..., lo:hi].reshape(h, -1)

        v_end = 2 * dk + ratio * dv
        qkv = _proj(u, jnp.concatenate(
            [part(0, dk), part(dk, 2 * dk), part(2 * dk, v_end)], axis=1))
        z = _proj(u, part(v_end, v_end + ratio * dv))          # [B, T, Hv*D]
        ba = _proj(u, layer["in_proj_ba"]).reshape(b, t, hk, 2 * ratio)
    with jax.named_scope(scopes.DELTA_CONV):
        # q and k stay float32 for their norms; v is rounded as the scan
        # reads it
        q, k, v = conv_silu_parts(
            qkv, layer["conv_weight"], None, (hk * dk, hk * dk, hv * dv),
            (jnp.float32, jnp.float32, operand),
            kernel=use_pallas and config.conv_refusal(t) is None,
            interpret=kernel_interpret)
        with jax.named_scope(scopes.DELTA_QK_NORM):
            q = l2_norm(q.reshape(b, t, hk, dk)) * dk ** -0.5
            k = l2_norm(k.reshape(b, t, hk, dk))
        v = v.reshape(b, t, hv, dv)
        with jax.named_scope(scopes.DELTA_GATES):
            beta = jax.nn.sigmoid(ba[..., :ratio].reshape(b, t, hv))
            g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
                ba[..., ratio:].reshape(b, t, hv) + layer["dt_bias"])
    with jax.named_scope(scopes.DELTA_SCAN):
        o, _ = gated_delta_scan(
            q.astype(operand), k.astype(operand), v, g, beta,
            chunk=config.delta_chunk,
            use_pallas=use_pallas and config.scan_refusal(t) is None,
            interpret=kernel_interpret)
    with jax.named_scope(scopes.DELTA_PROJ):
        y = gated_head_norm(o, z.reshape(b, t, hv, dv), layer["delta_norm"],
                            config.rms_norm_eps)
        return _proj(y.reshape(b, t, hv * dv), layer["out_proj"])


def gated_attention(layer: Dict, u: jax.Array, attention_mask: jax.Array,
                    lengths: jax.Array, config: Qwen3NextConfig, cos, sin, *,
                    use_pallas: bool = False, kernel_interpret: bool = False
                    ) -> jax.Array:
    """An ``F`` layer's mixer on the normed ``u``. ``use_pallas`` asks for
    the fused core (``ops.attention.windowed_attention`` handed the per-head
    norms' weights, the rotation's tables and the gates: q and k go to it
    as their projections wrote them)."""
    b, t, h = u.shape
    heads, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
    operand, eps = layer["q_proj"].dtype, config.rms_norm_eps
    with jax.named_scope(scopes.ATTN_PROJ):
        # a head's columns are query | gate: parted in the weight
        both = layer["q_proj"].reshape(h, heads, 2, d)
        q = _proj(u, both[:, :, 0].reshape(h, heads * d))      # [B, T, H*D]
        gate = jax.nn.sigmoid(_proj(u, both[:, :, 1].reshape(h, heads * d)))
        k = _proj(u, layer["k_proj"])                          # [B, T, kv*D]
        v = _proj(u, layer["v_proj"]).astype(operand)
    if use_pallas and config.core_refusal(t) is None:
        *tables, shift = rope_lane_tables(cos, sin, LANES)
        with jax.named_scope(scopes.ATTN_CORE):
            gated = windowed_attention(
                q, k, v, lengths, num_heads=heads, num_kv_heads=kv,
                rope=tuple(tables), rope_shift=shift, gate=gate,
                head_norm=(1.0 + layer["q_norm"], 1.0 + layer["k_norm"]),
                norm_eps=eps, out_dtype=operand, interpret=kernel_interpret)
    else:
        with jax.named_scope(scopes.ATTN_PROJ):
            cos_, sin_ = cos[:, None, :], sin[:, None, :]
            q = _rotate(znorm(q.reshape(b, t, heads, d), layer["q_norm"],
                              eps), cos_, sin_).astype(operand)
            k = _rotate(znorm(k.reshape(b, t, kv, d), layer["k_norm"], eps),
                        cos_, sin_).astype(operand)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = merge_heads(attention_reference(
                q.transpose(0, 2, 1, 3).astype(jnp.float32),
                k.transpose(0, 2, 1, 3).astype(jnp.float32),
                split_heads(v, kv).astype(jnp.float32), attention_mask,
                causal=True))
        with jax.named_scope(scopes.ATTN_PROJ):
            gated = ctx * gate
    with jax.named_scope(scopes.ATTN_PROJ):
        return _proj(gated, layer["o_proj"])


def qwen3_next_route(layer: Dict, x: jax.Array, config: Qwen3NextConfig
                     ) -> Tuple[jax.Array, jax.Array, None]:
    """``(experts i32[N, k] in the router's numbers, weights f32[N, k],
    None)`` for the normed rows ``x``: softmax over every published expert,
    the k largest renormalised over the chosen."""
    experts, weights = choose_experts(
        router_probs(x, layer["router"]), config.num_experts_per_tok,
        renormalise=config.norm_topk_prob)
    return experts, weights, None


def gated_shared_expert(layer: Dict, rows: jax.Array) -> jax.Array:
    """``sigmoid(x w_sg) S(x)``: the shared SwiGLU under its scalar gate
    (float32, one logit a token)."""
    gate = jax.nn.sigmoid(_proj(rows, layer["shared_expert_gate"]))
    return gate * swiglu(rows, layer["shared_gate"], layer["shared_up"],
                         layer["shared_down"])


def qwen3_next_layer(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                     lengths: jax.Array, config: Qwen3NextConfig, index: int,
                     cos, sin, *,
                     slots: Optional[Tuple[Optional[jax.Array],
                                           jax.Array]] = None,
                     use_pallas: bool = False, kernel_interpret: bool = False
                     ) -> Tuple[jax.Array, ExpertLoad]:
    """Layer ``index`` on ``h`` ``f32[B, T, hidden]``: ``(h, load)``, the
    ``ExpertLoad`` of its held experts."""
    b, t, width = h.shape
    kernels = dict(use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        u = znorm(h, layer["input_layernorm"], config.rms_norm_eps)
    if config.layer_kinds[index] == LINEAR:
        y = delta_mixer(layer, u, config, **kernels)
    else:
        y = gated_attention(layer, u, attention_mask, lengths, config, cos,
                            sin, **kernels)
    with jax.named_scope(scopes.LN):
        h = h + y
        m = znorm(h, layer["post_attention_layernorm"], config.rms_norm_eps)
    if slots is None:
        slots = token_slots(attention_mask, None)
    y, load, _ = routed_block(
        layer, m.reshape(b * t, width), slots,
        lambda rows: qwen3_next_route(layer, rows, config),
        shared=lambda rows: gated_shared_expert(layer, rows),
        router_width=config.router_experts,
        expert_offset=config.expert_offset, **kernels)
    with jax.named_scope(scopes.LN):
        return h + y.reshape(b, t, width), load


def qwen3_next_encode(params: Dict, input_ids: jax.Array,
                      attention_mask: jax.Array, config: Qwen3NextConfig, *,
                      capacity: Optional[int] = None,
                      use_pallas: bool = False, kernel_interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """Hidden states before the final norm ``f32[B, T, hidden]`` and the
    layers' statistics ``i32[3, layers]`` (``olmoe.launch_stats``).
    ``capacity``: the token slots the routed blocks are compiled for
    (``models/olmoe.py``); the mixers run every slot."""
    cos, sin = rope_tables(input_ids.shape[1], config.rotary_dim,
                           config.rope_theta)
    slots = token_slots(attention_mask, capacity)
    lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
    with jax.named_scope(scopes.EMBED):
        h = params["embed_tokens"][input_ids].astype(jnp.float32)
    loads = []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h, load = qwen3_next_layer(
                layer, h, attention_mask, lengths, config, i, cos, sin,
                slots=slots, use_pallas=use_pallas,
                kernel_interpret=kernel_interpret)
        loads.append(load)
    return h, launch_stats(loads)


def qwen3_next_predict(params: Dict, input_ids: jax.Array,
                       attention_mask: jax.Array, config: Qwen3NextConfig, *,
                       capacity: Optional[int] = None,
                       use_pallas: bool = False,
                       kernel_interpret: bool = False,
                       with_stats: bool = False):
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]`` from the
    last real token; with ``with_stats`` also the layers' statistics
    ``i32[3, layers]`` (``olmoe_predict``'s second output: the largest held
    group, the held pairs and the visited rows of each layer)."""
    hidden, stats = qwen3_next_encode(
        params, input_ids, attention_mask, config, capacity=capacity,
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    logits = last_token_logits(
        {"norm": 1.0 + params["norm"], "score": params["score"]}, hidden,
        attention_mask, config.rms_norm_eps)
    p = jax.nn.softmax(logits, axis=-1)[:, 1]
    return (p, stats) if with_stats else p


def _delta_chunks(config, launches, lengths):
    slots = sum(la.size * la.width for la in launches)
    return {"delta_chunks":
            slots // config.delta_chunk * config.num_delta_layers}


# routed AND recurrent: the routed encoders' row (capacity rungs, the second
# output, the experts' sites) with the delta scan's site and its counter
# beside them (models/text_encoder.py)
TEXT_ENCODER = routed_encoder(
    Qwen3NextConfig, init_qwen3_next_params, qwen3_next_predict,
    Qwen3NextConfig.core_refusal,
    sites=(KernelSite("delta_scan",
                      lambda c, width, slots: c.scan_refusal(width)),
           KernelSite("causal_conv",
                      lambda c, width, slots: c.conv_refusal(width))),
    dispatch_counters=_delta_chunks)
