"""Falcon-H1's parallel hybrid block as a text encoder, in pure JAX.

The sizes are ``tiiuae/Falcon-H1-34B-Instruct``'s ``config.json``
(``FalconH1Config`` holds every key of it under its own name); the layer
equations are written down from that file and Hugging Face's
``modeling_falcon_h1.py`` conventions, each assumption listed in the
benchmark's configuration file. Every layer is the same block, and in it TWO
mixers read one normed input and are summed — a Mamba-2 state-space mixer
and grouped-query attention — with a µP multiplier on every path:

On a float32 residual ``h`` ``[T, hidden]`` (text right-padded; the encoder
is causal, so nothing masks the state-space mixer: no real position reads a
padded one, and the answer is read at the last REAL token):

0. ``h = Emb[ids] * embedding_multiplier``.
1. ``u = RMSNorm(h)``; ``h += ssm_out_multiplier * Mixer(ssm_in_multiplier *
   u) + attention_out_multiplier * Attn(attention_in_multiplier * u)``.
2. ``Mixer(u)``: ``p = (u W_in) * m``, ``m`` the µP vector over ``W_in``'s
   outputs made of ``ssm_multipliers`` [z, x, B, C, dt] by segment
   (``mamba_d_ssm`` | ``mamba_d_ssm`` | ``G N`` | ``G N`` | heads, ``G`` =
   ``mamba_n_groups``, ``N`` = ``mamba_d_state``). ``p`` splits into ``z``,
   ``xBC``, ``dt``. ``xBC <- SiLU(conv(xBC))``: a depthwise causal
   convolution over positions, ``mamba_d_conv`` taps with bias, position t
   sees t-3..t, zeros before the row. ``xBC`` splits into ``x`` (heads of
   ``mamba_d_head``), ``B``, ``C`` (``G`` groups of ``N``; head j reads
   group ``j // (heads / G)``). ``dt <- softplus(dt + dt_bias)``, ``a =
   -exp(A_log)``. The recurrence (``ops/ssd_scan.py``): ``S_t = exp(dt_t a)
   S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``. Then
   ``mamba_rms_norm`` with ``mamba_norm_before_gate`` false: ``y <-
   GroupRMSNorm_G(y * SiLU(z)) * w`` (``G`` groups, eps ``rms_norm_eps``);
   the output is ``y W_out``.
3. ``Attn(u)``: ``q = u W_q``, ``k = (u W_k) * key_multiplier``, ``v = u
   W_v``; rotate-half RoPE at ``rope_theta`` on q and k; causal ``softmax(q
   k^T / sqrt(head_dim))`` with ``num_attention_heads /
   num_key_value_heads`` query heads a key-value head; ``W_o``. No biases,
   no window, no QK-norm.
4. ``n = RMSNorm(h)``; ``h += (silu(gate_multiplier * n W_gate) * n W_up)
   W_down * down_multiplier``, ``mlp_multipliers`` = [gate, down].

The head is ``models/olmoe.py``'s (final RMSNorm, last real token, bias-free
``Linear(hidden -> 2)``, ``softmax[:, 1]``): the language-model head and its
``lm_head_multiplier`` are not held (no token is emitted).

Precision: weights stored bfloat16; bfloat16 matmul operands with float32
accumulation in the seven projections, the attention core, both
contractions of the scan and the MLP (``x``, ``B`` and ``C`` are rounded to
bfloat16 once, after the convolution's SiLU; q and k after RoPE); float32
norms, softmax, RoPE, convolution, softplus, decays, state, gate and
residual. Every multiplier is applied in float32 to a matmul's float32
result or to a norm's float32 output.
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
    last_token_logits,
    rms_norm,
    rope_tables,
)
from realtime_fraud_detection_tpu.models.text_encoder import (
    KernelSite,
    TextEncoder,
    causal_counters,
)
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    merge_heads,
    rope_lane_tables,
    split_heads,
    windowed_attention,
    windowed_refusal,
)
from realtime_fraud_detection_tpu.ops.causal_conv import (
    LANES,
    causal_conv_silu,
    conv_refusal,
)
from realtime_fraud_detection_tpu.ops.ssd_scan import ssd_refusal, ssd_scan


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """``config.json`` of Falcon-H1-34B-Instruct, every key under its own
    name at its published value. What the equations above do not hold is
    refused by value (``__post_init__``), never ignored; ``lm_head_multiplier``
    and ``num_logits_to_keep`` are the language-model head's, which this
    path does not run."""

    attention_bias: bool = False
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    attn_layer_indices: Optional[Tuple[int, ...]] = None
    embedding_multiplier: float = 5.656854249492381
    head_dim: int = 128
    hidden_act: str = "silu"
    hidden_size: int = 5120
    intermediate_size: int = 21504
    key_multiplier: float = 0.011048543456039804
    lm_head_multiplier: float = 0.0078125
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_expand: int = 2
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_norm_before_gate: bool = False
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    max_position_embeddings: int = 262144
    mlp_bias: bool = False
    mlp_expansion_factor: int = 8
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    model_type: str = "falcon_h1"
    num_attention_heads: int = 20
    num_hidden_layers: int = 72
    num_key_value_heads: int = 4
    num_logits_to_keep: int = 1
    projectors_bias: bool = False
    rms_norm_eps: float = 1e-5
    rope_scaling: None = None
    rope_theta: float = 100000000000.0
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    tie_word_embeddings: bool = False
    vocab_size: int = 261120
    # not config.json keys: how the seeded weights are drawn
    # (``init_falcon_h1_params`` says why) and the classifier's width
    o_proj_gain: float = 2.0
    num_labels: int = 2

    def __post_init__(self) -> None:
        held = {"attention_bias": False, "mamba_proj_bias": False,
                "mlp_bias": False, "projectors_bias": False,
                "mamba_conv_bias": True, "mamba_rms_norm": True,
                "mamba_norm_before_gate": False, "mamba_use_mlp": True,
                "hidden_act": "silu", "rope_scaling": None,
                "attn_layer_indices": None, "model_type": "falcon_h1"}
        for key, value in held.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"FalconH1Config: {key} {getattr(self, key)!r} is not "
                    f"what the equations hold ({value!r})")
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"FalconH1Config: mamba_d_ssm {self.mamba_d_ssm} is not "
                f"{self.mamba_n_heads} heads of {self.mamba_d_head}")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("FalconH1Config: the heads must divide into "
                             "their groups, the mixer's and attention's")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("FalconH1Config: ssm_multipliers is [z, x, B, "
                             "C, dt] and mlp_multipliers [gate, down]")

    @property
    def conv_dim(self) -> int:
        """The convolved channels: ``x`` beside ``B`` and ``C``."""
        return (self.mamba_d_ssm
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    def mup_segments(self) -> Tuple[Tuple[int, float], ...]:
        """``(width, multiplier)`` of ``W_in``'s outputs in their order: z,
        x, B, C, dt."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return tuple(zip((self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                          self.mamba_n_heads), self.ssm_multipliers))

    def core_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA attention
        core where the fused one is asked for, or None where it holds the
        kernel (``ops.attention.windowed_refusal``: shapes alone)."""
        return windowed_refusal(seq_len, self.head_dim,
                                self.num_attention_heads,
                                self.num_key_value_heads, None)

    def scan_refusal(self, seq_len: int) -> Optional[str]:
        """The same of the mixer's scan (``ops.ssd_scan.ssd_refusal``)."""
        return ssd_refusal(seq_len, self.mamba_d_head, self.mamba_d_state,
                           self.mamba_chunk_size, self.mamba_n_heads,
                           self.mamba_n_groups)

    def conv_refusal(self, seq_len: int) -> Optional[str]:
        """The same of the mixer's convolution
        (``ops.causal_conv.conv_refusal``) over ``x | B | C``."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return conv_refusal(seq_len, (self.mamba_d_ssm, gn, gn),
                            self.mamba_d_conv, offset=self.mamba_d_ssm)


# two groups, more heads than groups, five query heads a key-value head; a
# chunk of 16 so that a short row still crosses chunk boundaries
TINY_FALCON_H1 = FalconH1Config(
    vocab_size=30522, hidden_size=128, intermediate_size=256,
    num_hidden_layers=2, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, mamba_n_heads=4, mamba_d_head=16, mamba_d_ssm=64,
    mamba_d_state=32, mamba_n_groups=2, mamba_chunk_size=16)


def mup_vector(config: FalconH1Config) -> np.ndarray:
    """``f32[in_proj_dim]``: each output of ``W_in`` times its segment's
    multiplier (a constant of the program)."""
    return np.concatenate([np.full((width,), mult, np.float32)
                           for width, mult in config.mup_segments()])


def init_falcon_h1_params(key: jax.Array, config: FalconH1Config) -> Dict:
    """Seeded weights drawn directly in bfloat16, one tensor at a time (no
    float32 copy of a layer ever exists), norm weights ones (float32), the
    head float32 at normal(0.02) as the other encoders'.

    **The scale of each matrix is ``1 / (its multiplier x sqrt(fan-in))``**:
    the published multipliers are the program's, and a µP checkpoint's raw
    weights are what those multipliers bring back to unit size — so every
    multiplied activation here has RMS ~1, as a trained network's, and none
    of the three paths shrinks out of a comparison's sight. (At
    normal(0.02) throughout, ``key_multiplier`` 0.011 makes every score
    ~0.02 — a uniform softmax whatever the core does — the state's share of
    the mixer's output is 1e-4 of the skip ``D x``, and attention adds a
    hundredth of what the MLP adds.) By tensor: the embedding ``1 /
    embedding_multiplier`` (h_0 of RMS 1); ``W_in`` BY SEGMENT ``1 /
    (ssm_in_multiplier x m_segment x sqrt(hidden))`` (z, x, B, C and dt
    each of RMS 1 ahead of the convolution: a program that swaps two
    segments of the µP vector is 1.4-2.8 x off on both); ``W_q``, ``W_v``,
    ``W_up`` ``1 / sqrt(hidden)`` (``attention_in_multiplier`` is 1);
    ``W_k`` ``1 / (key_multiplier x sqrt(hidden))`` (scores of RMS ~1: a
    softmax that is neither uniform nor one-hot); ``W_gate`` ``1 /
    (gate_multiplier x sqrt(hidden))``; the three output projections ``1 /
    (their multiplier x sqrt(their fan-in))`` — ``W_out``
    (``ssm_out_multiplier``, the normed mixer output has RMS 1), ``W_down``
    (``down_multiplier``; ``silu(g) * u`` has RMS ~0.6) and ``W_o``
    (``attention_out_multiplier``) times ``o_proj_gain``: a context is an
    average of values, of RMS well under 1 in the first layer, and the gain
    is what brings attention's share of a layer's update beside the other
    two's there — no further, because the average grows with depth (what
    attention adds is common to a row's positions, so later layers average
    values that agree): at 2 attention is a sixth of layer 0's update and
    two fifths of layer 5's, where the MLP still has over a fifth; at 5 it
    was a third of layer 0's and three quarters of layer 5's and crowded
    the MLP under a tenth from layer 3 on (measured in the float32
    reference at the published widths on the cell's own text: the
    benchmark's configuration file,
    ``assumed.weights``).

    The mixer's own parameters as Mamba-2's reference initialisation:
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
    log-uniform 1e-3..1e-1, ``D`` ones, the gated norm's weight ones; the
    convolution's taps normal(``1 / sqrt(mamba_d_conv)``) (a sum of four
    taps keeps its input's size: ZAYA1's reason) and its bias uniform in
    +-``1 / sqrt(mamba_d_conv)`` (PyTorch's ``Conv1d`` default, what the
    reference implementation leaves it at), both float32."""
    h, f = config.hidden_size, config.intermediate_size
    d = config.head_dim
    q_w, kv_w = config.num_attention_heads * d, config.num_key_value_heads * d
    heads, taps = config.mamba_n_heads, config.mamba_d_conv
    gate_mult, down_mult = config.mlp_multipliers
    unit = 1.0 / math.sqrt(h)

    def w(k, shape, std, dtype=jnp.bfloat16):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def ones(n=h):
        return jnp.ones((n,), jnp.float32)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(k_layers, config.num_hidden_layers):
        k = jax.random.split(lk, 13)
        in_keys = jax.random.split(k[0], 5)
        in_proj = jnp.concatenate([
            w(ik, (h, width), unit / (config.ssm_in_multiplier * mult))
            for ik, (width, mult) in zip(in_keys, config.mup_segments())],
            axis=1)
        dt = jnp.exp(jax.random.uniform(
            k[3], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        layers.append({
            "input_layernorm": ones(),
            "in_proj": in_proj,
            "conv_weight": w(k[1], (taps, config.conv_dim),
                             1.0 / math.sqrt(taps), jnp.float32),
            "conv_bias": jax.random.uniform(
                k[2], (config.conv_dim,), jnp.float32,
                -1.0 / math.sqrt(taps), 1.0 / math.sqrt(taps)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k[4], (heads,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "mixer_norm": ones(config.mamba_d_ssm),
            "out_proj": w(k[5], (config.mamba_d_ssm, h),
                          1.0 / (config.ssm_out_multiplier
                                 * math.sqrt(config.mamba_d_ssm))),
            "q_proj": w(k[6], (h, q_w),
                        unit / config.attention_in_multiplier),
            "k_proj": w(k[7], (h, kv_w), unit / (
                config.attention_in_multiplier * config.key_multiplier)),
            "v_proj": w(k[8], (h, kv_w),
                        unit / config.attention_in_multiplier),
            "o_proj": w(k[9], (q_w, h), config.o_proj_gain / (
                config.attention_out_multiplier * math.sqrt(q_w))),
            "pre_ff_layernorm": ones(),
            "mlp_gate": w(k[10], (h, f), unit / gate_mult),
            "mlp_up": w(k[11], (h, f), unit),
            "mlp_down": w(k[12], (f, h), 1.0 / (down_mult * math.sqrt(f))),
        })
    # the embedding a block of rows at a time: drawn whole, its float32
    # normals (5.3 GB at the published sizes) stand beside every weight
    # already made, and the start-up's peak is the chip's whole memory
    blocks = max(n for n in range(1, 17) if config.vocab_size % n == 0)
    embed = jax.lax.map(
        lambda bk: w(bk, (config.vocab_size // blocks, h),
                     1.0 / config.embedding_multiplier),
        jax.random.split(k_emb, blocks))
    return {
        "embed_tokens": embed.reshape(config.vocab_size, h),
        "layers": layers,
        "norm": ones(),
        "score": w(k_head, (h, config.num_labels), 0.02, jnp.float32),
    }


def causal_conv(x: jax.Array, taps: jax.Array,
                bias: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution over positions: ``x`` ``f32[B, T, C]``,
    ``taps`` ``f32[K, C]`` (tap ``K - 1`` weighs position t itself, tap 0
    position ``t - K + 1``), zeros before the row; ``bias`` ``f32[C]`` or
    None (a mixer whose convolution has none: ``models/qwen3_next.py``).
    The channels are the caller's: every mixer with such a convolution
    calls this."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * taps[i] for i in range(k))
    return conv if bias is None else bias + conv


def conv_silu_parts(x: jax.Array, taps: jax.Array,
                    bias: Optional[jax.Array], parts: Tuple[int, ...],
                    dtypes: Tuple, *, offset: int = 0, kernel: bool = False,
                    interpret: bool = False) -> Tuple[jax.Array, ...]:
    """``silu(causal_conv(x[..., offset:offset + sum(parts)], taps,
    bias))`` cut along the channels into ``parts``, each rounded once to
    its reader's dtype. ``kernel`` asks for the Pallas form
    (``ops.causal_conv.causal_conv_silu``, which reads the channels out of
    ``x`` where it lies and writes the parts itself): the caller answers
    for the shape (``conv_refusal``)."""
    if kernel:
        # an array whose last side is no whole number of lane tiles lies
        # positions-minor on the TPU: the kernel reads that, a bitcast away
        last = x.shape[-1] % LANES != 0
        out = causal_conv_silu(
            jnp.swapaxes(x, 1, 2) if last else x, taps, bias, parts=parts,
            dtypes=dtypes, offset=offset, positions_last=last,
            interpret=interpret)
        return tuple(jnp.swapaxes(part, 1, 2) for part in out) if last \
            else out
    y = jax.nn.silu(causal_conv(x[..., offset:offset + sum(parts)], taps,
                                bias))
    edges = np.cumsum((0,) + tuple(parts))
    return tuple(y[..., lo:hi].astype(dtype)
                 for lo, hi, dtype in zip(edges, edges[1:], dtypes))


def group_rms_norm(x: jax.Array, weight: jax.Array, groups: int, eps: float
                   ) -> jax.Array:
    """RMSNorm over each of ``groups`` equal parts of the last axis."""
    parts = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * weight


def mamba2_mix(layer: Dict, p: jax.Array, *, heads: int, head_dim: int,
               groups: int, state: int, chunk: int, eps: float,
               scan_kernel: bool = False, conv_kernel: bool = False,
               kernel_interpret: bool = False) -> jax.Array:
    """A Mamba-2 mixer from ``W_in``'s result on: ``p`` ``f32[B, T, d_inner
    + (d_inner + 2 groups state) + heads]`` (``z`` | ``xBC`` | ``dt``,
    whatever multiplied it already applied) through the convolution, the
    scan, the gate and the grouped norm to ``y W_out`` ``f32[B, T,
    hidden]``; ``d_inner`` = ``heads x head_dim``. The layer's own
    parameters under the names both encoders with such a mixer store them
    by (this one and ``models/nemotron_h.py``). ``scan_kernel`` and
    ``conv_kernel`` ask for the scan's and the convolution's Pallas forms:
    the caller answers for the shape (``ops.ssd_scan.ssd_refusal``,
    ``ops.causal_conv.conv_refusal``)."""
    b, t, _ = p.shape
    operand = layer["in_proj"].dtype
    d_ssm = heads * head_dim
    conv_dim = d_ssm + 2 * groups * state
    with jax.named_scope(scopes.SSM_PROJ), \
            jax.named_scope(scopes.SSM_IN_PROJ):
        z = p[..., :d_ssm]
        dt = p[..., d_ssm + conv_dim:]
    with jax.named_scope(scopes.SSM_CONV):
        x, b_in, c_in = conv_silu_parts(
            p, layer["conv_weight"], layer["conv_bias"],
            (d_ssm, groups * state, groups * state), (operand,) * 3,
            offset=d_ssm, kernel=conv_kernel, interpret=kernel_interpret)
        dt = jax.nn.softplus(dt + layer["dt_bias"])
        x = x.reshape(b, t, heads, head_dim)
        b_in = b_in.reshape(b, t, groups, state)
        c_in = c_in.reshape(b, t, groups, state)
    with jax.named_scope(scopes.SSM_SCAN):
        y, _ = ssd_scan(
            x, dt, -jnp.exp(layer["A_log"]), b_in, c_in, layer["D"],
            chunk=chunk, use_pallas=scan_kernel, interpret=kernel_interpret)
    with jax.named_scope(scopes.SSM_PROJ):
        with jax.named_scope(scopes.SSM_GATE_NORM):
            y = group_rms_norm(y.reshape(b, t, d_ssm) * jax.nn.silu(z),
                               layer["mixer_norm"], groups, eps)
        with jax.named_scope(scopes.SSM_OUT_PROJ):
            return _proj(y, layer["out_proj"])


def falcon_mixer(layer: Dict, u: jax.Array, config: FalconH1Config, *,
                 use_pallas: bool = False, kernel_interpret: bool = False
                 ) -> jax.Array:
    """The Mamba-2 mixer on the normed ``u`` ``f32[B, T, hidden]``:
    ``f32[B, T, hidden]`` ahead of ``ssm_out_multiplier``. ``use_pallas``
    asks for the scan's and the convolution's kernels; a shape one does not
    take (``FalconH1Config.scan_refusal`` / ``conv_refusal``) runs its XLA
    form."""
    with jax.named_scope(scopes.SSM_PROJ), \
            jax.named_scope(scopes.SSM_IN_PROJ):
        p = _proj(u * config.ssm_in_multiplier, layer["in_proj"]) \
            * mup_vector(config)
    t = u.shape[1]
    return mamba2_mix(
        layer, p, heads=config.mamba_n_heads, head_dim=config.mamba_d_head,
        groups=config.mamba_n_groups, state=config.mamba_d_state,
        chunk=config.mamba_chunk_size, eps=config.rms_norm_eps,
        scan_kernel=use_pallas and config.scan_refusal(t) is None,
        conv_kernel=use_pallas and config.conv_refusal(t) is None,
        kernel_interpret=kernel_interpret)


def falcon_attention(layer: Dict, u: jax.Array, attention_mask: jax.Array,
                     lengths: jax.Array, config: FalconH1Config, cos, sin,
                     *, use_pallas: bool = False,
                     kernel_interpret: bool = False) -> jax.Array:
    """Grouped-query causal attention on the normed ``u``: ``f32[B, T,
    hidden]`` ahead of ``attention_out_multiplier``. ``use_pallas`` asks
    for the fused core (``ops.attention.windowed_attention``, Laguna's
    form: q and k rotated in VMEM, no window, no gate)."""
    b, t, _ = u.shape
    heads, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
    operand = layer["q_proj"].dtype
    with jax.named_scope(scopes.ATTN_PROJ):
        a = u * config.attention_in_multiplier
        q = _proj(a, layer["q_proj"])                          # [B, T, H*D]
        k = _proj(a, layer["k_proj"]) * config.key_multiplier
        v = _proj(a, layer["v_proj"]).astype(operand)
    if use_pallas and config.core_refusal(t) is None:
        *tables, shift = rope_lane_tables(cos, sin, d)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = windowed_attention(
                q, k, v, lengths, num_heads=heads, num_kv_heads=kv,
                rope=tuple(tables), rope_shift=shift, out_dtype=operand,
                interpret=kernel_interpret)                    # [B, T, H*D]
    else:
        with jax.named_scope(scopes.ATTN_PROJ):
            # RoPE in float32 against [B, T, heads, D]; q and k then take
            # the operands' dtype, as the kernel rounds them
            cos_, sin_ = cos[:, None, :], sin[:, None, :]
            q = apply_rope(q.reshape(b, t, heads, d), cos_, sin_
                           ).astype(operand)
            k = apply_rope(k.reshape(b, t, kv, d), cos_, sin_
                           ).astype(operand)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = merge_heads(attention_reference(
                q.transpose(0, 2, 1, 3).astype(jnp.float32),
                k.transpose(0, 2, 1, 3).astype(jnp.float32),
                split_heads(v, kv).astype(jnp.float32), attention_mask,
                causal=True))
    with jax.named_scope(scopes.ATTN_PROJ):
        return _proj(ctx, layer["o_proj"])


def falcon_layer(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                 lengths: jax.Array, config: FalconH1Config, cos, sin, *,
                 use_pallas: bool = False, kernel_interpret: bool = False
                 ) -> jax.Array:
    """One parallel hybrid block on ``h`` ``f32[B, T, hidden]``."""
    with jax.named_scope(scopes.LN):
        u = rms_norm(h, layer["input_layernorm"], config.rms_norm_eps)
    mixed = falcon_mixer(layer, u, config, use_pallas=use_pallas,
                         kernel_interpret=kernel_interpret)
    attended = falcon_attention(layer, u, attention_mask, lengths, config,
                                cos, sin, use_pallas=use_pallas,
                                kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        h = (h + config.ssm_out_multiplier * mixed
             + config.attention_out_multiplier * attended)
        n = rms_norm(h, layer["pre_ff_layernorm"], config.rms_norm_eps)
    gate_mult, down_mult = config.mlp_multipliers
    with jax.named_scope(scopes.FFN):
        # one part a matmul (obs/scopes.SCOPE_PARTS); the SiLU, the product
        # and the rounding to what down reads are written where the TPU's
        # compiler fuses them, into up's matmul (gate's fusion writes its
        # float32 result, up's reads it: PERF.md §5)
        with jax.named_scope(scopes.FFN_GATE):
            gate = gate_mult * _proj(n, layer["mlp_gate"])
        with jax.named_scope(scopes.FFN_UP):
            act = (jax.nn.silu(gate) * _proj(n, layer["mlp_up"])).astype(
                layer["mlp_down"].dtype)
        with jax.named_scope(scopes.FFN_DOWN):
            y = _proj(act, layer["mlp_down"])
    with jax.named_scope(scopes.LN):
        return h + down_mult * y


def falcon_h1_encode(params: Dict, input_ids: jax.Array,
                     attention_mask: jax.Array, config: FalconH1Config, *,
                     use_pallas: bool = False, kernel_interpret: bool = False
                     ) -> jax.Array:
    """Hidden states before the final norm, ``f32[B, T, hidden]``."""
    t = input_ids.shape[1]
    cos, sin = rope_tables(t, config.head_dim, config.rope_theta)
    lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
    with jax.named_scope(scopes.EMBED):
        h = (params["embed_tokens"][input_ids].astype(jnp.float32)
             * config.embedding_multiplier)
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h = falcon_layer(layer, h, attention_mask, lengths, config, cos,
                             sin, use_pallas=use_pallas,
                             kernel_interpret=kernel_interpret)
    return h


def falcon_h1_logits(params: Dict, input_ids: jax.Array,
                     attention_mask: jax.Array, config: FalconH1Config, *,
                     use_pallas: bool = False, kernel_interpret: bool = False
                     ) -> jax.Array:
    """Sequence-classification logits ``f32[B, num_labels]`` from the last
    real token."""
    hidden = falcon_h1_encode(params, input_ids, attention_mask, config,
                              use_pallas=use_pallas,
                              kernel_interpret=kernel_interpret)
    return last_token_logits(params, hidden, attention_mask,
                             config.rms_norm_eps)


def falcon_h1_predict(params: Dict, input_ids: jax.Array,
                      attention_mask: jax.Array, config: FalconH1Config, *,
                      use_pallas: bool = False,
                      kernel_interpret: bool = False) -> jax.Array:
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]``."""
    logits = falcon_h1_logits(params, input_ids, attention_mask, config,
                              use_pallas=use_pallas,
                              kernel_interpret=kernel_interpret)
    return jax.nn.softmax(logits, axis=-1)[:, 1]


def _text_predict(params, input_ids, attention_mask, config, *, use_pallas,
                  kernel_interpret, capacity, dequant_kernel):
    return falcon_h1_predict(params, input_ids, attention_mask, config,
                             use_pallas=use_pallas,
                             kernel_interpret=kernel_interpret), None


def _dispatch_counters(config, launches, lengths):
    slots = sum(la.size * la.width for la in launches)
    return dict(causal_counters(config, launches, lengths),
                ssm_chunks=slots // config.mamba_chunk_size
                * config.num_hidden_layers)


# causal and dense: one launch at text_len on one device, every slot
# computed, and two more kernel sites, the mixer's scan and its convolution
# (models/text_encoder.py)
TEXT_ENCODER = TextEncoder(
    config_class=FalconH1Config, init=init_falcon_h1_params,
    predict=_text_predict,
    depth=lambda config: config.num_hidden_layers,
    sites=(KernelSite("attention",
                      lambda c, width, slots: c.core_refusal(width)),
           KernelSite("ssm_scan",
                      lambda c, width, slots: c.scan_refusal(width)),
           KernelSite("causal_conv",
                      lambda c, width, slots: c.conv_refusal(width))),
    one_device="the fused causal core and the scan; a causal",
    dispatch_counters=_dispatch_counters)
