"""ZAYA1's convolutional mixing — everything between the latent projections
and the attention core (``models/zaya.py``) — as one Pallas kernel for the
TPU: the latents and the projected values are read once, q, k and the shifted
v are written once, head-major as the core takes them.

The XLA form (``models.zaya.cca_mix`` with the value shift beside it) is the
numerics oracle and what every platform but the TPU runs. On the v5e it makes
eight or more passes over ``f32[heads, B, T, D]`` where the bytes need one
(98 ms a batch of 256 x 128 slots x 24 layers against 11.8; PERF.md, PR 30).

One grid step owns ``rows`` batch rows x ``BLOCK_T`` positions of every head.
A head of ``head_dim`` 128 is one lane tile, so the latents arrive as the
projection writes them (``[heads, B, T, D]``), the outputs leave as
``[B, heads, T, D]``, and nothing is transposed: a head is a leading index on
both sides. Per row and head, in VMEM: the depthwise taps (float32), the two
``D x D`` taps of the grouped convolution (bfloat16 operands, float32
accumulation: a tap weighs the previous position's convolved latent, so the
shift is taken on the operand, row for row the product the XLA form shifts
afterwards), the q-k mean over the group's query heads, the L2 norm (the key
head's with its temperature), rotate-half RoPE on the leading ``rotary_dim``
lanes as two lane rolls against tables the wrapper lays out, and the second
half of the value heads read from the previous position. The causal shift is
a sublane roll inside the block; a block that does not start its row takes
the two positions before it from a ``HALO``-row block of the same input
(the depthwise tap needs one, the grouped tap that one's own predecessor),
and position 0 of a row sees zeros. The taps (0.33 M parameters) keep one
block index over the whole grid, so they are fetched once.

Precision is the XLA form's: float32 in and out (the bytes
``benchmarks/kernels/cca_mix.py`` charges), bfloat16 MXU operands in the
grouped taps alone. q, k and v agree with it to float32 rounding (the order
of the sums in the norm and the mean).

``cca_mix_refusal`` is the ONE predicate on shapes: the traced guard in
``models/zaya.py``, the scorer's engagement counters and the tests ask it,
and it answers by name.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_T = 128       # positions a grid step
HALO = 8            # one float32 sublane tile: the positions before a block
# batch rows a grid step, 3 MB of double-buffered blocks each. Alone on a v5e
# at bucket 256 x 128 positions a layer takes 0.651 / 0.621 / 0.620 / 0.627 /
# 0.640 ms at 1 / 2 / 4 / 8 / 16 rows, a kernel that only copies the same
# blocks 0.607-0.610 at any of them (PERF.md, PR 32)
MAX_ROWS = 4
TAPS = 2            # both convolutions' kernel size, as the body is written


def cca_mix_refusal(seq_len: int, head_dim: int, kv_heads: int,
                    taps: Tuple[int, int] = (TAPS, TAPS)) -> Optional[str]:
    """Why ``cca_mix_fused`` does not take this shape, or None where it
    does: a head is one lane tile, a row whole blocks of positions, both
    convolutions two taps, and the value shift halves the key-value heads."""
    if head_dim != LANES:
        return f"head_dim {head_dim} is not one lane tile ({LANES})"
    if seq_len < BLOCK_T or seq_len % BLOCK_T:
        return (f"seq_len {seq_len} is not a multiple of the block "
                f"({BLOCK_T} positions)")
    if tuple(taps) != (TAPS, TAPS):
        return (f"cca_time0, cca_time1 {tuple(taps)}: the kernel holds "
                f"{TAPS} taps a convolution")
    if kv_heads % 2:
        return f"{kv_heads} key-value heads do not halve for the value shift"
    return None


def _rows_per_step(batch: int) -> int:
    """The most rows, up to ``MAX_ROWS``, that divide the batch (every
    bucket is 1 or a multiple of 8)."""
    return next(r for r in (MAX_ROWS, 2, 1) if batch % r == 0)


def _mix_kernel(*refs, heads: int, kv: int, half: int, eps: float,
                halo: bool):
    if halo:
        (lat_ref, v_ref, lat_halo_ref, v_halo_ref, dw_ref, gw_ref, temp_ref,
         rope_a_ref, rope_s_ref, q_ref, k_ref, vo_ref) = refs
        # the first block of a row has no past: its halo block is the row's
        # own first positions (the index map clamps), weighed zero
        keep = jnp.where(pl.program_id(1) == 0, 0.0, 1.0)
    else:
        (lat_ref, v_ref, dw_ref, gw_ref, temp_ref, rope_a_ref, rope_s_ref,
         q_ref, k_ref, vo_ref) = refs
    rows, block_t, d = lat_ref.shape[1:]
    group = heads // kv
    position = jax.lax.broadcasted_iota(jnp.int32, (block_t, d), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_t, d), 1)
    rope_a, rope_s = rope_a_ref[...], rope_s_ref[...]
    nothing = jnp.zeros((1, d), jnp.float32)

    def shift(x, before):
        """``y[t] = x[t - 1]``; ``y[0]`` is ``before`` ``[1, D]``."""
        return jnp.where(position == 0, before, pltpu.roll(x, 1, 0))

    def l2(x):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def rope(x):
        turned = jnp.where(lane < half, pltpu.roll(x, d - half, 1),
                           pltpu.roll(x, half, 1))
        return x * rope_a + turned * rope_s

    def one_row(r, carry):
        def convolved(head, c):
            tap0, tap1 = dw_ref[0, head], dw_ref[1, head]       # [1, D]
            c_before = c1_before = nothing
            if halo:    # the last position of the block before, and its c1
                c_before = lat_halo_ref[head, r, HALO - 1:] * keep
                c1_before = (lat_halo_ref[head, r, HALO - 2:HALO - 1] * tap0
                             * keep + c_before * tap1)
            c1 = shift(c, c_before) * tap0 + c * tap1
            return (jnp.dot(shift(c1, c1_before).astype(gw_ref.dtype),
                            gw_ref[head, :d], preferred_element_type=jnp.float32)
                    + jnp.dot(c1.astype(gw_ref.dtype), gw_ref[head, d:],
                              preferred_element_type=jnp.float32))

        for g in range(kv):
            k_pre = lat_ref[heads + g, r]
            q_sum = None
            for head in range(g * group, (g + 1) * group):
                q_pre = lat_ref[head, r]
                q = convolved(head, q_pre) + 0.5 * (q_pre + k_pre)
                q_ref[r, head] = rope(l2(q))
                q_sum = q_pre if q_sum is None else q_sum + q_pre
            k = convolved(heads + g, k_pre) + 0.5 * (q_sum / group + k_pre)
            k_ref[r, g] = rope(l2(k) * temp_ref[g])
            mine = slice(g * d, (g + 1) * d)
            v = v_ref[r, :, mine]
            if g >= kv // 2:        # read from the previous token
                v_before = (v_halo_ref[r, HALO - 1:, mine] * keep if halo
                            else nothing)
                v = shift(v, v_before)
            vo_ref[r, g] = v
        return carry

    jax.lax.fori_loop(0, rows, one_row, 0)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "eps", "interpret"))
def cca_mix_fused(latents: jax.Array, values: jax.Array,
                  conv_depthwise: jax.Array, conv_grouped: jax.Array,
                  temperature: jax.Array, cos, sin, *, num_heads: int,
                  num_kv_heads: int, eps: float, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(q f32[B, heads, T, D], k f32[B, kv_heads, T, D], v f32[B, kv_heads,
    T, D])`` from the latents ``f32[heads + kv_heads, B, T, D]`` (query
    heads, then key heads) and the projected values ``f32[B, T, kv_heads *
    D]`` (the heads read from this token, then those read from the previous
    one); ``conv_depthwise`` ``f32[2, (heads + kv_heads) * D]``,
    ``conv_grouped`` ``[heads + kv_heads, 2 * D, D]``, ``temperature``
    ``f32[kv_heads]``, ``cos`` / ``sin`` ``f32[T, rotary_dim]``
    (``models.olmoe.rope_tables``).

    ``interpret=True`` runs the kernel through the Pallas interpreter
    (CPU-testable); on TPU leave it False."""
    n_lat, b, t, d = latents.shape
    heads, kv = num_heads, num_kv_heads
    refusal = cca_mix_refusal(t, d, kv, (conv_depthwise.shape[0],
                                         conv_grouped.shape[1] // d))
    if refusal is None and (n_lat != heads + kv or heads % kv):
        refusal = (f"{n_lat} latent heads are not {heads} query heads over "
                   f"{kv} key heads")
    if refusal:
        raise ValueError(f"cca_mix_fused: {refusal}")
    rot = cos.shape[-1]
    half = rot // 2
    # x * rope_a + (x turned by half a rotation) * rope_s, whole lane tiles:
    # the lanes past the rotary dims pass through (1 and 0)
    rope_a = jnp.concatenate(
        [cos, jnp.ones((t, d - rot), jnp.float32)], axis=-1)
    rope_s = jnp.concatenate(
        [-sin[:, :half], sin[:, half:],
         jnp.zeros((t, d - rot), jnp.float32)], axis=-1)

    rows = _rows_per_step(b)
    halo = t > BLOCK_T

    def block(i, j):
        return i, j

    def before(i, j):       # the HALO positions ahead of block j, in HALO's
        return i, jnp.maximum(j * (BLOCK_T // HALO) - 1, 0)

    def lat_spec(length, where):
        return pl.BlockSpec((n_lat, rows, length, d),
                            lambda i, j: (0, *where(i, j), 0))

    def v_spec(length, where):
        return pl.BlockSpec((rows, length, kv * d),
                            lambda i, j: (*where(i, j), 0))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, j: (0,) * len(shape))

    def out_spec(n):
        return pl.BlockSpec((rows, n, BLOCK_T, d), lambda i, j: (i, 0, j, 0))

    table = pl.BlockSpec((BLOCK_T, d), lambda i, j: (j, 0))
    past = [lat_spec(HALO, before), v_spec(HALO, before)] if halo else []
    # in and out blocks double-buffered, the taps, and room for the body's
    # own temporaries (a few [BLOCK_T, D] tiles a head)
    vmem = 16 * rows * (n_lat + kv) * BLOCK_T * d + (12 << 20)
    return pl.pallas_call(
        functools.partial(_mix_kernel, heads=heads, kv=kv, half=half,
                          eps=eps, halo=halo),
        name="cca_mix",
        grid=(b // rows, t // BLOCK_T),
        in_specs=[lat_spec(BLOCK_T, block), v_spec(BLOCK_T, block), *past,
                  whole(TAPS, n_lat, 1, d), whole(n_lat, TAPS * d, d),
                  whole(kv, 1, d), table, table],
        out_specs=(out_spec(heads), out_spec(kv), out_spec(kv)),
        out_shape=tuple(jax.ShapeDtypeStruct((b, n, t, d), jnp.float32)
                        for n in (heads, kv, kv)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(latents, values, *([latents, values] if halo else []),
      conv_depthwise.reshape(TAPS, n_lat, 1, d), conv_grouped,
      jnp.broadcast_to(temperature.astype(jnp.float32)[:, None, None],
                       (kv, 1, d)),
      rope_a, rope_s)
