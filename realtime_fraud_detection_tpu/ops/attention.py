"""The attention core of the text branch: the XLA reference, and a fused
Pallas kernel for the TPU in which the scores never leave VMEM.

``attention_reference`` is the numerics oracle and what every platform but
the TPU runs. At the deployed shape (256 rows x 12 heads x 512 tokens) it
writes the f32 scores to HBM and reads them back twice: 3.2 GB a layer, a
compute-bound kernel run as a memory-bound one.

``flash_attention`` keeps them on the chip. One program owns ONE batch row
and all of its heads: q, k, v arrive as ``[B, T, H*D]`` — the projections'
own layout, so there is no head-split transpose on either side and every
block is lane-dense — and the head split happens in VMEM. ``128 // D`` heads
share a lane tile (two at ``head_dim`` 64); a head's q·kᵀ zeroes the other
heads' lanes of q and contracts over the whole tile, and its p·v keeps its
own lanes of the result: on a 128-deep MXU that costs what a 64-deep
contraction costs, and nothing is shuffled across lanes. A program walks its
row's queries in blocks of ``block_q``; a block sees ALL ``T`` keys at once
(``T x T`` f32 scores of one head are 1 MB at 512), so the softmax is the
plain two-pass one, with no online rescaling. Every score of every launched
row is computed, padding included.

Precision, as the configuration states it and as XLA's default-precision
einsum runs the reference on a TPU: bf16 MXU operands with f32 accumulation;
scale, mask, max, exp, sum and the normalisation in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array,
    key_mask: jax.Array | None = None, causal: bool = False,
) -> jax.Array:
    """Plain XLA attention (numerics oracle + CPU fallback). [B,H,S,D].
    ``causal``: a query also sees no key after its own position.

    Grouped-query attention: ``k`` and ``v`` may hold fewer heads than
    ``q`` (``[B,Hkv,S,D]``, ``H`` a multiple of ``Hkv``); key head ``j``
    serves query heads ``j*G .. j*G+G-1``. The G query heads of a group are
    stacked along the query axis of their key head, so no key or value is
    repeated in memory and the rest is the same two contractions."""
    heads, t_q, d = q.shape[1:]
    group = heads // k.shape[1]
    if group != 1:
        q = q.reshape(q.shape[0], k.shape[1], group * t_q, d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :], scores, NEG_INF)
    if causal:
        t_k = scores.shape[-1]
        scores = jnp.where(
            np.tile(np.tril(np.ones((t_q, t_k), bool)), (group, 1)), scores,
            NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)
    return ctx.reshape(ctx.shape[0], heads, t_q, d) if group != 1 else ctx


def split_heads(x: jax.Array, num_heads: int) -> jax.Array:
    """``[B, T, H*D]`` (the projections' layout) -> ``[B, H, T, D]``."""
    b, t, width = x.shape
    return x.reshape(b, t, num_heads, width // num_heads).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    """``[B, H, T, D]`` -> ``[B, T, H*D]``."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def flash_supported(seq_len: int, head_dim: int, num_heads: int) -> bool:
    """The shapes ``flash_attention`` takes: whole lane tiles of heads and of
    keys. The ONE predicate: the traced guard in ``models/bert.py``, the
    scorer's selector and its engagement counters all ask it.

    No crossover length is written in, because the v5e showed none inside
    what the tiling allows (PERF.md, PR 24): at the shortest admissible
    length, 128, the core alone took 0.43 ms against the reference's 1.61
    and the text branch 21.5 against 29.5 (bucket 256); at 512 tokens it is
    ahead at buckets 256, 128 and 32 and level at 8 and 1. Shorter
    sequences (the parked 64-token configurations) keep the reference."""
    return (head_dim == 64 and num_heads % (LANES // head_dim) == 0
            and seq_len >= LANES and seq_len % LANES == 0)


def narrowest_supported_len(head_dim: int, num_heads: int) -> int | None:
    """The shortest sequence ``flash_attention`` takes for this head layout
    (one lane tile of keys), or None where it takes none: the width at
    which rows whose text fits it are launched apart from the long ones
    (``scoring/text_split.py``). Asked of the predicate, not written down
    anywhere else."""
    return LANES if flash_supported(LANES, head_dim, num_heads) else None


def _block_q(seq_len: int) -> int:
    """Query rows per inner step: the most that divide the sequence, up to
    512 — the whole deployed window in one step (2.54 ms a layer against
    3.01 at 256 and 4.36 at 128, bucket 256 on a v5e; PERF.md, PR 24). A
    512 x 512 f32 score block is one megabyte of VMEM."""
    return next(bq for bq in (512, 256, LANES) if seq_len % bq == 0)


def _fused_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *, head_dim: int,
                  block_q: int, scale: float):
    seq_len, width = q_ref.shape[1], q_ref.shape[2]
    bias = bias_ref[0]                                     # f32[1, T]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    own = [(lane >= h * head_dim) & (lane < (h + 1) * head_dim)
           for h in range(LANES // head_dim)]

    for tile in range(width // LANES):                     # LANES // D heads
        lanes = slice(tile * LANES, (tile + 1) * LANES)
        k2 = k_ref[0, :, lanes].astype(jnp.bfloat16)       # [T, 128]
        v2 = v_ref[0, :, lanes].astype(jnp.bfloat16)

        def q_block(i, carry, lanes=lanes, k2=k2, v2=v2):
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            # 1/sqrt(64) is a power of two: scaling q is exact in bf16
            q2 = (q_ref[0, rows, lanes].astype(jnp.float32)
                  * scale).astype(jnp.bfloat16)            # [bq, 128]
            out = jnp.zeros((block_q, LANES), jnp.float32)
            for mine in own:
                s = jax.lax.dot_general(
                    jnp.where(mine, q2, jnp.zeros_like(q2)), k2,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [bq, T]
                # a masked key's score becomes exactly NEG_INF: any real
                # score is far below the f32 spacing at 1e30
                s = s + bias
                p = jnp.exp(s - s.max(axis=1, keepdims=True))
                denom = p.sum(axis=1, keepdims=True)       # >= 1
                pv = jnp.dot(p.astype(jnp.bfloat16), v2,
                             preferred_element_type=jnp.float32)
                out = jnp.where(mine, pv / denom, out)
            o_ref[0, rows, lanes] = out.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, seq_len // block_q, q_block, 0)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    key_mask: jax.Array | None = None,
    *,
    num_heads: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused attention core. q/k/v: ``[B, T, H*D]`` (heads side by side, as
    the projections write them) -> ``[B, T, H*D]`` in q's dtype; ``key_mask``
    is bool[B, T] marking valid (non-pad) keys.

    ``interpret=True`` runs the kernel through the Pallas interpreter
    (CPU-testable); on TPU leave it False.
    """
    b, t, width = q.shape
    head_dim = width // num_heads
    if width % num_heads or not flash_supported(t, head_dim, num_heads):
        raise ValueError(
            f"flash_attention takes seq_len a multiple of {LANES} and "
            f"pairs of 64-wide heads; got seq_len "
            f"{t}, {num_heads} heads over width {width}")
    if key_mask is None:
        key_mask = jnp.ones((b, t), bool)
    # f32[B, 1, T]: a (1, T) block meets the TPU's (8, 128)-or-full rule
    bias = jnp.where(key_mask, 0.0, NEG_INF).astype(jnp.float32)[:, None, :]

    block_q = _block_q(t)
    row = pl.BlockSpec((1, t, width), lambda i: (i, 0, 0))
    kernel = functools.partial(
        _fused_kernel, head_dim=head_dim, block_q=block_q,
        scale=1.0 / float(np.sqrt(head_dim)),  # rtfd-lint: allow[d2h] head_dim is a host shape int
    )
    # q, k, v and the output double-buffered, the f32 scores and bf16
    # weights of one step, and room for Mosaic's own temporaries
    vmem = (8 * t * width * q.dtype.itemsize + 16 * block_q * t
            + (8 << 20))
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[row, row, row,
                  pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((b, t, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(q, k, v, bias)
