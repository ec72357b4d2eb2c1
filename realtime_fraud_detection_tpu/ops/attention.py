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

``windowed_attention`` is the causal core of the encoders whose heads are
128 wide and share keys in groups (``models/laguna.py``): one program owns
one query block of ONE key-value head's whole group of query heads, stacked
along the query axis as ``attention_reference`` stacks them, so a key block
is read once a group; it walks the key blocks a query block can see — from
``q_start - window + 1`` (0 without a window) to its own diagonal — with
the online softmax, and a query block past its row's last real token is
skipped (the row lengths are scalar-prefetched). Scores never leave VMEM.
Where the encoder norms q and k over ALL heads before the split (OLMoE's
QK-norm, ``models/olmoe.py``) no program that holds one head can form the
statistic: handed the norm's weights, the same entry runs its one-block form
(``_whole_row_kernel``), in which a program owns every head of a row of one
block of positions, forms both statistics, scales, rotates and rounds in
VMEM, and has no key blocks to walk. Where a head's score has a second term
against ONE key every head shares (latent attention, ``models/joyai.py``),
handed that term the same kernel runs its latent form: the heads whose
shared parts fill a lane tile go a step together, each with keys and values
of its own, the shared term alone is rotated, and a step takes several
blocks of queries (a head's keys serve no other head, so that is how its
matmuls see more rows).

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
    window: int | None = None,
) -> jax.Array:
    """Plain XLA attention (numerics oracle + CPU fallback). [B,H,S,D].
    ``causal``: a query also sees no key after its own position.
    ``window`` (with ``causal``): nor one ``window`` or more positions
    before it — query ``i`` sees keys ``i - window < j <= i``, ``window`` of
    them with its own (the Hugging Face sliding-window convention).

    Grouped-query attention: ``k`` and ``v`` may hold fewer heads than
    ``q`` (``[B,Hkv,S,D]``, ``H`` a multiple of ``Hkv``); key head ``j``
    serves query heads ``j*G .. j*G+G-1``. The G query heads of a group are
    stacked along the query axis of their key head, so no key or value is
    repeated in memory and the rest is the same two contractions. ``v`` may
    be of another width than ``q`` and ``k`` (latent attention: scores over
    192, values of 128): the scale is the score width's, the result ``v``'s
    wide."""
    heads, t_q, d = q.shape[1:]
    group = heads // k.shape[1]
    if group != 1:
        q = q.reshape(q.shape[0], k.shape[1], group * t_q, d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :], scores, NEG_INF)
    if causal:
        t_k = scores.shape[-1]
        visible = np.tril(np.ones((t_q, t_k), bool))
        if window is not None:
            visible &= ~np.tril(np.ones((t_q, t_k), bool), -window)
        scores = jnp.where(np.tile(visible, (group, 1)), scores, NEG_INF)
    elif window is not None:
        raise ValueError("attention_reference: a window is a causal core's")
    weights = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)
    return (ctx.reshape(ctx.shape[0], heads, t_q, ctx.shape[-1])
            if group != 1 else ctx)


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


# ---------------------------------------------------------------------------
# the causal core at head_dim 128 with grouped keys and an optional window

WINDOW_BLOCK = LANES     # queries and keys a block: one lane tile of scores


def windowed_refusal(seq_len: int, head_dim: int, num_heads: int,
                     num_kv_heads: int, window: int | None,
                     qk_norm: bool = False, shared_key_dim: int | None = None,
                     value_dim: int | None = None,
                     head_norm: bool = False) -> str | None:
    """Why ``windowed_attention`` does not take a shape, by name, or None
    where it does. The ONE predicate: the traced guards in
    ``models/laguna.py``, ``models/olmoe.py`` and ``models/joyai.py`` and
    the scorer's selector all ask it. ``qk_norm``: the call hands it a norm
    over the whole projection (the one-block form). ``shared_key_dim``: the
    call hands it a second score term that wide from ONE key head all query
    heads share (the latent form: ``head_dim`` is then the per-head part of
    a score, ``value_dim`` the values' width where it is not
    ``head_dim``). ``head_norm``: the call hands it a norm over each HEAD
    (``models/qwen3_next.py``), the form that also takes heads of TWO lane
    tiles (scores summed over both, values two tiles wide), with no window,
    no whole-projection norm and no shared key."""
    if head_norm:
        if head_dim not in (LANES, 2 * LANES) \
                or (value_dim or head_dim) != head_dim:
            return (f"windowed_attention takes heads of {LANES} or "
                    f"{2 * LANES} (one or two lane tiles a head) under a "
                    f"per-head norm: head_dim {head_dim}"
                    + (f", value_dim {value_dim}" if value_dim else ""))
        if window is not None or qk_norm or shared_key_dim is not None:
            return ("windowed_attention norms q and k a head at a time with "
                    "no window, no norm over the whole projection and no "
                    f"shared key: window {window}")
    elif head_dim != LANES or (value_dim or head_dim) != LANES:
        return (f"windowed_attention takes heads of {LANES} (one lane tile "
                f"a head): head_dim {head_dim}"
                + (f", value_dim {value_dim}" if value_dim else ""))
    if num_heads % num_kv_heads:
        return (f"windowed_attention: {num_heads} query heads do not divide "
                f"into {num_kv_heads} key-value heads")
    if seq_len < WINDOW_BLOCK or seq_len % WINDOW_BLOCK:
        return (f"windowed_attention takes whole blocks of {WINDOW_BLOCK} "
                f"positions: seq_len {seq_len}")
    if window is not None and (window < WINDOW_BLOCK
                               or window % WINDOW_BLOCK):
        return (f"windowed_attention takes a window of whole blocks of "
                f"{WINDOW_BLOCK} positions: window {window}")
    if qk_norm and (seq_len != WINDOW_BLOCK or window is not None):
        return (f"windowed_attention norms q and k over all heads in a step "
                f"that holds a row's one block of {WINDOW_BLOCK} positions, "
                f"with no window: seq_len {seq_len}, window {window}")
    if shared_key_dim is not None:
        if (shared_key_dim < 2 or shared_key_dim % 2
                or LANES % shared_key_dim):
            return (f"windowed_attention takes a shared key whose pairs of "
                    f"dims tile a lane tile of {LANES}: shared_key_dim "
                    f"{shared_key_dim}")
        if (num_heads != num_kv_heads or window is not None or qk_norm
                or num_heads % (LANES // shared_key_dim)):
            return (f"windowed_attention takes a shared key beside one key "
                    f"head a query head, {LANES // shared_key_dim} heads a "
                    f"step, with no window and no QK-norm: {num_heads} query "
                    f"heads, {num_kv_heads} key-value heads, window {window}")
    return None


def rope_lane_tables(cos: np.ndarray, sin: np.ndarray, head_dim: int
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Rotate-half RoPE on the first ``rot = cos.shape[-1]`` dims of a head
    as three per-lane tables and a lane shift, for a kernel that rotates in
    VMEM: ``x * c + roll(x, +s) * up + roll(x, -s) * down`` with ``s = rot /
    2`` — ``roll(x, +s)[l] = x[l - s]`` carries the pair's first half to its
    second (times ``+sin``), ``roll(x, -s)[l] = x[l + s]`` the second to the
    first (times ``-sin``); lanes past ``rot`` keep ``c = 1`` and nothing
    else. ``cos`` and ``sin`` are ``f32[T, rot]`` in the rotate-half layout
    (``models/olmoe.rope_tables``). Host constants of the program."""
    t, rot = cos.shape
    half = rot // 2
    c = np.ones((t, head_dim), np.float32)
    up = np.zeros((t, head_dim), np.float32)
    down = np.zeros((t, head_dim), np.float32)
    c[:, :rot] = cos
    down[:, :half] = -sin[:, :half]
    up[:, half:rot] = sin[:, half:]
    return c, up, down, half


def rope_pair_tables(cos: np.ndarray, sin: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """INTERLEAVED RoPE (the rotated pairs are the adjacent dims ``2i``,
    ``2i + 1``) of ``rot``-wide heads side by side in one lane tile, in
    ``rope_lane_tables``' form — three per-lane tables and a lane shift of
    1: ``roll(x, +1)[l] = x[l - 1]`` carries a pair's even dim to its odd
    one (times ``+sin``), ``roll(x, -1)`` the odd to the even (times
    ``-sin``). ``cos`` and ``sin`` are ``f32[T, rot / 2]``, one column a
    pair; the ``LANES // rot`` heads of a tile read the same tables."""
    t, pairs = cos.shape
    repeat = LANES // (2 * pairs)
    c = np.tile(np.repeat(cos, 2, axis=1), (1, repeat)).astype(np.float32)
    s = np.tile(np.repeat(sin, 2, axis=1), (1, repeat)).astype(np.float32)
    odd = (np.arange(LANES) % 2).astype(bool)[None]
    return c, np.where(odd, s, 0.0), np.where(odd, 0.0, -s), 1


# the widest query step the latent form takes. A head's keys serve no other
# head (group 1), so its matmuls see only as many rows as a step holds
# queries: alone on the v5e at the cell's shape (8 rows x 2,048, 32 heads,
# the mix's lengths) a layer took 4.63 ms at one block of 128, 3.75 at two,
# 3.43 at four and 3.89 at eight, whose steps waste more of a short row
# (every slot real: 8.69 / 6.12 / 4.90 / 4.84; PERF.md, PR 43)
LATENT_QUERY_BLOCKS = 4


def latent_query_blocks(seq_len: int) -> int:
    """Blocks of 128 queries a step of the latent form: the most, up to
    ``LATENT_QUERY_BLOCKS``, that tile the sequence."""
    span = LATENT_QUERY_BLOCKS
    while seq_len % (span * WINDOW_BLOCK):
        span //= 2
    return span


def _windowed_kernel(lens_ref, *refs, group: int, window_blocks: int | None,
                     scale: float, rope_shift: int | None, gated: bool,
                     shared_key: bool = False, span: int = 1,
                     head_dim: int = LANES, head_norm_eps: float | None = None):
    # ``span`` key blocks a query block: queries go by ``span * block`` a
    # step against keys ``block`` at a time (1 but for the latent form).
    # ``head_dim`` lanes a head (one lane tile, or two under
    # ``head_norm_eps``: q and k then normed a head at a time ahead of the
    # rotation, the gates one a LANE)
    block, d = WINDOW_BLOCK, head_dim
    block_q = span * block
    # inputs: q, k, v, [the queries' and the keys' shared score term], [this
    # query block's three tables, the whole row's three], [the gates];
    # output; scratch: m, l, acc, [the rotated keys]
    refs = list(refs)
    q_ref, k_ref, v_ref = (refs.pop(0) for _ in range(3))
    if shared_key:
        # the latent form: ``group`` heads a step, each with keys and values
        # of its own, and the ONE rotated key they all share
        qs_ref, ks_ref = refs.pop(0), refs.pop(0)
    if rope_shift is not None:
        c_ref, up_ref, down_ref, kc_ref, kup_ref, kdown_ref = (
            refs.pop(0) for _ in range(6))
        keys_ref = refs.pop()       # this (row, head)'s rotated keys
    if head_norm_eps is not None:
        qw_ref, kw_ref = refs.pop(0), refs.pop(0)
    if gated:
        gate_ref = refs.pop(0)
    o_ref, m_ref, l_ref, acc_ref = refs
    row, qi = pl.program_id(0), pl.program_id(2)

    def rotated(x, c, up, down):
        x = x.astype(jnp.float32)
        if d == LANES:
            return (x * c + pltpu.roll(x, rope_shift, 1) * up
                    + pltpu.roll(x, d - rope_shift, 1) * down)
        # the tables are one lane tile wide: the rotated dims lie in a
        # head's first tile, the rest of it passes
        first = x[:, :LANES]
        return jnp.concatenate([
            first * c + pltpu.roll(first, rope_shift, 1) * up
            + pltpu.roll(first, LANES - rope_shift, 1) * down,
            x[:, LANES:]], axis=1)

    def normed(x, w_ref):
        # RMS norm over ONE head's dims, float32, ahead of the rotation
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=1, keepdims=True) + head_norm_eps
        ) * w_ref[...]

    def head_wide(x):
        # a statistic kept lane tile wide, against a head's ``d`` lanes
        return x if d == LANES else jnp.concatenate(
            [x] * (d // LANES), axis=1)

    if rope_shift is not None:
        # a (row, head)'s query blocks go by in order: its keys are rotated
        # once, ahead of the first, and kept in VMEM for the rest
        @pl.when((qi == 0) & (lens_ref[row] > 0))
        def _rotate_keys():
            def one(kb, carry):
                at = pl.ds(pl.multiple_of(kb * block, block), block)
                keys = (ks_ref if shared_key else k_ref)[0, at, :]
                if head_norm_eps is not None:
                    keys = normed(keys, kw_ref)
                keys_ref[at, :] = rotated(
                    keys, kc_ref[at, :], kup_ref[at, :],
                    kdown_ref[at, :]).astype(keys_ref.dtype)
                return carry

            jax.lax.fori_loop(0, k_ref.shape[1] // block, one, 0)

    @pl.when(qi * block_q >= lens_ref[row])
    def _past_the_text():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(qi * block_q < lens_ref[row])
    def _real():
        def head(j):
            x = q_ref[0, :, j * d:(j + 1) * d]
            if head_norm_eps is not None:
                x = normed(x, qw_ref)
            if rope_shift is not None and not shared_key:
                x = rotated(x, c_ref[...], up_ref[...], down_ref[...])
            return x.astype(v_ref.dtype)

        if shared_key:
            # each head's own part of q as it arrives, and its lanes of the
            # tile of shared parts, rotated once a block (the other heads'
            # lanes zero: the contraction runs over the whole tile)
            q = [head(j) for j in range(group)]
            tile = rotated(qs_ref[0], c_ref[...], up_ref[...], down_ref[...])
            lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, d), 1)
            wide = d // group
            q_shared = [jnp.where((lane >= j * wide) & (lane < (j + 1) * wide),
                                  tile, 0.0).astype(v_ref.dtype)
                        for j in range(group)]
        else:
            # the group's query heads, stacked along the query axis:
            # [G*bq, D]
            q = jnp.concatenate([head(j) for j in range(group)], axis=0)
        # where a key stands against a query inside a block pair: the same
        # pattern in every stacked head
        rows_ = jax.lax.broadcasted_iota(
            jnp.int32, (group * block_q, block), 0) & (block_q - 1)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (group * block_q, block), 1)

        def against(x, k):
            return jax.lax.dot_general(
                x, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        def scores(kb):
            keys = pl.ds(pl.multiple_of(kb * block, block), block)
            if shared_key:
                # a head's scores are its own term and the shared one's;
                # the heads stacked along the query axis as a group's are
                shared = keys_ref[keys, :]
                s = jnp.concatenate([
                    against(q[j], k_ref[0, keys, j * d:(j + 1) * d])
                    + against(q_shared[j], shared)
                    for j in range(group)], axis=0)
                return s * scale, v_ref[0, keys, :]
            k = k_ref[0, keys, :] if rope_shift is None else keys_ref[keys, :]
            return against(q, k) * scale, v_ref[0, keys, :]  # [G*bq, bk]

        def weighted(p, v):
            p = p.astype(v.dtype)
            if shared_key:
                # each stacked head against its own values
                return jnp.concatenate([
                    jnp.dot(p[j * block_q:(j + 1) * block_q],
                            v[:, j * d:(j + 1) * d],
                            preferred_element_type=jnp.float32)
                    for j in range(group)], axis=0)
            return jnp.dot(p, v, preferred_element_type=jnp.float32)

        def fold(s, v):
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
            acc_ref[...] = head_wide(alpha) * acc_ref[...] + weighted(p, v)
            m_ref[...] = m_next

        # the diagonal block first: every query sees its own key there, so
        # the running maximum is a real score before any wholly masked row
        # of the window's edge arrives
        # (one block a step: ``qi`` itself, so that the form traces what it
        # always did)
        diagonal = qi if span == 1 else qi * span
        s, v = scores(diagonal)
        s = jnp.where(cols <= rows_, s, NEG_INF)
        m = s.max(axis=1, keepdims=True)
        p = jnp.exp(s - m)
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(p.sum(axis=1, keepdims=True),
                                      l_ref.shape)
        acc_ref[...] = weighted(p, v)

        # the blocks every query of this one sees whole
        first = 0 if window_blocks is None else jnp.maximum(
            qi - window_blocks + 1, 0)

        def whole(kb, carry):
            fold(*scores(kb))
            return carry

        jax.lax.fori_loop(first, diagonal, whole, 0)

        # the rest of a wide query block's diagonal: key j of the i-th
        # further block is seen from position i * block + j of this one on
        # (the rows before it fold nothing in: their maximum is a real
        # score since the first diagonal block)
        for i in range(1, span):
            s, v = scores(diagonal + i)
            fold(jnp.where(cols + i * block <= rows_, s, NEG_INF), v)

        if window_blocks is not None:
            # the window's edge: key j of that block is seen by the queries
            # before position j of this one
            @pl.when(qi >= window_blocks)
            def _edge():
                s, v = scores(qi - window_blocks)
                fold(jnp.where(cols > rows_, s, NEG_INF), v)

        out = acc_ref[...] / head_wide(l_ref[...])
        for j in range(group):
            mine = out[j * block_q:(j + 1) * block_q]
            if gated and head_norm_eps is not None:
                mine = mine * gate_ref[0, :, j * d:(j + 1) * d]
            elif gated:
                mine = mine * gate_ref[0, 0, :, j:j + 1]
            o_ref[0, :, j * d:(j + 1) * d] = mine.astype(o_ref.dtype)


def _whole_row_kernel(lens_ref, q_ref, k_ref, v_ref, qw_ref, kw_ref, c_ref,
                      up_ref, down_ref, o_ref, *, group: int, eps: float,
                      scale: float, rope_shift: int):
    """The one-block form: a program owns EVERY head of one row of one block
    of positions, so it can norm q and k over the whole projection (``x *
    rsqrt(mean(x^2) + eps) * weight`` in float32, the statistic over all
    heads), rotate them and round them once, and then run each head's
    causal softmax on one block of scores: no key blocks to walk, no
    running maximum. A row of no real token comes out zero."""
    block, d = WINDOW_BLOCK, LANES
    length = lens_ref[pl.program_id(0)]
    c, up, down = c_ref[...], up_ref[...], down_ref[...]
    visible = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
               <= jax.lax.broadcasted_iota(jnp.int32, (block, block), 0))

    def lanes_of(j):
        return pl.ds(pl.multiple_of(j * d, d), d)

    # the loops over heads are unrolled where they are lowered, not in
    # Python: the body is traced once, not once a head (1.4 s a program
    # shape on the chip's host at sixteen heads), and Mosaic still sees
    # straight code (a rolled loop read 2.20 ms a layer against 1.49)
    def inverse_rms(x_ref):
        width = x_ref.shape[2]

        def add(j, squares):
            x = x_ref[0, :, lanes_of(j)].astype(jnp.float32)
            return squares + x * x

        squares = jax.lax.fori_loop(
            0, width // d, add, jnp.zeros((block, d), jnp.float32),
            unroll=True)
        return jax.lax.rsqrt(
            squares.sum(axis=1, keepdims=True) * (1.0 / width) + eps)

    def head(x_ref, w_ref, inv, j):
        lanes = lanes_of(j)
        x = x_ref[0, :, lanes].astype(jnp.float32) * inv * w_ref[:, lanes]
        x = (x * c + pltpu.roll(x, rope_shift, 1) * up
             + pltpu.roll(x, d - rope_shift, 1) * down)
        return x.astype(v_ref.dtype)

    @pl.when(length == 0)
    def _no_text():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _real():
        inv_q, inv_k = inverse_rms(q_ref), inverse_rms(k_ref)

        def key_head(g, carry):
            k = head(k_ref, kw_ref, inv_k, g)
            v = v_ref[0, :, lanes_of(g)]
            for i in range(group):
                j = g * group + i
                s = jax.lax.dot_general(
                    head(q_ref, qw_ref, inv_q, j), k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(visible, s, NEG_INF)
                p = jnp.exp(s - s.max(axis=1, keepdims=True))
                out = jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)
                o_ref[0, :, lanes_of(j)] = (
                    out / p.sum(axis=1, keepdims=True)).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, k_ref.shape[2] // d, key_head, 0, unroll=True)


def _whole_row_call(lengths, q, k, v, norm, rope, *, group: int, eps: float,
                    rope_shift: int, out_dtype, interpret: bool):
    """``_whole_row_kernel`` over ``(rows,)``: a row's 3 MB of blocks at
    sixteen heads already hide a step's overhead (1.49 ms a layer at bucket
    256 on the v5e against 1.53 at two or four rows a step: PERF.md, PR 34)."""
    b, block, width = q.shape
    kv_width = k.shape[2]

    def whole_row(w):
        return pl.BlockSpec((1, block, w), lambda i, lens: (i, 0, 0))

    def constant(*shape):
        return pl.BlockSpec(shape, lambda i, lens: (0, 0))

    # q, k, v and the context of a step, double-buffered, and room for the
    # tables, one head's scores and Mosaic's own temporaries
    vmem = 2 * block * (
        width * (q.dtype.itemsize + np.dtype(out_dtype).itemsize)
        + kv_width * (k.dtype.itemsize + v.dtype.itemsize)) + (16 << 20)
    return pl.pallas_call(
        functools.partial(_whole_row_kernel, group=group, eps=eps,
                          scale=LANES ** -0.5, rope_shift=rope_shift),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[whole_row(width), whole_row(kv_width),
                      whole_row(kv_width), constant(1, width),
                      constant(1, kv_width)] + [constant(block, LANES)] * 3,
            out_specs=whole_row(width),
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="windowed_attention",
    )(lengths.astype(jnp.int32), q, k, v,
      *(jnp.asarray(w, jnp.float32).reshape(1, -1) for w in norm),
      *(jnp.asarray(x, jnp.float32) for x in rope))


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "window", "rope_shift", "norm_eps",
    "out_dtype", "interpret"))
def windowed_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       lengths: jax.Array, *, num_heads: int,
                       num_kv_heads: int, window: int | None = None,
                       rope: tuple | None = None,
                       rope_shift: int | None = None,
                       gate: jax.Array | None = None,
                       norm: tuple | None = None,
                       norm_eps: float | None = None,
                       shared_key: tuple | None = None,
                       head_norm: tuple | None = None, out_dtype=None,
                       interpret: bool = False) -> jax.Array:
    """Fused causal core with grouped keys. ``q`` ``[B, T, H*128]``, ``k``
    and ``v`` ``[B, T, Hkv*128]`` (heads side by side, as the projections
    write them; query heads ``g*G .. g*G+G-1`` read key head ``g``) ->
    ``[B, T, H*128]`` in ``out_dtype`` (q's by default). Query ``i`` sees
    keys ``j <= i``, with ``window`` only ``j > i - window``. ``lengths``
    ``i32[B]``: the real tokens of each right-padded row; positions from a
    row's first wholly padded query block on come out zero, a padded
    position inside its last real block holds nothing anyone may read
    (``attention_reference`` masks padded keys: the two agree at every real
    position).

    Three things may ride the kernel so that no pass stands between the
    projections and it. ``rope`` = ``rope_lane_tables``' three ``f32[T,
    128]`` tables with ``rope_shift``: q and k arrive UNROTATED (float32, as
    their projections wrote them) and are rotated in VMEM in float32 and
    rounded once to v's dtype — a query block as it arrives, a (row, head)'s
    keys once, ahead of its first query block, into a scratch that its
    later blocks read. ``gate`` ``f32[B, T, H]``: head ``g``'s context is
    multiplied by ``gate[:, :, g]`` in float32 before the one rounding to
    ``out_dtype``. ``norm`` = ``(f32[H*128], f32[Hkv*128])`` with
    ``norm_eps``: the weights of an RMS norm of q and of k over their WHOLE
    projection, all heads, ahead of the rotation (OLMoE's QK-norm). That
    statistic spans the heads, so the call runs the one-block form
    (``_whole_row_kernel``: every head of a row a program, ``seq_len`` one
    block, no window; it rotates too, and has no gate).

    ``shared_key`` = ``(q_shared f32[B, T, H*R], k_shared f32[B, T, R])``,
    the latent form (``models/joyai.py``): a head's score is ``q . k`` over
    its own 128 PLUS ``q_shared . k_shared`` over ``R`` more dims against
    ONE key every head shares, the sum scaled by ``(128 + R)^-1/2``. The
    ``rope`` tables (``rope_pair_tables``) are then the SHARED term's and
    the only rotation: ``q`` and ``k`` go to the contraction as they
    arrive. One key head a query head, ``128 // R`` heads a step (their
    shared parts fill one lane tile; the shared key arrives tiled that many
    times), no window, no gate. A step takes ``latent_query_blocks(T)``
    blocks of 128 queries against keys 128 at a time: a head's own keys
    serve no other head, so a wider query block is the only way its matmuls
    see more rows. A row's output is then zero from its first wholly padded
    STEP on.

    ``head_norm`` = ``(f32[D], f32[D])`` with ``norm_eps``
    (``models/qwen3_next.py``): the weights of an RMS norm of q and of k
    over each HEAD's ``D`` dims (one weight the heads share, as it
    multiplies: a zero-centred norm hands ``1 + w``), ahead of the rotation,
    in the blocked kernel. In this form a head is ``D`` = 128 or 256 wide
    (two lane tiles: scores sum both, values are two tiles wide, the scale
    is ``D^-1/2``), the ``rope`` tables stay ONE lane tile wide
    (``rope_lane_tables(cos, sin, 128)``: the rotated dims lie in a head's
    first tile), and ``gate`` is ``f32[B, T, H*D]``, one multiplier a LANE
    of the context. No window, no shared key.

    ``interpret=True`` runs the kernel through the Pallas interpreter."""
    b, t, width = q.shape
    shared_dim = None if shared_key is None else shared_key[1].shape[-1]
    refusal = windowed_refusal(
        t, width // num_heads, num_heads, num_kv_heads, window,
        qk_norm=norm is not None, shared_key_dim=shared_dim,
        value_dim=v.shape[-1] // num_kv_heads,
        head_norm=head_norm is not None)
    if refusal or width % num_heads:
        raise ValueError(refusal or "windowed_attention: ragged heads")
    if (rope is None) != (rope_shift is None):
        raise ValueError("windowed_attention: rope tables and rope_shift "
                         "come together")
    if (norm is None and head_norm is None) != (norm_eps is None):
        raise ValueError("windowed_attention: norm weights and norm_eps "
                         "come together")
    if head_norm is not None and (
            rope is None or gate is None or gate.shape != q.shape):
        raise ValueError("windowed_attention: the per-head norm comes with "
                         "a rotation and a gate a lane")
    group, block = num_heads // num_kv_heads, WINDOW_BLOCK
    if norm is not None:
        if rope is None or gate is not None:
            raise ValueError("windowed_attention: the one-block form norms "
                             "AND rotates, and has no gate")
        return _whole_row_call(
            lengths, q, k, v, norm, rope, group=group, eps=norm_eps,
            rope_shift=rope_shift, out_dtype=out_dtype or q.dtype,
            interpret=interpret)
    d = width // num_heads              # 128 but under ``head_norm``
    steps, kv_lanes, scale, span = num_kv_heads, d, d ** -0.5, 1
    if shared_key is not None:
        if rope is None or gate is not None:
            raise ValueError("windowed_attention: the latent form rotates "
                             "its shared term, and has no gate")
        # the heads whose shared parts fill a lane tile go a step together,
        # each with its own keys and values beside it
        group = LANES // shared_dim
        steps, kv_lanes = num_kv_heads // group, group * LANES
        scale = (LANES + shared_dim) ** -0.5
        span = latent_query_blocks(t)
    kernel = functools.partial(
        _windowed_kernel, group=group,
        window_blocks=None if window is None else window // block,
        scale=scale, rope_shift=rope_shift,
        gated=gate is not None, shared_key=shared_key is not None, span=span,
        **({} if head_norm is None
           else dict(head_dim=d, head_norm_eps=norm_eps)))
    block_q = span * block
    stacked = (group * block_q, LANES)
    heads_block = pl.BlockSpec((1, block_q, group * d),
                               lambda i, g, qi, lens: (i, qi, g))
    # a row's keys and values of one head stay put while its query blocks
    # go by: fetched once a (row, head)
    row_block = pl.BlockSpec((1, t, kv_lanes),
                             lambda i, g, qi, lens: (i, 0, g))
    in_specs, operands = [heads_block, row_block, row_block], [q, k, v]
    scratch = [pltpu.VMEM(stacked, jnp.float32)] * 2 + [
        pltpu.VMEM((group * block_q, d), jnp.float32)]
    if shared_key is not None:
        q_shared, k_shared = shared_key
        in_specs += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda i, g, qi, lens: (i, qi, g)),
            pl.BlockSpec((1, t, LANES), lambda i, g, qi, lens: (i, 0, 0))]
        operands += [q_shared.astype(jnp.float32),
                     jnp.tile(k_shared.astype(jnp.float32), (1, 1, group))]
    if rope is not None:
        tables = [jnp.asarray(x, jnp.float32) for x in rope]
        table = pl.BlockSpec((block_q, LANES),
                             lambda i, g, qi, lens: (qi, 0))
        whole = pl.BlockSpec((t, LANES), lambda i, g, qi, lens: (0, 0))
        in_specs += [table] * 3 + [whole] * 3
        operands += tables + tables
        scratch.append(pltpu.VMEM((t, d), v.dtype))
    if head_norm is not None:
        in_specs += [pl.BlockSpec((1, d), lambda i, g, qi, lens: (0, 0))] * 2
        operands += [jnp.asarray(w, jnp.float32).reshape(1, d)
                     for w in head_norm]
        in_specs.append(heads_block)
        operands.append(gate.astype(jnp.float32))
    elif gate is not None:
        # [B, Hkv, T, G]: a group's gates are a block's last axis whole
        in_specs.append(pl.BlockSpec((1, 1, block_q, group),
                                     lambda i, g, qi, lens: (i, g, qi, 0)))
        operands.append(gate.astype(jnp.float32).reshape(
            b, t, num_kv_heads, group).transpose(0, 2, 1, 3))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, steps, t // block_q),
        in_specs=in_specs,
        out_specs=heads_block,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, width), out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="windowed_attention",
    )(lengths.astype(jnp.int32), *operands)
