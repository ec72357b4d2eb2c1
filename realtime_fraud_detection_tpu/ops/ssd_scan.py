"""The state-space scan of a Mamba-2 mixer (``models/falcon_h1.py``): the
chunked algorithm in XLA, and as one Pallas kernel for the TPU in which
neither the decay masks nor the per-chunk states leave VMEM.

The recurrence, a head at a time, on a state ``S`` (``d_state x head_dim``,
``S_0`` = ``initial_state`` or zero)::

    S_t = exp(dt_t a) S_{t-1} + dt_t B_t x_t^T        y_t = S_t^T C_t + D x_t

``a`` is one negative scalar a head, ``dt`` the step (after its softplus),
``B`` and ``C`` are shared by the heads of a group (head ``j`` reads group
``j // (heads / groups)``). Written out, ``y_t = sum_{s <= t} exp(cum_t -
cum_s) dt_s (C_t . B_s) x_s + D x_t`` with ``cum`` the running sum of ``dt
a``: a causal "attention" whose scores are masked by a decay. The chunked
form (Dao and Gu's SSD) cuts the positions into chunks of ``chunk``:

- inside a chunk, the decay-masked product ``((C B^T) * decay * dt) x``;
- a chunk's closing state ``sum_s exp(cum_end - cum_s) dt_s B_s x_s^T``;
- the states carried chunk to chunk, ``S_in[c+1] = exp(cum_end[c]) S_in[c]
  + closing[c]`` — the one sequential chain, ``T / chunk`` links long;
- what the earlier chunks contribute, ``exp(cum_t) C_t . S_in``.

``_ssd_xla`` is that in ``jax.numpy``: the numerics oracle, and what a CPU
host, a mesh or a declined shape runs. It writes the ``[chunks, heads,
chunk, chunk]`` float32 masks and the per-chunk states to HBM. ``_ssd_pallas``
keeps them on the chip: a grid over (row, group, chunk), chunks innermost and
in order, the group's heads' states in a VMEM scratch across a row's chunks.
A step forms ``C B^T`` once for the group's heads, then a head at a time the
mask, the three products and the state's update. ``B`` arrives transposed
(``[d_state, chunk]``) and the state is kept ``[d_state, head_dim]``, so
every product is a plain ``[m, k] x [k, n]``; the running sums ``cum`` and
``dt`` arrive as rows ``[heads, chunk]`` (made by XLA: 2 MB a launch), and
the one thing a head needs along the other axis, ``cum`` down the queries,
is a ``chunk x chunk`` transpose of its broadcast (``head_dim`` = ``chunk``
= one lane tile: the same square scales the rows of ``[chunk, head_dim]``).
Heads of 64 (``models/nemotron_h.py``) go TWO a lane tile
(``_ssd_pair_kernel``): a pair's ``x``, ``y`` and states lie side by side
in one tile of 128 lanes, each head's masked product and closing state are formed against the whole
tile (the MXU is 128 wide: a product 64 wide would cost the same) and a
select by lane keeps each head's half; ``C . S`` is ONE product for the
pair. The states and ``D`` arrive and leave pair by pair (``[N, 2 x 64]``),
paired and parted by XLA outside the call.

Precision, both forms: float32 running sums, decays, masks, state and
accumulation; the matmul operands (``x``, ``B``, ``C``, the masked scores,
the state where it is an operand) in ``x``'s dtype — bfloat16 where the
configuration's weights are, float32 in the float32 tests.

``ssd_refusal`` is the ONE predicate on shapes: the traced guard in
``models/falcon_h1.py``, the scorer's engagement counters and the tests ask
it, and it answers by name. Nothing chooses between the forms but it and
``use_pallas`` (the flag the other kernels get: a one-device TPU program).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def ssd_refusal(seq_len: int, head_dim: int, d_state: int, chunk: int,
                num_heads: int, num_groups: int) -> Optional[str]:
    """Why the Pallas form does not take a shape, by name, or None where it
    does (the XLA form takes any)."""
    if head_dim not in (LANES, LANES // 2) or chunk != LANES:
        return (f"ssd_scan's kernel takes heads of {LANES} or {LANES // 2} "
                f"(one or two a lane tile) and chunks of {LANES}: head_dim "
                f"{head_dim}, chunk {chunk}")
    if d_state < LANES or d_state % LANES:
        return (f"ssd_scan's kernel takes a state of whole lane tiles of "
                f"{LANES}: d_state {d_state}")
    if seq_len < chunk or seq_len % chunk:
        return (f"ssd_scan's kernel takes whole chunks of {chunk} "
                f"positions: seq_len {seq_len}")
    if num_heads % num_groups or (num_heads // num_groups) % SUBLANES:
        return (f"ssd_scan's kernel takes groups of whole sublane tiles of "
                f"{SUBLANES} heads: {num_heads} heads in {num_groups} groups")
    return None


def _chunk_sums(dt: jax.Array, a: jax.Array, chunk: int
                ) -> Tuple[jax.Array, jax.Array]:
    """``(dt, cum)`` as ``f32[B, H, T]``: the steps, and the running sum of
    ``dt a`` inside each chunk of ``chunk`` positions (inclusive)."""
    b, t, h = dt.shape
    dt = dt.astype(jnp.float32).transpose(0, 2, 1)               # [B, H, T]
    cum = jnp.cumsum(
        (dt * a.astype(jnp.float32)[None, :, None]).reshape(
            b, h, t // chunk, chunk), axis=-1)
    return dt, cum.reshape(b, h, t)


def _ssd_xla(x, dt, a, B, C, D, chunk: int, initial_state
             ) -> Tuple[jax.Array, jax.Array]:
    """The chunked algorithm in ``jax.numpy`` (module docstring). Every
    contraction is written with its batch indices first: the CPU backend
    has no bfloat16 contraction with a batch dimension that is not
    leading."""
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg, nc, f32, operand = h // g, t // chunk, jnp.float32, x.dtype
    dt_, cum = _chunk_sums(dt, a, chunk)
    dt_ = dt_.reshape(b, g, hg, nc, chunk).transpose(0, 3, 1, 2, 4)
    cum = cum.reshape(b, g, hg, nc, chunk).transpose(0, 3, 1, 2, 4)
    # [B, chunks, G, heads a group, chunk(, P)]; B and C [B, chunks, G, L, N]
    xc = x.reshape(b, nc, chunk, g, hg, p).transpose(0, 1, 3, 4, 2, 5)
    Bc = B.reshape(b, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    Cc = C.reshape(b, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)

    # inside a chunk: ((C B^T) * decay * dt) x
    scores = jnp.einsum("bcgln,bcgsn->bcgls", Cc, Bc,
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    apart = cum[..., :, None] - cum[..., None, :]        # cum_t - cum_s
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, apart, 0.0)), 0.0)
    masked = scores[:, :, :, None] * decay * dt_[..., None, :]
    y = jnp.einsum("bcghls,bcghsp->bcghlp", masked.astype(operand), xc,
                   preferred_element_type=f32)

    # a chunk's closing state, [B, chunks, G, heads a group, N, P]
    to_end = jnp.exp(cum[..., -1:] - cum) * dt_
    weighed = (xc.astype(f32) * to_end[..., None]).astype(operand)
    closing = jnp.einsum("bcgln,bcghlp->bcghnp", Bc, weighed,
                         preferred_element_type=f32)

    # the states carried chunk to chunk: the one sequential chain
    s0 = (jnp.zeros((b, g, hg, n, p), f32) if initial_state is None
          else initial_state.astype(f32).reshape(b, g, hg, n, p))

    def carry(state, link):
        closing_c, total_c = link
        return jnp.exp(total_c)[..., None, None] * state + closing_c, state

    final, incoming = jax.lax.scan(
        carry, s0, (jnp.moveaxis(closing, 1, 0),
                    jnp.moveaxis(cum[..., -1], 1, 0)))
    incoming = jnp.moveaxis(incoming, 0, 1)      # [B, chunks, G, hg, N, P]

    # what the earlier chunks contribute: exp(cum_t) C_t . S_in
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcgln,bcghnp->bcghlp", Cc, incoming.astype(operand),
        preferred_element_type=f32)
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(b, t, h, p)
    y = y + D.astype(f32)[:, None] * x.astype(f32)
    return y, final.reshape(b, h, n, p)


def _open_step(refs, carried: bool):
    """What both kernel bodies do ahead of their loop over a group's heads:
    the refs by name, the group's states set on a row's first chunk (to
    ``s0_ref``'s or zero), ``B`` (transposed) and ``C`` of the chunk, ``C
    B^T`` and the causal mask; and ``close()``, which hands the states out
    on the row's last chunk."""
    refs = list(refs)
    x_ref, bt_ref, c_ref, dt_ref, cum_ref, d_ref = (refs.pop(0)
                                                    for _ in range(6))
    s0_ref = refs.pop(0) if carried else None
    y_ref, final_ref, state = refs
    chunk_i, chunks = pl.program_id(2), pl.num_programs(2)

    @pl.when(chunk_i == 0)
    def _first_chunk():
        state[...] = (s0_ref[0] if carried else jnp.zeros_like(state))

    b_t, c_m = bt_ref[0], c_ref[0]                  # [N, L] and [L, N]
    scores = jnp.dot(c_m, b_t, preferred_element_type=jnp.float32)  # [L, L]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0))

    def close():
        @pl.when(chunk_i == chunks - 1)
        def _last_chunk():
            final_ref[0] = state[...]

    return (x_ref, dt_ref, cum_ref, d_ref, y_ref, state, b_t, c_m, scores,
            causal, close)


def _ssd_kernel(*refs, heads: int, carried: bool):
    """One (row, group, chunk) step: ``heads`` heads of one group over one
    chunk of one row, the group's states in ``state`` (scratch) since the
    row's first chunk."""
    (x_ref, dt_ref, cum_ref, d_ref, y_ref, state, b_t, c_m, scores, causal,
     close) = _open_step(refs, carried)
    lanes = LANES
    operand = x_ref.dtype

    # unrolled where it is lowered, not in Python: the body is traced once
    # (``ops/attention._whole_row_kernel`` says what a Python loop cost)
    def head(j, carry):
        at = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
        cum_s = jnp.broadcast_to(cum_ref[0, pl.ds(j, 1), :], (lanes, lanes))
        cum_t = cum_s.T                 # cum down the queries, every lane
        dt_s = dt_ref[0, pl.ds(j, 1), :]                          # [1, L]
        x = x_ref[0, :, at]                                       # [L, P]
        s_in = state[j]                                           # [N, P]
        decay = jnp.where(
            causal, jnp.exp(jnp.where(causal, cum_t - cum_s, 0.0)), 0.0)
        y = jnp.dot((scores * decay * dt_s).astype(operand), x,
                    preferred_element_type=jnp.float32)
        y = y + jnp.exp(cum_t) * jnp.dot(
            c_m, s_in.astype(operand), preferred_element_type=jnp.float32)
        y_ref[0, :, at] = (
            y + d_ref[pl.ds(j, 1), :] * x.astype(jnp.float32)
        ).astype(y_ref.dtype)
        # the closing state: the chunk's own part, and what came in decayed
        # over the whole chunk
        end = cum_t[lanes - 1:]         # [1, L]: cum at the chunk's end
        to_end = jnp.exp(end - cum_s[:1]) * dt_s                  # [1, L]
        state[j] = jnp.exp(end) * s_in + jnp.dot(
            (b_t.astype(jnp.float32) * to_end).astype(operand), x,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, heads, head, 0, unroll=True)
    close()


def _ssd_pair_kernel(*refs, heads: int, carried: bool):
    """``_ssd_kernel``'s step for heads of 64, two a lane tile: ``heads``
    heads of one group as ``heads / 2`` pairs, ``state`` ``[pairs, N, 128]``
    (head ``2 j`` in a tile's first 64 lanes, ``2 j + 1`` in the rest), and
    ``d_ref`` ``[1, pairs, 128]`` likewise."""
    (x_ref, dt_ref, cum_ref, d_ref, y_ref, state, b_t, c_m, scores, causal,
     close) = _open_step(refs, carried)
    lanes = LANES
    operand = x_ref.dtype

    def halves(first, second):
        # the pair's first head in a tile's first 64 lanes, the second's
        # in the rest
        return jnp.where(jax.lax.broadcasted_iota(
            jnp.int32, first.shape, 1) < lanes // 2, first, second)

    def pair(j, carry):
        at = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
        x = x_ref[0, :, at]                     # [L, 2 x 64]: both heads
        s_in = state[j]                         # [N, 2 x 64]

        def one(head):
            # against the pair's whole tile: the other head's lanes are
            # dropped by ``halves``
            cum_s = jnp.broadcast_to(cum_ref[0, pl.ds(head, 1), :],
                                     (lanes, lanes))
            cum_t = cum_s.T
            dt_s = dt_ref[0, pl.ds(head, 1), :]                   # [1, L]
            decay = jnp.where(
                causal, jnp.exp(jnp.where(causal, cum_t - cum_s, 0.0)), 0.0)
            y = jnp.dot((scores * decay * dt_s).astype(operand), x,
                        preferred_element_type=jnp.float32)
            end = cum_t[lanes - 1:]                               # [1, L]
            to_end = jnp.exp(end - cum_s[:1]) * dt_s
            closing = jnp.dot(
                (b_t.astype(jnp.float32) * to_end).astype(operand), x,
                preferred_element_type=jnp.float32)
            return y, jnp.exp(cum_t), jnp.exp(end), closing

        first, second = one(2 * j), one(2 * j + 1)
        y, since, whole, closing = (halves(a, b)
                                    for a, b in zip(first, second))
        # what the earlier chunks contribute, ONE product for the pair
        y = y + since * jnp.dot(c_m, s_in.astype(operand),
                                preferred_element_type=jnp.float32)
        y_ref[0, :, at] = (
            y + d_ref[0, pl.ds(j, 1), :] * x.astype(jnp.float32)
        ).astype(y_ref.dtype)
        state[j] = whole * s_in + closing
        return carry

    jax.lax.fori_loop(0, heads // 2, pair, 0, unroll=True)
    close()


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_pallas(x, dt, a, B, C, D, initial_state, *, chunk: int,
                interpret: bool) -> Tuple[jax.Array, jax.Array]:
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    refusal = ssd_refusal(t, p, n, chunk, h, g)
    if refusal:
        raise ValueError(refusal)
    dt_, cum = _chunk_sums(dt, a, chunk)
    # heads of 64 go two a lane tile: a group's heads are ``tiles`` tiles
    # of ``LANES``, the states and D paired by XLA on the way in and parted
    # on the way out (nothing where a head is a tile)
    side = LANES // p
    tiles = hg // side
    operands = [
        x.reshape(b, t, h * p),
        B.reshape(b, t, g * n).transpose(0, 2, 1),          # [B, G*N, T]
        C.reshape(b, t, g * n),
        dt_, cum,
        jnp.broadcast_to(D.astype(jnp.float32)[:, None], (h, p)),
    ]
    skip = pl.BlockSpec((hg, p), lambda i, k, c: (k, 0))
    if side > 1:
        # a group's pairs as a block's whole side: four pairs are no
        # sublane tile
        operands[-1] = operands[-1].reshape(g, tiles, LANES)
        skip = pl.BlockSpec((1, tiles, LANES), lambda i, k, c: (k, 0, 0))
    rows = pl.BlockSpec((1, chunk, hg * p), lambda i, k, c: (i, c, k))
    per_head = pl.BlockSpec((1, hg, chunk), lambda i, k, c: (i, k, c))
    states = pl.BlockSpec((1, tiles, n, LANES), lambda i, k, c: (i, k, 0, 0))
    in_specs = [
        rows,
        pl.BlockSpec((1, n, chunk), lambda i, k, c: (i, k, c)),
        pl.BlockSpec((1, chunk, n), lambda i, k, c: (i, c, k)),
        per_head, per_head,
        skip,
    ]
    if initial_state is not None:
        in_specs.append(states)
        s0 = initial_state.astype(jnp.float32)
        if side > 1:
            s0 = s0.reshape(b, h // side, side, n, p).transpose(
                0, 1, 3, 2, 4).reshape(b, h // side, n, LANES)
        operands.append(s0)
    y, final = pl.pallas_call(
        functools.partial(_ssd_kernel if side == 1 else _ssd_pair_kernel,
                          heads=hg, carried=initial_state is not None),
        name="ssd_scan",
        grid=(b, g, t // chunk),
        in_specs=in_specs,
        out_specs=(rows, states),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * p), jnp.float32),
                   jax.ShapeDtypeStruct((b, h // side, n, LANES),
                                        jnp.float32)),
        scratch_shapes=[pltpu.VMEM((tiles, n, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(*operands)
    if side > 1:
        final = final.reshape(b, h // side, n, side, p).transpose(
            0, 1, 3, 2, 4).reshape(b, h, n, p)
    return y.reshape(b, t, h, p), final


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, *, chunk: int,
             initial_state: Optional[jax.Array] = None,
             use_pallas: bool = False, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """``(y f32[B, T, H, P], final_state f32[B, H, N, P])`` of the
    recurrence in the module docstring: ``x`` ``[B, T, H, P]``, ``dt``
    ``f32[B, T, H]`` (positive), ``a`` ``f32[H]`` (negative), ``B`` and ``C``
    ``[B, T, G, N]`` in ``x``'s dtype, ``D`` ``f32[H]``; ``initial_state``
    ``f32[B, H, N, P]`` or None (zero). A sequence cut in two, the first
    part's ``final_state`` handed on as the second's ``initial_state``,
    gives what the whole gives. ``use_pallas`` asks for the kernel; a shape
    ``ssd_refusal`` names runs the XLA form, as does a ``T`` that is no
    whole number of chunks (padded with steps of ``dt`` 0, which leave the
    state alone). ``interpret=True`` runs the kernel through the Pallas
    interpreter."""
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if use_pallas and ssd_refusal(t, p, n, chunk, h, g) is None:
        return _ssd_pallas(x, dt, a, B, C, D, initial_state, chunk=chunk,
                           interpret=interpret)
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    y, final = _ssd_xla(x, dt, a, B, C, D, chunk, initial_state)
    return y[:, :t], final
