"""The depthwise causal convolution of a mixer with its SiLU, as one Pallas
kernel for the TPU: ``silu(bias + sum_i taps[i] x[t - (K - 1) + i])`` over
``C`` channels of ``x`` ``f32[B, T, W]``, every element read from HBM once
and every output written once, already cut into the parts its caller reads.

The XLA form is ``models/falcon_h1.causal_conv`` under ``jax.nn.silu``: a
pad and ``K`` shifted slices along the position axis. A shift of one, two or
three positions is no whole tile of the TPU's memory, whichever way the
array lies, and the fusion runs at a quarter of its bytes' rate (PERF.md §6,
PR 55). Here a grid step holds ALL ``T`` positions of one row under one tile
of channels, so no step needs a neighbour's positions: it walks the block a
piece at a time, and a piece's ``K - 1`` shifted views are ``pltpu.roll``s
of the piece with the tile of positions before it in front — zeros before
the row's first position, which is what causality asks. The sum runs in
``causal_conv``'s own order (tap 0 first, the bias last), SiLU in float32,
then ONE rounding to the part's dtype.

**The kernel reads ``x`` where it lies.** A ``[B, T, W]`` array whose ``W``
is a whole number of lane tiles lies row-major on the TPU, positions down
the sublanes: a block is ``[T, tile]`` and the rolls run along the
sublanes. One whose ``W`` is not (``W_in``'s result in a Mamba-2 mixer:
9,248 or 10,304 wide) is stored POSITIONS-MINOR instead — ``[B, W, T]`` in
memory, the 2,048 positions along the lanes — and a Mosaic call handed a
row-major slice of it costs a transposing copy of the slice. So
``positions_last`` takes the array as ``[B, W, T]`` (``jnp.swapaxes`` of
what the caller holds: a bitcast), a block is ``[tile, T]``, the rolls run
along the lanes, and the parts come ``[B, C_i, T]``. Either way ``offset``
names the first convolved channel of ``W``, so a caller's slice is the
block index and no copy.

The callers cut the result at once (``x | B | C`` of a Mamba-2 mixer, ``q |
k | v`` of a Gated-DeltaNet one): ``parts`` are the widths, whole lane
tiles each, and the kernel writes one array a part in the dtype its reader
rounds to — a grid step belongs to one part and writes that part's block
alone (the other parts' block indices stand still, so nothing of them is
written back).

``conv_refusal`` is the ONE predicate on shapes: the traced guards in
``models/falcon_h1.py`` / ``models/qwen3_next.py``, the scorer's engagement
counters and the tests ask it, and it answers by name. Nothing chooses
between the forms but it and ``use_pallas`` (the flag the other kernels
get: a one-device TPU program). ``tools/conv_alone.py`` times the kernel
beside the XLA form on the chip (``tools/conv_alone_pr55.json``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# (positions, channels) a loop step convolves: what one step costs beside
# its work — ~90 cycles, the step's own latency, nothing overlapping the
# next's — is shared by the vregs it holds, and under ~16 of them it shows
# (tools/conv_alone_pr55.json). Positions down the sublanes: a strip of
# rows under the block's every channel (None); along the lanes, where a
# step also broadcasts its channels' taps along the lanes: up to a whole
# row of positions under two packed bfloat16 tiles of channels
STEP = (32, None)
STEP_POSITIONS_LAST = (2048, 32)
# what a grid step's blocks may take of VMEM: x in and a part out, float32
# at the widest, each double-buffered
BLOCK_BYTES = 16 << 20
# channels a grid step holds, where the parts and the budget allow
CHANNEL_TILE = 512


def conv_tiling(seq_len: int, parts: Sequence[int], offset: int = 0) -> int:
    """Channels a grid step holds: the widest whole number of lane tiles,
    up to ``CHANNEL_TILE``, that divides every part (and ``offset``) and
    keeps a step's blocks of ``seq_len`` positions inside ``BLOCK_BYTES``
    (0: not one lane tile fits)."""
    common = math.gcd(offset, *parts)
    fits = [tile for tile in range(LANES, min(common, CHANNEL_TILE) + 1, LANES)
            if common % tile == 0 and seq_len * tile * 16 <= BLOCK_BYTES]
    return max(fits, default=0)


def conv_refusal(seq_len: int, parts: Sequence[int], taps: int,
                 offset: int = 0) -> Optional[str]:
    """Why the Pallas form does not take a shape, by name, or None where it
    does (the XLA form takes any): ``parts`` the widths the channels are
    cut into, from channel ``offset`` of the array on, ``taps`` the
    convolution's length."""
    if any(width < LANES or width % LANES for width in parts) \
            or offset % LANES:
        return (f"causal_conv's kernel writes parts of whole lane tiles of "
                f"{LANES} channels: parts {tuple(parts)} from channel "
                f"{offset}")
    if not 1 <= taps <= SUBLANES:
        return (f"causal_conv's kernel shifts inside one sublane tile of "
                f"{SUBLANES} positions: {taps} taps")
    if seq_len < LANES or seq_len % LANES:
        return (f"causal_conv's kernel takes whole lane tiles of {LANES} "
                f"positions: seq_len {seq_len}")
    if not conv_tiling(seq_len, parts, offset):
        return (f"causal_conv's kernel holds a row's every position in one "
                f"block of {BLOCK_BYTES >> 20} MiB: seq_len {seq_len}")
    return None


def _lay(last: bool, row, positions, channels):
    """An index or a shape of ``x`` or of a part, by orientation."""
    return (row, channels, positions) if last else (row, positions, channels)


def _by_channel(last: bool, channels, other):
    """The same of the taps and the bias: the channels run the way ``x``'s
    do."""
    return (channels, other) if last else (other, channels)


def _conv_kernel(*refs, bounds: Tuple[Tuple[int, int], ...], taps: int,
                 step: Tuple[int, int], biased: bool, last: bool):
    """One (row, channel tile) grid step: all positions of one row under
    one tile of channels, written to the part the tile lies in. ``bounds``
    are the parts' first and past-the-last tiles; ``last`` says the
    positions are the block's last axis (``[tile, T]``, not ``[T, tile]``);
    a loop step convolves ``step`` = (positions, channels) of the block."""
    x_ref, w_ref = refs[:2]
    out_refs = refs[2 + biased:]
    axis = 1 if last else 0
    halo = (SUBLANES, LANES)[axis]
    along = x_ref.shape[1 + axis] // step[0]
    down = x_ref.shape[2 - axis] // step[1]

    def convolve(out_ref):
        def a_step(i, carry):
            at = pl.multiple_of(i % along * step[0], step[0])
            before = pl.multiple_of(jnp.maximum(at - halo, 0), halo)
            ch = pl.ds(pl.multiple_of(i // along * step[1], step[1]), step[1])
            cur = x_ref[_lay(last, 0, pl.ds(at, step[0]), ch)]
            # the tile of positions before the piece in front of it: zeros
            # before the row's first position
            window = jnp.concatenate(
                [jnp.where(at > 0,
                           x_ref[_lay(last, 0, pl.ds(before, halo), ch)], 0.0),
                 cur], axis=axis)
            acc = None
            for tap in range(taps):
                shift = taps - 1 - tap
                seen = cur if shift == 0 else jax.lax.slice_in_dim(
                    pltpu.roll(window, shift, axis), halo, None, axis=axis)
                term = seen * w_ref[_by_channel(last, ch, pl.ds(tap, 1))]
                acc = term if acc is None else acc + term
            if biased:
                acc = refs[2][_by_channel(last, ch, slice(None))] + acc
            out_ref[_lay(last, 0, pl.ds(at, step[0]), ch)] = jax.nn.silu(
                acc).astype(out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, along * down, a_step, 0)

    tile = pl.program_id(1)
    for out_ref, (first, past) in zip(out_refs, bounds):
        pl.when((first <= tile) & (tile < past))(
            functools.partial(convolve, out_ref))


@functools.partial(jax.jit, static_argnames=(
    "offset", "parts", "dtypes", "tile", "step", "last", "interpret"))
def _conv_pallas(x, taps, bias, *, offset: int, parts: Tuple[int, ...],
                 dtypes: Tuple[jnp.dtype, ...], tile: int,
                 step: Tuple[int, int], last: bool, interpret: bool
                 ) -> Tuple[jax.Array, ...]:
    b, t = x.shape[0], x.shape[2 if last else 1]
    k, c = taps.shape
    if sum(parts) != c or len(parts) != len(dtypes):
        raise ValueError(f"causal_conv: parts {parts} in {dtypes} under "
                         f"taps of {c} channels")
    edges = [sum(parts[:i]) // tile for i in range(len(parts) + 1)]
    bounds = tuple(zip(edges, edges[1:]))
    lead = offset // tile
    lay = functools.partial(_lay, last)
    by_channel = functools.partial(_by_channel, last)

    def part_blocks(first, past):
        # a part's block stands still while the grid walks the others'
        # tiles: only the part a step writes is written back
        return pl.BlockSpec(lay(1, t, tile), lambda i, j: lay(
            i, 0, jnp.clip(j - first, 0, past - first - 1)))

    taps = taps.astype(jnp.float32)
    operands = [x.astype(jnp.float32), taps.T if last else taps]
    in_specs = [pl.BlockSpec(lay(1, t, tile), lambda i, j: lay(i, 0, lead + j)),
                pl.BlockSpec(by_channel(tile, k),
                             lambda i, j: by_channel(j, 0))]
    if bias is not None:
        operands.append(bias.astype(jnp.float32).reshape(by_channel(c, 1)))
        in_specs.append(pl.BlockSpec(by_channel(tile, 1),
                                     lambda i, j: by_channel(j, 0)))
    return tuple(pl.pallas_call(
        functools.partial(_conv_kernel, bounds=bounds, taps=k, step=step,
                          biased=bias is not None, last=last),
        name="causal_conv",
        grid=(b, c // tile),
        in_specs=in_specs,
        out_specs=tuple(part_blocks(*edge) for edge in bounds),
        out_shape=tuple(jax.ShapeDtypeStruct(lay(b, t, width), dtype)
                        for width, dtype in zip(parts, dtypes)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=BLOCK_BYTES + (16 << 20)),
        interpret=interpret,
    )(*operands))


def causal_conv_silu(x: jax.Array, taps: jax.Array,
                     bias: Optional[jax.Array] = None, *,
                     parts: Sequence[int], dtypes: Sequence, offset: int = 0,
                     positions_last: bool = False, interpret: bool = False
                     ) -> Tuple[jax.Array, ...]:
    """``silu(causal_conv(x[..., offset:offset + C], taps, bias))`` cut
    along the channels into ``parts``, part ``i`` ``[B, T, parts[i]]`` in
    ``dtypes[i]``: ``x`` ``f32[B, T, W]``, ``taps`` ``f32[K, C]`` (tap ``K
    - 1`` weighs position t itself), ``bias`` ``f32[C]`` or None, zeros
    before the row. With ``positions_last`` ``x`` is ``[B, W, T]`` and part
    ``i`` ``[B, parts[i], T]`` (the module docstring says when). The kernel
    alone: the caller answers for the shape (``conv_refusal``).
    ``interpret=True`` runs it through the Pallas interpreter."""
    parts = tuple(int(width) for width in parts)
    seq_len = x.shape[2 if positions_last else 1]
    refusal = conv_refusal(seq_len, parts, taps.shape[0], offset)
    if refusal:
        raise ValueError(refusal)
    tile = conv_tiling(seq_len, parts, offset)
    positions, channels = STEP_POSITIONS_LAST if positions_last else STEP
    return _conv_pallas(
        x, taps, bias, offset=offset, parts=parts,
        dtypes=tuple(jnp.dtype(d) for d in dtypes), tile=tile,
        step=(math.gcd(seq_len, positions), channels or tile),
        last=positions_last, interpret=interpret)
