"""The routed experts' way out: the tokens' rows gathered into expert order,
``ops.combine`` with the copy's direction turned.

``rows_to_experts(x, src, held, dtype)`` is ``x.astype(dtype)[src]`` on
every row under ``held``: ``src`` ``i32[pairs]`` is ``order // top_k`` of
``models.olmoe.apply_experts`` (the token each sorted (token, expert) pair
reads), and ``held`` is ``sum(group_sizes)`` — the pairs of padding tokens
and of experts held elsewhere are keyed past the last group and sort last,
the grouped kernels never visit their rows, so the rows from ``held`` on
need no fetch and hold whatever they hold. Two forms:

- The XLA form: the cast and a gather of every row. What CPU hosts and the
  tests run, and every shape where XLA's gather already writes its rows at
  the speed of HBM (``dispatch_supported``).
- The Pallas form, two calls. ``lay_rows`` casts the source and lays it so
  that a row is ONE contiguous piece in the tiled HBM layout — ``[N, H /
  128, 128]``; a row of ``bf16[N, H]`` is ``H / 128`` pieces of 256 B — in
  one pass (XLA's own reshape of the cast rows is a second, relayout copy
  that carries no scope). ``dispatch_rows`` leaves that array in HBM: a
  grid over blocks of ``TM`` OUTPUT rows; a step's ``src`` arrives in
  SMEM, every row under ``held`` starts one copy into one of two VMEM slots
  (a DMA semaphore each; eight copies a turn of the scalar loop, no branch
  a row; block ``i``'s copies are started before block ``i - 1``'s are
  waited for, and a block is waited for by the bits of its count, not row
  by row), and the block is written dense as ``dtype[TM, H]``, which the
  grouped kernels read exactly as they read XLA's result. The turns past
  the last held block ask for its blocks again: nothing is fetched or
  written for the rows no group holds. What the kernel pays for is the
  scalar loop that starts the copies, not the bytes.

**A 16-bit row in 32-bit words.** A ``bf16[16, 128]`` tile holds rows ``2s``
and ``2s + 1`` in the low and high halves of sublane ``s``'s words, so the
source of a 16-bit ``dtype`` is handed over as the words themselves,
``u32[N, H / 256, 128]`` (lane tile ``2s`` low, ``2s + 1`` high: the bytes
of ``bf16[N, H / 128, 128]``), fetched as words, and a dense output tile's
words are put together from an even and an odd row's with two shifts and
two masks (``_packed_block``). A 32-bit ``dtype`` (float32 weights: the
tests) goes as it is.

``dispatch_supported`` is the ONE predicate on shapes: the traced guard
below, the scorer's ``KernelSite`` and the tests all ask it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realtime_fraud_detection_tpu.ops.combine import SMEM_TILE
from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    LANES,
    VMEM_CEILING,
)

# the OUTPUT rows a step holds: the largest that divides the pairs
ROW_TILES = (1024, 512, 256, 128, 64, 32, 16)
# the rows started a turn of the scalar loop, and what a block's fetched
# rows are rounded up to (a row past ``held`` has a source like any other)
ROWS_A_TURN = 8
# the output rows put together at a time: their words stay in registers
BLOCK_ROWS = 64
# the source rows a step of ``lay_rows`` casts and lays
LAY_TILES = (512, 256, 128, 64, 32, 16, 8)
# The line between the two forms: the largest cast source that XLA's gather
# was seen to read about once on a v5e (tools/grouped_alone.py --dispatch ->
# tools/grouped_alone_pr53.json, ``xla_gather_by_source``; PERF.md section
# 6, PR 53). Gathering 262,144 rows out of ``bf16[n, 2048]`` it wrote a row
# in 6.8 ns at n = 24,576 and at n = 28,672 (96 and 112 MiB) and in 33.7 ns
# at n = 29,184 and 32,768 (114 and 128 MiB): up to there XLA holds the
# source in its fast memory space (``S(1)`` in the compiled text), past it
# in HBM.
XLA_KEEPS_BYTES = 112 << 20


def dispatch_tile(pairs: int) -> int:
    """``TM``, the output rows a step of the kernel holds (0: no tile
    divides ``pairs``)."""
    return next((t for t in ROW_TILES if pairs % t == 0), 0)


def dispatch_takes(pairs: int, hidden: int, itemsize: int) -> bool:
    """The shapes ``dispatch_rows`` can run at, wherever it pays: rows of
    whole 32-bit lane tiles, a block of rows that divides the pairs."""
    return (itemsize in (2, 4) and hidden % (LANES * 4 // itemsize) == 0
            and dispatch_tile(pairs) > 0)


def dispatch_supported(n: int, pairs: int, hidden: int, itemsize: int
                       ) -> bool:
    """Whether ``pairs`` rows of ``hidden`` elements of ``itemsize`` bytes
    out of ``n`` tokens' go out through the kernel: a shape it can run at
    (``dispatch_takes``) whose cast source, ``n x hidden x itemsize``
    bytes, is larger than ``XLA_KEEPS_BYTES`` — "the source does not fit
    what XLA keeps on chip". Of the seven routed cells' programs that is the
    every-slot program of 32,768 slots of 2,048 (OLMoE's and ZAYA1's
    full-window cells, a source of 128 MiB: XLA's cast and gather 9.42 and
    1.82 ms a layer, ``lay_rows`` + ``dispatch_rows`` 4.75 and 1.24). The
    compact programs of 24,576 slots (96 MiB: XLA 1.64 / 0.54 ms against
    the kernels' 2.33 / 0.64) and JoyAI's 2,048-position programs (48-64
    MiB: 0.89 / 1.13 against 1.16 / 1.53) keep XLA's gather, which a fetch
    paced by the scalar loop cannot match while the source sits in the fast
    memory; Nemotron-3-Nano's rows (2,688 = 10 1/2 words of lane tiles) the
    kernel cannot take. (Laguna's programs, 72-96 MiB, keep XLA's too, at
    1.48 / 1.94 ms against 0.70 / 1.52: there a quarter of the pairs are
    held and the fetch skips the rest — a rule on shapes alone does not see
    that; PERF.md section 7, PR 53.)"""
    return (dispatch_takes(pairs, hidden, itemsize)
            and n * hidden * itemsize > XLA_KEEPS_BYTES)


def dispatch_vmem_bytes(tm: int, hidden: int, itemsize: int) -> int:
    """The VMEM ``dispatch_rows`` names: the two slots of fetched rows, the
    result block double-buffered, and 4 MB for what Mosaic keeps itself."""
    return 4 * tm * hidden * itemsize + (4 << 20)


def dispatch_reference(x: jax.Array, src: jax.Array, dtype) -> jax.Array:
    """The XLA form: matmul operands take the stored dtype of the weights
    (bfloat16 as deployed; float32 weights make a float32 program, for
    tests)."""
    return x.astype(dtype)[src]


def row_pieces(x: jax.Array, dtype) -> jax.Array:
    """``x`` ``[N, H]`` cast to ``dtype`` and laid a row a contiguous piece,
    as 32-bit words: ``dtype[N, H / 128, 128]`` of a 32-bit ``dtype``,
    ``u32[N, H / 256, 128]`` of a 16-bit one (lane tile ``2s`` in the low
    halves of sublane ``s``, ``2s + 1`` in the high: how a ``[16, 128]``
    tile of it lies). The XLA form of ``lay_rows``: what the tests hold the
    kernel to (on a TPU it compiles to several passes, one of them a
    relayout copy that carries no scope)."""
    n, hidden = x.shape
    x = x.astype(dtype)
    if x.dtype.itemsize == 4:
        return x.reshape(n, hidden // LANES, LANES)
    halves = jax.lax.bitcast_convert_type(
        x.reshape(n, hidden // (2 * LANES), 2, LANES), jnp.uint16
    ).astype(jnp.uint32)
    return halves[:, :, 0] | (halves[:, :, 1] << 16)


def _lay_kernel(x, out, *, dtype):
    """A block of ``x``'s rows ``[tb, H]`` cast to ``dtype`` and stored a
    lane tile (of a 16-bit ``dtype``: a pair of them, as words) at a time
    into ``out`` ``[tb, pieces, 128]``: a store with a sublane stride, the
    one ``ops.grouped_matmul._down_kernel`` writes its rows with."""
    tb, pieces, _ = out.shape
    rows_at_once = min(tb, BLOCK_ROWS)

    def lanes(rows, c):
        return x[rows, c * LANES:(c + 1) * LANES].astype(dtype)

    def laid(b, _):
        rows = pl.ds(pl.multiple_of(b * rows_at_once, rows_at_once),
                     rows_at_once)
        for s in range(pieces):
            if dtype.itemsize == 4:
                out[rows, s, :] = lanes(rows, s)
                continue
            # a 16-bit value's bits are the high half of its float32's
            low, high = (pltpu.bitcast(lanes(rows, 2 * s + h).astype(
                jnp.float32), jnp.uint32) for h in (0, 1))
            out[rows, s, :] = (low >> 16) | (high & jnp.uint32(0xFFFF0000))
        return 0
    jax.lax.fori_loop(0, tb // rows_at_once, laid, 0)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def lay_rows(x: jax.Array, *, dtype, interpret: bool = False) -> jax.Array:
    """``row_pieces(x, dtype)`` as ONE pass: ``x`` is read once and the
    words written once, under whatever scope the call is traced in."""
    dtype = jnp.dtype(dtype)
    n, hidden = x.shape
    pieces = hidden * dtype.itemsize // (4 * LANES)
    tb = next(t for t in LAY_TILES if n % t == 0)
    words = jnp.dtype(jnp.uint32) if dtype.itemsize == 2 else dtype
    return pl.pallas_call(
        functools.partial(_lay_kernel, dtype=dtype),
        name="lay_rows",
        out_shape=jax.ShapeDtypeStruct((n, pieces, LANES), words),
        grid=(n // tb,),
        in_specs=[pl.BlockSpec((tb, hidden), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb, pieces, LANES), lambda i: (i, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=32 << 20),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=n * hidden * (x.dtype.itemsize + dtype.itemsize)),
        interpret=interpret,
    )(x)


def _packed_block(even, odd, high: bool, dtype):
    """``dtype[2 R, 128]`` of 16 bits from ``u32[R, 128]`` words of the
    even and of the odd output rows: row ``2r`` is the low (``high``: the
    high) halves of ``even[r]``, row ``2r + 1`` those of ``odd[r]``."""
    if high:
        words = (even >> 16) | (odd & jnp.uint32(0xFFFF0000))
    else:
        words = (even & jnp.uint32(0xFFFF)) | (odd << 16)
    return pltpu.bitcast(words, dtype)


def _dispatch_kernel(held, src, x3, out, buf, sems, *, tm: int, pieces: int):
    """Turn ``i`` of ``steps + 1``: the copies of block ``i``'s rows are
    started, then block ``i - 1`` — whose copies the turn before started —
    is waited for and written. ``held`` ``i32[1]`` in SMEM: the rows that
    are fetched, a prefix; ``src``: block ``i``'s source rows, ``i32[tm]``
    in SMEM; ``x3`` ``[N, pieces, 128]`` 32-bit words in HBM; ``out`` block
    ``i - 1``'s ``[tm, H]``; ``buf`` ``[2, tm * pieces, 128]``: slot, a
    row's ``pieces`` sublanes one under the other."""
    turn_i, blocks = pl.program_id(0), pl.num_programs(0) - 1
    into, slot = turn_i % 2, (turn_i + 1) % 2
    packed = out.dtype.itemsize == 2

    def fetched(block):
        # the block's rows under ``held``, in whole turns of the walk
        rows = jnp.clip(held[0] - block * tm, 0, tm)
        return (rows + ROWS_A_TURN - 1) // ROWS_A_TURN * ROWS_A_TURN

    @pl.when(turn_i < blocks)
    def _():
        def turn(g, _):
            for u in range(ROWS_A_TURN):
                n = g * ROWS_A_TURN + u
                pltpu.make_async_copy(
                    x3.at[src[n]], buf.at[into, pl.ds(n * pieces, pieces), :],
                    sems.at[into]).start()
            return 0
        jax.lax.fori_loop(0, fetched(turn_i) // ROWS_A_TURN, turn, 0)

    def arrived(rows: int):
        # a wait is for a number of bytes on a semaphore, whatever copies
        # brought them: ``rows`` rows' worth at once
        piece = buf.at[slot, pl.ds(0, rows * pieces), :]
        pltpu.make_async_copy(piece, piece, sems.at[slot]).wait()

    rows_at_once = min(tm, BLOCK_ROWS)

    def written(b, _):
        first = pl.multiple_of(b * rows_at_once, rows_at_once)

        def piece(s, _):
            # sublane s of rows_at_once rows: ONE load with a sublane
            # stride (few operations to trace: the program's host pays for
            # each, every start)
            if not packed:
                out[pl.ds(first, rows_at_once),
                    pl.ds(pl.multiple_of(s * LANES, LANES), LANES)] = buf[
                        slot, pl.ds(first * pieces + s, rows_at_once,
                                    stride=pieces), :]
                return 0
            even, odd = (buf[slot, pl.ds((first + r) * pieces + s,
                                         rows_at_once // 2,
                                         stride=2 * pieces), :]
                         for r in (0, 1))
            for high in (False, True):
                out[pl.ds(first, rows_at_once),
                    pl.ds(pl.multiple_of((2 * s + high) * LANES, LANES),
                          LANES)] = _packed_block(even, odd, high, out.dtype)
            return 0
        jax.lax.fori_loop(0, pieces, piece, 0, unroll=True)
        return 0

    @pl.when(turn_i > 0)
    def _():
        count = fetched(turn_i - 1)

        @pl.when(count > 0)
        def _():
            # by the bits of the count: a few waits where a wait a row is
            # hundreds
            for bit in range(ROWS_A_TURN.bit_length() - 1, tm.bit_length()):
                @pl.when(((count >> bit) & 1) == 1)
                def _():
                    arrived(1 << bit)
            jax.lax.fori_loop(0, tm // rows_at_once, written, 0)


@functools.partial(jax.jit, static_argnames=("dtype", "rows", "interpret"))
def dispatch_rows(x3: jax.Array, src: jax.Array, held: jax.Array, *,
                  dtype, rows: int, interpret: bool = False) -> jax.Array:
    """The Pallas form at ``rows`` output rows a step (a power of two that
    divides the pairs): ``x3`` is ``lay_rows(x, dtype)``, ``src``
    ``i32[pairs]``, ``held`` ``i32[]``. ``dtype[pairs, H]`` whose rows under
    ``held`` are ``x.astype(dtype)[src]``; from the block after the one that
    holds row ``held - 1`` on, nothing is written. Jitted with a static
    block: the layers of a program share one trace and one lowering."""
    dtype = jnp.dtype(dtype)
    n, pieces, _ = x3.shape
    hidden = pieces * LANES * 4 // dtype.itemsize
    (pairs,) = src.shape
    steps = pairs // rows
    # a step's rows as one SMEM block: a 1-D int32 array is tiled by
    # SMEM_TILE words, so each step's are padded to whole tiles
    span = -(-rows // SMEM_TILE) * SMEM_TILE
    flat = jnp.pad(src.reshape(steps, rows),
                   ((0, 0), (0, span - rows))).reshape(-1)

    def last(held):
        # the last block with a row to fetch: the turns past it ask for its
        # blocks again, so nothing is moved for them
        return jnp.maximum((held[0] + rows - 1) // rows - 1, 0)

    # turn i starts block i's copies and writes block i - 1: one turn more
    # than blocks, the first without a block to write, the last without
    # copies
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, tm=rows, pieces=pieces),
        name="dispatch_rows",
        out_shape=jax.ShapeDtypeStruct((pairs, hidden), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps + 1,),
            in_specs=[
                pl.BlockSpec((span,), lambda i, held: (
                    jnp.minimum(i, last(held)),), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, hidden), lambda i, held: (
                jnp.minimum(jnp.maximum(i - 1, 0), last(held)), 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows * pieces, LANES), x3.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            # a turn writes what the turn before fetched: in order, on one
            # core
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(
                dispatch_vmem_bytes(rows, hidden, dtype.itemsize),
                VMEM_CEILING)),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=2 * pairs * hidden * dtype.itemsize + pairs * 4),
        interpret=interpret,
    )(held.reshape(1).astype(jnp.int32), flat, x3)


def rows_to_experts(x: jax.Array, src: jax.Array, held: jax.Array, dtype,
                    *, use_pallas: bool = False, interpret: bool = False
                    ) -> jax.Array:
    """``dtype[pairs, H]`` whose rows under ``held`` (``i32[]``) are
    ``x.astype(dtype)[src]`` for ``x`` ``[N, H]`` and ``src`` ``i32[pairs]``;
    the rows from ``held`` on hold anything. ``use_pallas`` asks for the
    kernel; a shape ``dispatch_supported`` declines runs the XLA form, and
    traces nothing of the kernel."""
    n, hidden = x.shape
    dtype = jnp.dtype(dtype)
    if use_pallas and dispatch_supported(n, src.shape[0], hidden,
                                         dtype.itemsize):
        return dispatch_rows(lay_rows(x, dtype=dtype, interpret=interpret),
                             src.astype(jnp.int32), held, dtype=dtype,
                             rows=dispatch_tile(src.shape[0]),
                             interpret=interpret)
    return dispatch_reference(x, src, dtype)
