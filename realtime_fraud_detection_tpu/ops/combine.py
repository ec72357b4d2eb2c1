"""The routed experts' way home: each token's weighted sum of its experts'
result rows, fetched one row at a time from where the grouped matmul left
them.

``ops.grouped_matmul.grouped_matmul`` leaves an expert's float32 result rows
in expert order as ``f32[M, H / 128, 128]``: in the tiled HBM layout a row
of that array is ONE contiguous piece of ``4 H`` bytes, where a row of
``f32[M, H]`` is ``H / 128`` pieces of 512 B, 4 KB apart — and a gather of
such rows pays for its pieces, not its bytes (PERF.md section 6, PR 48).

``weighted_combine(out3, home, weights, valid)`` is

    y[n] = sum_j  valid[n, j] ? weights[n, j] * out3[home[n, j]] : 0

in float32, ``j`` ascending. An invalid pair's row is never read: the
grouped kernels leave the rows of no group unwritten, and whatever they hold
stops here. Two forms:

- The XLA form: a gather on the 3-D array, a select and the sum. What CPU
  hosts and the tests run, and any shape the kernel does not take.
- The Pallas form, ``combine_rows``: a grid over blocks of ``TM`` tokens.
  ``out3`` stays in HBM; a step's ``home`` arrives in SMEM and every VALID
  pair starts one copy of its row into ``buf[j, n]`` (an invalid pair
  starts nothing); the copies of block ``i`` are started before those of
  block ``i - 1`` are waited for (two slots, a DMA semaphore each), and a
  block's rows' bytes are waited for in a dozen pieces, not row by row; then the
  ``top_k`` planes are multiplied by their weights and summed in VMEM
  under a select (an invalid pair's place holds what an earlier step left
  there), read with a sublane stride so that the result is written as a
  dense ``[TM, H]`` block. No ``[pairs, H]`` temporary exists in HBM. What
  the kernel pays for is the scalar loop that walks the pairs (~17 ns a
  pair on the v5e, valid or not), not the bytes.

``combine_supported`` is the ONE predicate on shapes: the traced guard
below, the scorer's selector and the tests all ask it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    LANES,
    VMEM_CEILING,
)

# what a step's two slots of fetched rows may take of VMEM (2 x top_k x TM
# rows of 4 H bytes): from the kernel alone on a v5e at the four routed
# encoders' shapes (tools/grouped_alone.py --combine ->
# tools/grouped_alone_pr48.json; PERF.md section 6, PR 48)
ROWS_BUDGET = 16 << 20
TOKEN_TILES = (256, 128, 64, 32)
# the tokens summed at a time: their top_k weights stay in registers
SUM_ROWS = 16
SMEM_TILE = 1024
PAIRS_A_TURN = 8


def combine_tokens(n: int, top_k: int, hidden: int) -> int:
    """``TM``, the tokens a step of the kernel holds: the largest of
    ``TOKEN_TILES`` that divides ``n`` and whose two slots of rows fit
    ``ROWS_BUDGET`` (0: none does)."""
    return next((t for t in TOKEN_TILES if n % t == 0
                 and 2 * top_k * t * hidden * 4 <= ROWS_BUDGET), 0)


def combine_supported(n: int, top_k: int, hidden: int) -> bool:
    """Rows of whole lane tiles, and a block of tokens that divides ``n``
    inside the budget. (At the encoders' widths, multiples of 1,024, a row
    of ``[M, H / 128, 128]`` is whole ``(8, 128)`` tiles.)"""
    return (hidden % LANES == 0 and top_k <= LANES
            and combine_tokens(n, top_k, hidden) > 0)


def combine_vmem_bytes(tm: int, top_k: int, hidden: int) -> int:
    """The VMEM ``combine_rows`` names: the two slots of rows, the result
    block double-buffered, and 4 MB for the small blocks and what Mosaic
    keeps itself."""
    return (2 * top_k + 2) * tm * hidden * 4 + (4 << 20)


def weighted_combine_reference(out3: jax.Array, home: jax.Array,
                               weights: jax.Array, valid: jax.Array
                               ) -> jax.Array:
    """The XLA form. A token's experts outermost, ``[top_k, N, C, 128]``:
    splitting the LEADING axis of the gathered rows moves nothing, where
    ``[N, top_k, ...]`` with ``top_k`` no multiple of a sublane tile is a
    copy of every row (PERF.md, PR 33). A select, not a zero weight: 0 x
    whatever an unwritten row holds is not 0."""
    n, top_k = home.shape
    back = out3[home.T.reshape(-1)].reshape(top_k, n, -1)
    back = jnp.where(valid.T[:, :, None], back * weights.T[:, :, None], 0.0)
    return jnp.sum(back, axis=0)


def _combine_kernel(counts, home, weights, held, out3, y, buf, sems, *,
                    tm: int, top_k: int, chunks: int):
    """Turn ``i`` of ``steps + 1``: the copies of block ``i``'s rows are
    started, then block ``i - 1`` — whose copies the turn before started —
    is waited for and summed. ``counts`` ``i32[steps]`` in SMEM: the valid
    pairs of each block; ``home``: block ``i``'s rows, ``i32[tm * top_k]``
    in SMEM, -1 for an invalid pair; ``weights`` ``f32[tm, 128]`` and
    ``held`` ``i32[tm, 128]`` (block ``i - 1``'s weights, and its rows once
    more, for the vector unit: a token a sublane, its ``top_k`` in the first
    lanes) and ``y`` its result; ``buf`` ``f32[2, top_k, tm * chunks,
    128]``: slot, plane, a token's ``chunks`` lane tiles one under the
    other."""
    turn_i, blocks = pl.program_id(0), pl.num_programs(0) - 1
    into, slot = turn_i % 2, (turn_i + 1) % 2
    # the tokens a turn of the walk takes: about PAIRS_A_TURN pairs, so that
    # one expert a token does not pay a turn's overhead (~20 ns on the v5e,
    # more than its pair) for every pair
    walk = max(1, PAIRS_A_TURN // top_k)

    @pl.when(turn_i < blocks)
    def _():
        def turn(g, _):
            for u in range(walk):
                n = g * walk + u
                pair, place = n * top_k, pl.ds(n * chunks, chunks)
                for j in range(top_k):
                    row = home[pair + j]

                    @pl.when(row >= 0)
                    def _():
                        pltpu.make_async_copy(
                            out3.at[row], buf.at[into, j, place, :],
                            sems.at[into]).start()
            return 0
        jax.lax.fori_loop(0, tm // walk, turn, 0)

    def arrived(rows: int):
        # a wait is for a number of bytes on a semaphore, whatever copies
        # brought them: ``rows`` rows' worth at once
        piece = buf.at[slot, 0, pl.ds(0, rows * chunks), :]
        pltpu.make_async_copy(piece, piece, sems.at[slot]).wait()

    def summed(b, _):
        first = pl.multiple_of(b * SUM_ROWS, SUM_ROWS)
        w = weights[pl.ds(first, SUM_ROWS), :]
        rows = held[pl.ds(first, SUM_ROWS), :]

        def planes(block):
            # [SUM_ROWS, 128] -> [top_k, SUM_ROWS, 128]: a pair's value
            # along the lanes of its plane
            return jnp.stack([
                jnp.broadcast_to(block[:, j:j + 1], (SUM_ROWS, LANES))
                for j in range(top_k)])
        # a select, not a zero weight: an invalid pair's place holds what an
        # earlier turn left there
        w, valid = planes(w), planes(rows) >= 0

        def chunk(c, _):
            # chunk c of SUM_ROWS tokens in every plane: ONE load with a
            # sublane stride (few operations to trace: the program's host
            # pays for each, every start)
            got = buf[slot, :, pl.ds(first * chunks + c, SUM_ROWS,
                                     stride=chunks), :]
            y[pl.ds(first, SUM_ROWS),
              pl.ds(pl.multiple_of(c * LANES, LANES), LANES)] = jnp.sum(
                  jnp.where(valid, w * got, 0.0), axis=0)
            return 0
        jax.lax.fori_loop(0, chunks, chunk, 0, unroll=True)
        return 0

    @pl.when(turn_i > 0)
    def _():
        # the block's ``counts`` rows, in whole planes' worth and then by
        # the bits of the rest: a dozen waits where a wait a row is a
        # thousand (a third of the kernel's time on the v5e)
        count, plane = counts[turn_i - 1], tm.bit_length() - 1

        def planes_(_, carry):
            arrived(tm)
            return carry
        jax.lax.fori_loop(0, count >> plane, planes_, 0)
        for bit in range(plane):
            @pl.when(((count >> bit) & 1) == 1)
            def _():
                arrived(1 << bit)
        jax.lax.fori_loop(0, tm // SUM_ROWS, summed, 0)


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def combine_rows(out3: jax.Array, home: jax.Array, weights: jax.Array, *,
                 tokens: int, interpret: bool = False) -> jax.Array:
    """The Pallas form at ``tokens`` a step (a power of two): ``home``
    ``i32[N, top_k]`` with -1 for a pair that is not fetched. Jitted with a
    static block: the layers of a program share one trace and one
    lowering."""
    n, top_k = home.shape
    _, chunks, _ = out3.shape
    hidden = chunks * LANES
    steps = n // tokens
    by_step = home.reshape(steps, tokens * top_k)
    # a step's pairs as one SMEM block: a 1-D int32 array is tiled by
    # SMEM_TILE words, so each step's are padded to whole tiles
    span = -(-tokens * top_k // SMEM_TILE) * SMEM_TILE
    flat = jnp.pad(by_step, ((0, 0), (0, span - tokens * top_k)),
                   constant_values=-1).reshape(-1)
    # turn i starts block i's copies and sums block i - 1: one turn more
    # than blocks, the first without a sum, the last without copies
    pairs = pl.BlockSpec((span,), lambda i: (jnp.minimum(i, steps - 1),),
                         memory_space=pltpu.SMEM)

    def a_token_a_sublane(x, fill):
        """``x`` ``[N, top_k]`` as ``[N, 128]``, its ``top_k`` in the first
        lanes: what a ``[N, top_k]`` operand of the call occupies anyway.
        Built from ``x``'s COLUMNS, each a 1-D array: handed over as ``[N,
        top_k]`` (or padded from it), the call's row-major tiled layout
        spreads up through the router's own arithmetic, and at one expert a
        token every operation of it then works on one lane of 128 (+1.9 ms a
        layer in ZAYA1's cells: PERF.md, PR 48)."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 1)
        out = jax.lax.full((n, LANES), fill, x.dtype)
        for j in range(top_k):
            # (lax, not jnp: every operation here is traced again by every
            # program a process builds, before its first batch)
            column = jax.lax.reshape(
                jax.lax.slice_in_dim(x, j, j + 1, axis=1), (n,))
            out = jax.lax.select(
                lane == j, jax.lax.broadcast_in_dim(column, (n, LANES), (0,)),
                out)
        return out

    def behind(i):
        return jnp.maximum(i - 1, 0), 0

    block = pl.BlockSpec((tokens, LANES), behind)
    return pl.pallas_call(
        functools.partial(_combine_kernel, tm=tokens, top_k=top_k,
                          chunks=chunks),
        name="weighted_combine",
        out_shape=jax.ShapeDtypeStruct((n, hidden), jnp.float32),
        grid=(steps + 1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), pairs, block, block,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, hidden), behind),
        scratch_shapes=[
            pltpu.VMEM((2, top_k, tokens * chunks, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            # a turn sums what the turn before fetched: in order, on one core
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(
                combine_vmem_bytes(tokens, top_k, hidden), VMEM_CEILING)),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * top_k * hidden, transcendentals=0,
            bytes_accessed=(n * top_k + n) * hidden * 4 + n * top_k * 12),
        interpret=interpret,
    )(jnp.sum(by_step >= 0, axis=1, dtype=jnp.int32), flat,
      a_token_a_sublane(weights, 0.0), a_token_a_sublane(home, -1), out3)


def weighted_combine(out3: jax.Array, home: jax.Array, weights: jax.Array,
                     valid: jax.Array, *, use_pallas: bool = False,
                     interpret: bool = False) -> jax.Array:
    """``f32[N, C * 128]``: ``sum_j valid[n, j] ? weights[n, j] *
    out3[home[n, j]] : 0`` for ``out3`` ``f32[M, C, 128]``, ``home``
    ``i32[N, top_k]``, ``weights`` ``f32[N, top_k]``, ``valid`` ``bool[N,
    top_k]``. ``use_pallas`` asks for the kernel; a shape it does not take
    runs the XLA form."""
    n, top_k = home.shape
    hidden = out3.shape[1] * out3.shape[2]
    if use_pallas and combine_supported(n, top_k, hidden):
        return combine_rows(
            out3, jnp.where(valid, home, -1).astype(jnp.int32),
            weights.astype(jnp.float32),
            tokens=combine_tokens(n, top_k, hidden), interpret=interpret)
    return weighted_combine_reference(out3, home, weights, valid)
