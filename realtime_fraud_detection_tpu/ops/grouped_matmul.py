"""Grouped matmuls for routed experts: ``[rows sorted by group, K] x [G, K,
N]``, and an expert's first half — gated or not — in one call.

Row ``i`` of ``lhs`` belongs to the group whose span of
``cumsum(group_sizes)`` holds ``i``; each group's rows are multiplied by
that group's own ``[K, N]`` matrix. Groups are ragged (sizes are data: what
the router chose), may be empty, and one may hold every row. bf16 operands,
f32 accumulation — the precision the routed configurations state.

Three entry points, each with two forms:

- ``grouped_matmul``: one grouped matmul, f32 result (an expert's ``down``),
  each result row as its own ``N / 128`` lane tiles: ``f32[M, N / 128,
  128]``. In the tiled HBM layout a row of that array is ONE contiguous
  piece of ``4 N`` bytes, where a row of ``f32[M, N]`` is ``N / 128`` pieces
  of 512 B, 4 KB apart; the rows go home one at a time
  (``ops/combine.py``), and a row fetched from HBM is paid by the piece. The
  XLA form is ``jax.lax.ragged_dot`` and the reshape: what CPU hosts and
  the tests run, and what any shape the kernel does not take runs on the
  chip. The Pallas form is ``down_gmm``, below (a grid over row tiles, each
  tile multiplied by the matrix of the group that owns it, tiles that
  straddle two groups visited once per group with a row mask; the result
  block is ``(tm, tn / 128, 128)``, stored a lane tile at a time), at the
  tile ``gmm_tiling`` gives its shapes. Unlike XLA's own lowering of
  ``ragged_dot``, whose custom calls are named ``ragged-dot-none`` whatever
  scope they were traced under, it keeps the ``jax.named_scope`` path in
  its ``op_name``, so a device trace can attribute it.
- ``grouped_gated_matmul``: ``silu(rows @ gate) * (rows @ up)`` in the
  dtype the next matmul reads (an expert's ``gate``, ``up`` and SiLU ⊙). The
  XLA form is two ``ragged_dot`` calls and the product. The Pallas form is
  ``gated_gmm``, below: the same grid and row masks with TWO right-hand
  blocks a step — the group's ``[tk, tn]`` block of each of the two
  ``[G, K, N]`` operands, as the parameters hold them — and an epilogue that
  takes the SiLU and the product of the two f32 results in VMEM and writes
  them rounded once. The rows are read once, no f32 ``[rows, N]`` reaches
  HBM, and no pass stands between the matmuls.
- ``grouped_relu2_matmul``: ``relu(rows @ up)^2`` in the dtype the next
  matmul reads — the first half of an expert that has NO gate matrix
  (``models/nemotron_h.py``). The XLA form is one ``ragged_dot``, the
  ReLU and the square. The Pallas form is ``relu2_gmm``: the fused
  kernel's grid with ONE right-hand block a step and that epilogue — and
  its matrices taken ``[G, N, K]``, the block ``[tn, tk]``, the product
  contracting both last axes: the wrapper hands it ``swapaxes`` of the
  ``[G, K, N]`` parameter, which inside a program moves no byte (below).

**A side that is no whole number of lane tiles** (Nemotron's experts are
1,856 = 14 1/2 tiles wide) goes whole in one block: a Pallas block equal to
the array's whole side needs no lane multiple, and the tile rule takes K
and N whole wherever the budget holds them anyway. **Where that side is a
stored matrix's LAST** (N of the ungated call: ``bf16[128, 2688, 1856]``)
the TPU does not pad it to lane tiles: it lays the array out with its
lane-multiple side, K, innermost (``{1,2,0}``). A Mosaic call takes its
operands row-major, so a kernel that asks for that matrix ``[G, K, N]`` is
handed a transposing copy of it on every launch (1.28 GB read and written a
layer, 4 ms: 9% of Nemotron's period, PERF.md section 6, PR 51), and one that
asks for ``[G, N, K]`` is handed the bytes where they lie. A hidden size is
always whole lane tiles, so K innermost is copy-free whatever the expert's
width. The gated call's and down's matrices end in whole lane tiles (768 to
3,072) and are held row-major: they stay ``[G, K, N]``.

All three kernels are ``_grouped_call`` — megablox's schedule
(``make_group_metadata``), the grid, the block specs (the right-hand one
either way round), the budget the call names — round a body of their own.

``grouped_matmul_supported`` is the ONE predicate on shapes for all: the
traced guards below, the scorer's selector
(``FraudScorer.effective_use_pallas``) and the tests all ask it.
``gmm_tiling`` is the ONE tile rule of all, a function of the call's
``(m, k, n)``, group count and right-hand blocks a step alone;
``gated_tile_rows`` counts what the first call's grid then visits.
"""

from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# --- the tile rule's constants, from the two kernels alone on a v5e at the
# four routed encoders' shapes, both capacity rungs, even and skewed groups,
# and at six shapes of the smaller buckets (tools/grouped_alone.py ->
# tools/grouped_alone_pr47.json; PERF.md section 6, PR 47). One call of the
# fused gate / up kernel at the 3/4 rung, ms, (tm, K, N) with K whole:
#   OLMoE   196,608 rows / 64 groups,  2048 -> 1024: (256,.,1024) 8.42,
#           (128,.,1024) 8.59, (512,.,1024) 8.98, (512,.,512) 9.08 [PR 46's]
#   ZAYA1    24,576 / 16,  2048 -> 2048: (128,.,2048) 2.40 = (256,.,2048),
#           (256,.,1024) 2.45, (512,.,2048) 2.70, (512,.,512) 2.71 [PR 46's]
#   Laguna  122,880 / 64,  3072 -> 1024: (128,.,1024) 3.16 = (256,.,1024),
#           (512,.,1024) 4.09, (512, 1024, 1024) 4.82 [PR 46's: K in three]
#   JoyAI    98,304 / 256, 2048 -> 768:  (128,.,768) 5.16, (256,.,768) 5.19,
#           (256,.,384) 5.41, (512,.,768) 7.00, (512,.,256) 7.18 [PR 46's]
# and of down's (then megablox.gmm): OLMoE (256,.,2048) 4.58 against (256,.,1024)
# 4.80 and PR 46's (512,.,1024) 4.89; JoyAI (128, 768, 2048) 3.23 against
# (256, 768, 2048) 3.65 and PR 46's (512, 256, 2048) 4.87. Every tile with K
# in one block gave the same bits on every real row (452 timings).
# Down as ``down_gmm`` (PR 48, tools/grouped_alone_pr48.json), beside
# megablox.gmm at PR 47's tile, ms at the 3/4 rung: OLMoE 4.85 against 4.58,
# JoyAI 3.38 against 3.25, Laguna (128, 1024, 3072) 1.94 against (.., 1536)
# 1.96, ZAYA1 (128, 2048, 2048) 1.35 against (.., 1024) 1.37: the 3-D store
# costs 4-6% where the tile was the same, N whole pays for it where the
# budget now allows it; bit-equal on every real row.
#
# A row tile: 512 rows lost at every shape timed (a tile straddles more
# groups, and every group that touches a tile computes all of it), so 256 or
# 128. 256 where a group holds at least ROWS_PER_TILE tiles of it by the
# upper estimate m // groups, else 128: at 310-560 real rows a group (JoyAI,
# and Laguna, whose chip holds a quarter of the pairs m counts) 128 ties in
# the fused kernel and wins by 3-11% in down's; at 1,300-3,600 (ZAYA1,
# OLMoE) 256 ties or wins by up to 2% and 7%; in the smaller buckets (64 to
# 1,024 rows a group) 128 is within 1.2% of the best tile timed in the fused
# kernel and 2.1% in down's, at 0.51-0.89 of PR 46's 512.
ROW_TILES = (256, 128)
ROWS_PER_TILE = 8
# what either kernel's call may name: half the 128 MiB of a v5e core's VMEM
# (ZAYA1's whole 2048 x 2048 pair of blocks at 256 rows names 52 MB in the
# fused kernel; down's widest, Laguna's (128, 1024, 3072), 26 MB)
VMEM_CEILING = 64 << 20
SUBLANES = 8


# a side that is no whole number of lane tiles is whole sublane tiles of
# bfloat16 at least (two rows a sublane): it is a matrix's second-to-last
# side in one of an expert's two calls
PACKED_SUBLANES = 16


def grouped_matmul_supported(m: int, k: int, n: int) -> bool:
    """Whole lane tiles of rows (the kernel's row tiling must divide
    ``m``); K and N whole lane tiles too, or — a block equal to the array's
    whole side needs no lane multiple — a side of at least one lane tile
    and of whole packed sublane tiles that the tile rule takes WHOLE in one
    block, K and N both, inside the budget of either call (an expert's
    width is N of its first call and K of its second)."""
    if m < LANES or m % LANES:
        return False
    if k % LANES == 0 and n % LANES == 0:
        return True
    if min(k, n) < LANES or k % PACKED_SUBLANES or n % PACKED_SUBLANES:
        return False
    for gated, room in ((True, gated_vmem_bytes), (False, down_vmem_bytes)):
        tile = gmm_tiling(m, k, n, 1, gated=gated)
        if tile[1:] != (k, n) or room(*tile) > VMEM_CEILING:
            return False
    return True


def gated_vmem_bytes(tm: int, tk: int, tn: int, operand_bytes: int = 2,
                     out_bytes: int = 2, matrices: int = 2) -> int:
    """The VMEM the experts' first call names at ``(tm, tk, tn)`` with
    ``matrices`` right-hand blocks a step (``gated_gmm`` two, ``relu2_gmm``
    one): the row block, the matrix blocks and the result block
    double-buffered, three f32 ``[tm, tn]`` tiles a matrix (its product,
    its accumulator, the epilogue's temporaries), and 4 MB for what Mosaic
    keeps itself."""
    blocks = (tm * tk * operand_bytes + matrices * tk * tn * operand_bytes
              + tm * tn * out_bytes)
    return 2 * blocks + 3 * matrices * tm * tn * 4 + (4 << 20)


def down_vmem_bytes(tm: int, tk: int, tn: int, operand_bytes: int = 2) -> int:
    """The VMEM ``down_gmm`` names for a call at ``(tm, tk, tn)``: the row
    and matrix blocks and the f32 result block double-buffered, three f32
    ``[tm, tn]`` tiles (the product, the accumulator, the store's
    temporaries), and 4 MB for what Mosaic keeps itself."""
    return (2 * ((tm * tk + tk * tn) * operand_bytes + tm * tn * 4)
            + 3 * tm * tn * 4 + (4 << 20))


def _lane_divisors(size: int) -> List[int]:
    """Every whole number of lane tiles that divides ``size``, widest
    first; of a size that is no whole number of lane tiles, itself alone
    (a block may be the array's whole side)."""
    if size % LANES:
        return [size]
    lanes = size // LANES
    return [d * LANES for d in range(lanes, 0, -1) if lanes % d == 0]


def down_widths(n: int) -> List[int]:
    """The widths of N ``down_gmm``'s result block ``(tm, tn / 128, 128)``
    may take, widest first: all of N, or whole sublane tiles of lane tiles
    (1,024 columns) that divide it."""
    return [t for t in _lane_divisors(n)
            if t == n or t % (SUBLANES * LANES) == 0]


def gmm_tiling(m: int, k: int, n: int, groups: int, *, gated: bool = False,
               matrices: int = 2) -> Tuple[int, int, int]:
    """(tm, tk, tn) for a supported ``[m, k] x [groups, k, n]`` call, of the
    experts' first call (``gated``: the fused kernel, with ``matrices``
    right-hand blocks a step — gate and up, or up alone where the expert
    has no gate) or of down's ``down_gmm``: a function of the shapes and of
    nothing else, in this order.

    1. K whole in one block wherever the call's budget holds it beside the
       narrowest row and result tiles: no accumulator pass, and any two
       tile choices give the same bits on the real rows. (Else the widest
       divisor of K in lane tiles that does fit.)
    2. N as wide as the budget then allows: any whole number of lane tiles
       that divides N in the fused kernel, ``down_widths`` in down's (whose
       result block is 3-D). Each further block of N is another pass of
       the rows through HBM.
    3. The row tile: the largest of ``ROW_TILES`` that divides ``m``, still
       fits beside (1) and (2), and of which a group holds at least
       ``ROWS_PER_TILE`` — by ``m // groups``, which is an UPPER estimate
       of a group's rows: padding and the pairs of experts held elsewhere
       sort last and belong to no group (Laguna's layer holds a quarter of
       the pairs ``m`` counts).

    The budget is what the call names, ``gated_vmem_bytes`` or
    ``down_vmem_bytes``, under ``VMEM_CEILING``; both count bfloat16
    operands, as deployed."""
    room = (functools.partial(gated_vmem_bytes, matrices=matrices)
            if gated else down_vmem_bytes)
    widths = (_lane_divisors(n) if gated else down_widths(n)) or [LANES]

    def widest(sides, tile):
        # (the narrowest where nothing fits: the compiler then says so)
        return next((t for t in sides if room(*tile(t)) <= VMEM_CEILING),
                    sides[-1] if sides else LANES)

    tk = widest(_lane_divisors(k), lambda t: (LANES, t, widths[-1]))
    tn = widest(widths, lambda t: (LANES, tk, t))
    rows = [t for t in ROW_TILES
            if m % t == 0 and t * ROWS_PER_TILE <= m // groups] + [LANES]
    return widest(rows, lambda t: (t, tk, tn)), tk, tn


def gated_tile_rows(group_sizes: jax.Array, m: int, k: int, n: int, *,
                    use_pallas: bool, matrices: int = 2) -> jax.Array:
    """``i32[]``: the rows the grid of the experts' first call's kernel
    (``gated_gmm``, or ``relu2_gmm`` with ``matrices`` 1) visits for
    ``group_sizes`` (``i32[G]``) of an ``[m, k] x [G, k, n]``
    call — over the non-empty groups, the row tiles a group's span of rows
    touches, times the row tile (``megablox``'s schedule: a tile that
    straddles groups is visited once for each). The real rows over it is
    how full the visited tiles were. 0 where the call runs the XLA form,
    which visits no tile. A few integer operations on ``i32[G]``."""
    if not (use_pallas and grouped_matmul_supported(m, k, n)):
        return jnp.zeros((), jnp.int32)
    tm = gmm_tiling(m, k, n, group_sizes.shape[0], gated=True,
                    matrices=matrices)[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    tiles = (ends + tm - 1) // tm - (ends - sizes) // tm
    return jnp.sum(jnp.where(sizes > 0, tiles, 0)) * tm


def grouped_matmul_reference(lhs: jax.Array, rhs: jax.Array,
                             group_sizes: jax.Array) -> jax.Array:
    """The XLA form: ``jax.lax.ragged_dot`` with f32 accumulation."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, use_pallas: bool = False, interpret: bool = False
                   ) -> jax.Array:
    """``f32[M, N / 128, 128]``: rows of ``lhs`` (``[M, K]``, sorted by
    group) times their group's matrix of ``rhs`` (``[G, K, N]``), each
    result row as ``N / 128`` lane tiles of its own — in the tiled HBM
    layout ONE contiguous piece, which a row of ``f32[M, N]`` is not (it is
    ``N / 128`` pieces of 512 B, 4 KB apart): the form
    ``ops.combine.weighted_combine`` fetches single rows in. ``group_sizes``
    ``i32[G]`` sums to ``M``. ``use_pallas`` asks for the kernel
    (``down_gmm``); a shape it does not take runs the XLA form,
    ``ragged_dot`` and the reshape."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    # (a result row is lane tiles: K may be a side of no lane multiple, N
    # may not)
    if use_pallas and n % LANES == 0 and grouped_matmul_supported(m, k, n):
        return down_gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                        tiling=gmm_tiling(m, k, n, rhs.shape[0]),
                        interpret=interpret)
    # (a width that is no whole number of lane tiles stays one piece)
    return grouped_matmul_reference(lhs, rhs, group_sizes).reshape(
        (m, n // LANES, LANES) if n % LANES == 0 else (m, 1, n))


def _grouped_call(body, name: str, lhs: jax.Array, matrices, group_sizes,
                  tiling: Tuple[int, int, int], *, out_shape, out_block,
                  out_index, vmem: int, flops_per_mkn: int,
                  transcendentals: int, interpret: bool,
                  transposed: bool = False) -> jax.Array:
    """The scaffold of the three kernels: ``body`` run over megablox's grid
    for ``lhs`` ``[M, K]`` against the group's ``[tk, tn]`` block of each of
    ``matrices`` (``[G, K, N]`` each; ``transposed``: ``[G, N, K]`` each,
    the group's block ``[tn, tk]``). The grid is ``(N tiles, visits, K
    tiles)``; megablox's own schedule (``make_group_metadata``) says which
    row tile and which group each visit holds — a tile that straddles
    groups is visited once for each — and how many visits hold work. Rows
    past the last group are never visited. ``body`` gets the three
    prefetched schedules, the blocks, the result block (``out_block`` at
    ``out_index(row tile, N tile)``) and, where K takes several steps, one
    f32 ``[tm, tn]`` accumulator a matrix."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    m, k = lhs.shape
    groups = matrices[0].shape[0]
    n = matrices[0].shape[1 if transposed else 2]
    tm, tk, tn = tiling
    tiles_k, tiles_n = k // tk, n // tn
    metadata, visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=groups,
        visit_empty_groups=False)

    def rows_at(n_i, visit, k_i, offsets, group_ids, row_tiles):
        return row_tiles[visit], k_i

    def matrix_at(n_i, visit, k_i, offsets, group_ids, row_tiles):
        return ((group_ids[visit], n_i, k_i) if transposed
                else (group_ids[visit], k_i, n_i))

    def out_at(n_i, visit, k_i, offsets, group_ids, row_tiles):
        return out_index(row_tiles[visit], n_i)

    return pl.pallas_call(
        functools.partial(body, tm=tm, tn=tn, tiles_k=tiles_k),
        name=name,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), rows_at)]
            + [pl.BlockSpec((None, tn, tk) if transposed else (None, tk, tn),
                            matrix_at)] * len(matrices),
            out_specs=pl.BlockSpec(out_block, out_at),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            * len(matrices) if tiles_k > 1 else [])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=flops_per_mkn * m * k * n, transcendentals=transcendentals,
            bytes_accessed=(tiles_n * m * k * lhs.dtype.itemsize
                            + len(matrices) * metadata[1].size * k * n
                            * matrices[0].dtype.itemsize
                            + math.prod(out_shape.shape)
                            * out_shape.dtype.itemsize)),
        interpret=interpret,
    )(*metadata, lhs, *matrices)


def _own_rows(visit, offsets, group_ids, row_tiles, tm: int, shape):
    """``bool[shape]``: which rows (axis 0) of the row tile that ``visit``
    (the grid's middle index, read outside any ``pl.when``) holds belong to
    the visiting group. The other rows of the tile keep what an earlier
    visit wrote."""
    group = group_ids[visit]
    row = row_tiles[visit] * tm + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _over_k(products, accs, tiles_k: int, store):
    """``store(*sums)`` of this step's ``products`` summed over the K steps
    of a visit: at once where K is one block, else through the f32
    accumulators on the last step."""
    k_i = pl.program_id(2)
    if tiles_k == 1:
        store(*products)
        return

    @pl.when(k_i == 0)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    for acc, product in zip(accs, products):
        acc[...] += product

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(*(acc[...] for acc in accs))


def _gated_kernel(offsets, group_ids, row_tiles, lhs, gate_w, up_w, out,
                  *accs, tm: int, tn: int, tiles_k: int):
    """One visit of a row tile by one group, one ``[tk, tn]`` block of each
    matrix: both products, summed over the K steps, and on the last the
    SiLU ⊙ of the group's own rows of the tile."""
    visit = pl.program_id(1)

    def store(gate, up):
        mine = _own_rows(visit, offsets, group_ids, row_tiles, tm, (tm, tn))
        out[...] = jnp.where(mine, jax.nn.silu(gate) * up,
                             out[...].astype(jnp.float32)).astype(out.dtype)

    x = lhs[...]
    _over_k((jnp.dot(x, gate_w[...], preferred_element_type=jnp.float32),
             jnp.dot(x, up_w[...], preferred_element_type=jnp.float32)),
            accs, tiles_k, store)


def _relu2_kernel(offsets, group_ids, row_tiles, lhs, up_w, out, *accs,
                  tm: int, tn: int, tiles_k: int):
    """``_gated_kernel`` with one matrix, whose block is ``[tn, tk]``: the
    product contracting both last axes, summed over the K steps, and on the
    last the squared ReLU of the group's own rows of the tile."""
    visit = pl.program_id(1)

    def store(up):
        mine = _own_rows(visit, offsets, group_ids, row_tiles, tm, (tm, tn))
        out[...] = jnp.where(mine, jnp.square(jnp.maximum(up, 0.0)),
                             out[...].astype(jnp.float32)).astype(out.dtype)

    _over_k((jax.lax.dot_general(lhs[...], up_w[...],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32),),
            accs, tiles_k, store)


def _down_kernel(offsets, group_ids, row_tiles, lhs, w, out, *accs,
                 tm: int, tn: int, tiles_k: int):
    """One visit of a row tile by one group: the product summed over the K
    steps, and on the last the group's own rows of the tile stored a lane
    tile at a time into the ``[tm, tn / 128, 128]`` result block (row
    ``r``'s lanes ``128 c ...`` become ``out[r, c]``: a store with a
    sublane stride)."""
    visit = pl.program_id(1)

    def store(result):
        mine = _own_rows(visit, offsets, group_ids, row_tiles, tm,
                         (tm, LANES))
        for c in range(tn // LANES):
            out[:, c, :] = jnp.where(
                mine, result[:, c * LANES:(c + 1) * LANES], out[:, c, :])

    _over_k((jnp.dot(lhs[...], w[...],
                     preferred_element_type=jnp.float32),),
            accs, tiles_k, store)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tiling",
                                             "interpret"))
def gated_gmm(lhs: jax.Array, gate_w: jax.Array, up_w: jax.Array,
              group_sizes: jax.Array, *, out_dtype,
              tiling: Tuple[int, int, int], interpret: bool = False
              ) -> jax.Array:
    """The Pallas form of ``grouped_gated_matmul`` at ``tiling`` (tm, tk,
    tn), whole tiles on every side. Jitted with static tiles: the layers of
    a program share one trace and one lowering. Rows past the last group are
    never written."""
    m, k = lhs.shape
    n = gate_w.shape[-1]
    tm, tk, tn = tiling
    out_dtype = jnp.dtype(out_dtype)
    return _grouped_call(
        _gated_kernel, "gated_gmm", lhs, (gate_w, up_w), group_sizes, tiling,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        out_block=(tm, tn), out_index=lambda row_tile, n_i: (row_tile, n_i),
        # over the 16 MB a call may use on the v5e unasked at every tile the
        # rule picks for the encoders' shapes, so the call names its budget
        vmem=gated_vmem_bytes(tm, tk, tn, lhs.dtype.itemsize,
                              out_dtype.itemsize),
        flops_per_mkn=4, transcendentals=m * n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tiling",
                                             "interpret"))
def relu2_gmm(lhs: jax.Array, up_w: jax.Array, group_sizes: jax.Array, *,
              out_dtype, tiling: Tuple[int, int, int],
              interpret: bool = False) -> jax.Array:
    """The Pallas form of ``grouped_relu2_matmul`` at ``tiling`` (tm, tk,
    tn): ``gated_gmm``'s call with one matrix, taken ``[G, N, K]`` — K
    innermost, which is how the TPU holds a ``[G, K, N]`` parameter whose N
    is no whole number of lane tiles (the module docstring). Rows past the
    last group are never written."""
    m, k = lhs.shape
    n = up_w.shape[1]
    tm, tk, tn = tiling
    out_dtype = jnp.dtype(out_dtype)
    return _grouped_call(
        _relu2_kernel, "relu2_gmm", lhs, (up_w,), group_sizes, tiling,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        out_block=(tm, tn), out_index=lambda row_tile, n_i: (row_tile, n_i),
        vmem=gated_vmem_bytes(tm, tk, tn, lhs.dtype.itemsize,
                              out_dtype.itemsize, matrices=1),
        flops_per_mkn=2, transcendentals=0, interpret=interpret,
        transposed=True)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def down_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
             tiling: Tuple[int, int, int], interpret: bool = False
             ) -> jax.Array:
    """The Pallas form of ``grouped_matmul`` at ``tiling`` (tm, tk, tn):
    ``f32[M, N / 128, 128]``, the result block ``(tm, tn / 128, 128)`` — so
    ``tn`` is all of N or whole sublane tiles of lane tiles
    (``down_widths``). Jitted with static tiles, as ``gated_gmm``. Rows
    past the last group are never written."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    tm, tk, tn = tiling
    return _grouped_call(
        _down_kernel, "down_gmm", lhs, (rhs,), group_sizes, tiling,
        out_shape=jax.ShapeDtypeStruct((m, n // LANES, LANES), jnp.float32),
        out_block=(tm, tn // LANES, LANES),
        out_index=lambda row_tile, n_i: (row_tile, n_i, 0),
        vmem=down_vmem_bytes(tm, tk, tn, lhs.dtype.itemsize),
        flops_per_mkn=2, transcendentals=0, interpret=interpret)


def grouped_gated_matmul(rows: jax.Array, gate_w: jax.Array,
                         up_w: jax.Array, group_sizes: jax.Array, *,
                         out_dtype, use_pallas: bool = False,
                         interpret: bool = False) -> jax.Array:
    """``out_dtype[M, N]``: ``silu(rows @ gate) * (rows @ up)`` with each row
    of ``rows`` (``[M, K]``, sorted by group) against its group's matrices
    of ``gate_w`` and ``up_w`` (``[G, K, N]`` each); f32 accumulation, SiLU
    and product in f32, one rounding to ``out_dtype``. ``use_pallas`` asks
    for the one fused kernel; a shape it does not take runs the XLA form,
    two ``ragged_dot`` calls and the product."""
    m, k = rows.shape
    n = gate_w.shape[-1]
    if use_pallas and grouped_matmul_supported(m, k, n):
        tiling = gmm_tiling(m, k, n, gate_w.shape[0], gated=True)
        return gated_gmm(rows, gate_w, up_w, group_sizes.astype(jnp.int32),
                         out_dtype=out_dtype, tiling=tiling,
                         interpret=interpret)
    gate = grouped_matmul_reference(rows, gate_w, group_sizes)
    up = grouped_matmul_reference(rows, up_w, group_sizes)
    return (jax.nn.silu(gate) * up).astype(out_dtype)


def grouped_relu2_matmul(rows: jax.Array, up_w: jax.Array,
                         group_sizes: jax.Array, *, out_dtype,
                         use_pallas: bool = False, interpret: bool = False
                         ) -> jax.Array:
    """``out_dtype[M, N]``: ``relu(rows @ up)^2`` with each row of ``rows``
    (``[M, K]``, sorted by group) against its group's matrix of ``up_w``
    (``[G, K, N]``, as the tree stores it): the first half of an expert
    with no gate; f32 accumulation, ReLU and square in f32, one rounding to
    ``out_dtype``. ``use_pallas`` asks for the kernel, which takes the
    matrices ``[G, N, K]``; a shape it does not take runs the XLA form,
    ``ragged_dot``, the ReLU and the square."""
    m, k = rows.shape
    n = up_w.shape[-1]
    if use_pallas and grouped_matmul_supported(m, k, n):
        tiling = gmm_tiling(m, k, n, up_w.shape[0], gated=True, matrices=1)
        # (inside a program the swap moves no byte of a matrix that lies
        # with K innermost, as one of a ragged N does: the module docstring)
        return relu2_gmm(rows, jnp.swapaxes(up_w, 1, 2),
                         group_sizes.astype(jnp.int32), out_dtype=out_dtype,
                         tiling=tiling, interpret=interpret)
    up = grouped_matmul_reference(rows, up_w, group_sizes)
    return jnp.square(jnp.maximum(up, 0.0)).astype(out_dtype)
