"""Grouped matmuls for routed experts: ``[rows sorted by group, K] x [G, K,
N]``, and an expert's gated first half in one call.

Row ``i`` of ``lhs`` belongs to the group whose span of
``cumsum(group_sizes)`` holds ``i``; each group's rows are multiplied by
that group's own ``[K, N]`` matrix. Groups are ragged (sizes are data: what
the router chose), may be empty, and one may hold every row. bf16 operands,
f32 accumulation — the precision the routed configurations state.

Two entry points, each with two forms:

- ``grouped_matmul``: one grouped matmul, f32 result (an expert's ``down``).
  The XLA form is ``jax.lax.ragged_dot``: what CPU hosts and the tests run,
  and what any shape the kernel does not take runs on the chip. The Pallas
  form is ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (a grid over row
  tiles, each tile multiplied by the matrix of the group that owns it, tiles
  that straddle two groups visited once per group with a row mask), at the
  tile ``gmm_tiling`` gives its shapes. On the v5e it is a quarter to a third
  faster than XLA's own lowering of ``ragged_dot`` (itself a grouped kernel,
  not per-group dense work), and — unlike that lowering, whose custom calls
  are named ``ragged-dot-none`` whatever scope they were traced under — it
  keeps the ``jax.named_scope`` path in its ``op_name``, so a device trace
  can attribute it.
- ``grouped_gated_matmul``: ``silu(rows @ gate) * (rows @ up)`` in the
  dtype the next matmul reads (an expert's ``gate``, ``up`` and SiLU ⊙). The
  XLA form is two ``ragged_dot`` calls and the product. The Pallas form is
  ``gated_gmm``, below: megablox's grid and row masks with TWO right-hand
  blocks a step — the group's ``[tk, tn]`` block of each of the two
  ``[G, K, N]`` operands, as the parameters hold them — and an epilogue that
  takes the SiLU and the product of the two f32 results in VMEM and writes
  them rounded once. The rows are read once, no f32 ``[rows, N]`` reaches
  HBM, and no pass stands between the matmuls.

``grouped_matmul_supported`` is the ONE predicate on shapes for both: the
traced guards below, the scorer's selector
(``FraudScorer.effective_use_pallas``) and the tests all ask it.
``gmm_tiling`` is the ONE tile rule of both, a function of the call's
``(m, k, n)`` and group count alone; ``gated_tile_rows`` counts what the
fused kernel's grid then visits.
"""

from __future__ import annotations

import functools
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
# and of down's megablox.gmm: OLMoE (256,.,2048) 4.58 against (256,.,1024)
# 4.80 and PR 46's (512,.,1024) 4.89; JoyAI (128, 768, 2048) 3.23 against
# (256, 768, 2048) 3.65 and PR 46's (512, 256, 2048) 4.87. Every tile with K
# in one block gave the same bits on every real row (452 timings).
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
# what the fused kernel's call may name: half the 128 MiB of a v5e core's
# VMEM (ZAYA1's whole 2048 x 2048 pair of blocks at 256 rows names 52 MB)
GATED_VMEM_CEILING = 64 << 20
# down's call is megablox's and gets the 16 MB a call may use unasked; 3 MB
# of it are left to Mosaic, which reported up to 2.3 MB of its own past
# ``gmm_vmem_bytes`` where it refused a tile
GMM_VMEM_BUDGET = 13 << 20


def grouped_matmul_supported(m: int, k: int, n: int) -> bool:
    """Whole lane tiles on every side (the kernel's row tiling must divide
    ``m``)."""
    return (m >= LANES and m % LANES == 0 and k % LANES == 0
            and n % LANES == 0)


def gated_vmem_bytes(tm: int, tk: int, tn: int, operand_bytes: int = 2,
                     out_bytes: int = 2) -> int:
    """The VMEM ``gated_gmm`` names for a call at ``(tm, tk, tn)``: the row
    block, the two matrix blocks and the result block double-buffered, six
    f32 ``[tm, tn]`` tiles (the two products, the two accumulators, the
    epilogue's temporaries), and 4 MB for what Mosaic keeps itself."""
    blocks = (tm * tk * operand_bytes + 2 * tk * tn * operand_bytes
              + tm * tn * out_bytes)
    return 2 * blocks + 6 * tm * tn * 4 + (4 << 20)


def gmm_vmem_bytes(tm: int, tk: int, tn: int, operand_bytes: int = 2) -> int:
    """What ``megablox.gmm`` holds in VMEM at ``(tm, tk, tn)``, f32 result:
    the row and matrix blocks double-buffered, the result block and the
    accumulator (what Mosaic reports, to the byte, where it refuses a tile
    for the 16 MB a call gets unasked: ``tools/grouped_alone.py --aot``)."""
    return (2 * (tm * tk + tk * tn) * operand_bytes + 2 * tm * tn * 4)


def _lane_divisors(size: int) -> List[int]:
    """Every whole number of lane tiles that divides ``size``, widest
    first."""
    lanes = size // LANES
    return [d * LANES for d in range(lanes, 0, -1) if lanes % d == 0]


def gmm_tiling(m: int, k: int, n: int, groups: int, *, gated: bool = False
               ) -> Tuple[int, int, int]:
    """(tm, tk, tn) for a supported ``[m, k] x [groups, k, n]`` call, of the
    fused gate / up kernel (``gated``) or of down's ``megablox.gmm``: a
    function of the shapes and of nothing else, in this order.

    1. K whole in one block wherever the call's budget holds it beside the
       narrowest row and result tiles: no accumulator pass, and any two
       tile choices give the same bits on the real rows. (Else the widest
       divisor of K in lane tiles that does fit.)
    2. N as wide as the budget then allows, any whole number of lane tiles
       that divides N (768 whole, 1536 of 3072): each further block of N is
       another pass of the rows through HBM.
    3. The row tile: the largest of ``ROW_TILES`` that divides ``m``, still
       fits beside (1) and (2), and of which a group holds at least
       ``ROWS_PER_TILE`` — by ``m // groups``, which is an UPPER estimate
       of a group's rows: padding and the pairs of experts held elsewhere
       sort last and belong to no group (Laguna's layer holds a quarter of
       the pairs ``m`` counts).

    The budget is ``gated_vmem_bytes`` under ``GATED_VMEM_CEILING`` for the
    fused kernel, which names it, and ``gmm_vmem_bytes`` under
    ``GMM_VMEM_BUDGET`` for down's; both count bfloat16 operands, as
    deployed."""
    room, ceiling = ((gated_vmem_bytes, GATED_VMEM_CEILING) if gated
                     else (gmm_vmem_bytes, GMM_VMEM_BUDGET))

    def widest(sides, tile):
        return next((t for t in sides if room(*tile(t)) <= ceiling), LANES)

    tk = widest(_lane_divisors(k), lambda t: (LANES, t, LANES))
    tn = widest(_lane_divisors(n), lambda t: (LANES, tk, t))
    rows = [t for t in ROW_TILES
            if m % t == 0 and t * ROWS_PER_TILE <= m // groups]
    return widest(rows, lambda t: (t, tk, tn)), tk, tn


def gated_tile_rows(group_sizes: jax.Array, m: int, k: int, n: int, *,
                    use_pallas: bool) -> jax.Array:
    """``i32[]``: the rows the grid of ``grouped_gated_matmul``'s kernel
    visits for ``group_sizes`` (``i32[G]``) of an ``[m, k] x [G, k, n]``
    call — over the non-empty groups, the row tiles a group's span of rows
    touches, times the row tile (``megablox``'s schedule: a tile that
    straddles groups is visited once for each). The real rows over it is
    how full the visited tiles were. 0 where the call runs the XLA form,
    which visits no tile. A few integer operations on ``i32[G]``."""
    if not (use_pallas and grouped_matmul_supported(m, k, n)):
        return jnp.zeros((), jnp.int32)
    tm = gmm_tiling(m, k, n, group_sizes.shape[0], gated=True)[0]
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
    """``f32[M, N]``: rows of ``lhs`` (``[M, K]``, sorted by group) times
    their group's matrix of ``rhs`` (``[G, K, N]``); ``group_sizes``
    ``i32[G]`` sums to ``M``. ``use_pallas`` asks for the kernel; a shape it
    does not take runs the XLA form."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if use_pallas and grouped_matmul_supported(m, k, n):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(lhs, rhs, group_sizes.astype(jnp.int32), jnp.float32,
                   gmm_tiling(m, k, n, rhs.shape[0]), interpret=interpret)
    return grouped_matmul_reference(lhs, rhs, group_sizes)


def _gated_kernel(offsets, group_ids, row_tiles, lhs, gate_w, up_w, out,
                  *accs, tm: int, tn: int, tiles_k: int):
    """One visit of a row tile by one group, one ``[tk, tn]`` block of each
    matrix: both products, summed over the K steps, and on the last the
    SiLU ⊙ of the group's own rows of the tile."""
    visit, k_i = pl.program_id(1), pl.program_id(2)

    def store(gate, up):
        group = group_ids[visit]
        row = row_tiles[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[group]) & (row < offsets[group + 1])
        # the other rows of the tile keep what an earlier visit wrote
        out[...] = jnp.where(mine, jax.nn.silu(gate) * up,
                             out[...].astype(jnp.float32)).astype(out.dtype)

    x = lhs[...]
    gate = jnp.dot(x, gate_w[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, up_w[...], preferred_element_type=jnp.float32)
    if tiles_k == 1:
        store(gate, up)
        return
    acc_gate, acc_up = accs

    @pl.when(k_i == 0)
    def _():
        acc_gate[...] = jnp.zeros_like(acc_gate)
        acc_up[...] = jnp.zeros_like(acc_up)

    acc_gate[...] += gate
    acc_up[...] += up

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc_gate[...], acc_up[...])


@functools.partial(jax.jit, static_argnames=("out_dtype", "tiling",
                                             "interpret"))
def gated_gmm(lhs: jax.Array, gate_w: jax.Array, up_w: jax.Array,
              group_sizes: jax.Array, *, out_dtype,
              tiling: Tuple[int, int, int], interpret: bool = False
              ) -> jax.Array:
    """The Pallas form of ``grouped_gated_matmul`` at ``tiling`` (tm, tk,
    tn), whole tiles on every side. Jitted with static tiles: the layers of
    a program share one trace and one lowering. Rows past the last group are
    never written, as ``megablox.gmm`` leaves them."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    m, k = lhs.shape
    groups, _, n = gate_w.shape
    tm, tk, tn = tiling
    tiles_k, tiles_n = k // tk, n // tn
    # megablox's own schedule: which row tile and which group each step of
    # the grid's middle axis visits, and how many steps hold work
    metadata, visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=groups,
        visit_empty_groups=False)

    def rows_at(n_i, visit, k_i, offsets, group_ids, row_tiles):
        return row_tiles[visit], k_i

    def matrix_at(n_i, visit, k_i, offsets, group_ids, row_tiles):
        return group_ids[visit], k_i, n_i

    def out_at(n_i, visit, k_i, offsets, group_ids, row_tiles):
        return row_tiles[visit], n_i

    out_dtype = jnp.dtype(out_dtype)
    matrix = pl.BlockSpec((None, tk, tn), matrix_at)
    # over the 16 MB a call may use on the v5e unasked at every tile the
    # rule picks for the encoders' shapes, so the call names its own budget
    vmem = gated_vmem_bytes(tm, tk, tn, lhs.dtype.itemsize,
                            out_dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_gated_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        name="gated_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), rows_at), matrix, matrix],
            out_specs=pl.BlockSpec((tm, tn), out_at),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)] * 2
                            if tiles_k > 1 else [])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=4 * m * k * n, transcendentals=m * n,
            bytes_accessed=(tiles_n * m * k * lhs.dtype.itemsize
                            + 2 * metadata[1].size * k * n
                            * gate_w.dtype.itemsize
                            + m * n * out_dtype.itemsize)),
        interpret=interpret,
    )(*metadata, lhs, gate_w, up_w)


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
