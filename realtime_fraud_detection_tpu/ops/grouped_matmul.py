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
  that straddle two groups visited once per group with a row mask), with the
  tiling chosen here for the encoders' shapes, K 2048 / N 1024 and K 1024 /
  N 2048 at thousands of rows a group. On the v5e it is a quarter to a third
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
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# a row tile is one group's rows against one [tk, tn] block of that group's
# matrix, so the larger the row tile the fewer times a matrix block crosses
# HBM
MAX_TM = 512
# the whole contraction in one block where it fits (no accumulator passes),
# and as wide a result block as keeps tk x tn at a million elements: with 512
# rows that is 2 MB of lhs, 2 MB of rhs and 1-2 MB of f32 result, double
# buffered, plus the f32 accumulator — inside the 16 MB a kernel may use on
# the v5e ((512, 2048, 1024) is refused there: out of VMEM).
# Measured on a v5e at 262,144 rows in 64 groups (PERF.md, PR 26), even /
# skewed groups: K 2048 -> N 1024 with (512, 2048, 512) 7.2 / 7.3 ms against
# 8.0 / 8.7 at (512, 1024, 1024) and XLA's own ragged_dot 9.7 / 10.5; K 1024
# -> N 2048 with (512, 1024, 1024) 7.3 / 7.5 ms against 12.4 / 13.9 at
# (512, 2048, 512) and ragged_dot 11.7 / 12.4.
MAX_TK = 2048
BLOCK_ELEMENTS = 1024 * 1024


def grouped_matmul_supported(m: int, k: int, n: int) -> bool:
    """Whole lane tiles on every side (the kernel's row tiling must divide
    ``m``)."""
    return (m >= LANES and m % LANES == 0 and k % LANES == 0
            and n % LANES == 0)


def _largest_tile(size: int, limit: int) -> int:
    """The largest power-of-two multiple of a lane tile that divides ``size``
    and is at most ``limit``."""
    tile = LANES
    while tile * 2 <= limit and size % (tile * 2) == 0:
        tile *= 2
    return tile


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) for a supported shape."""
    tm = _largest_tile(m, MAX_TM)
    tk = _largest_tile(k, MAX_TK)
    tn = _largest_tile(n, BLOCK_ELEMENTS // tk)
    return tm, tk, tn


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
                   gmm_tiling(m, k, n), interpret=interpret)
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
    tile = tm * tn * 4
    matrix = pl.BlockSpec((None, tk, tn), matrix_at)
    # in and out blocks double-buffered, and six f32 tiles: the two
    # products, the two accumulators and the epilogue's temporaries. At
    # (512, 2048, 512) that is 13 + 6 MB, over the 16 MB a call may use on
    # the v5e unasked, so the call names its own budget
    vmem = (2 * (tm * tk * lhs.dtype.itemsize
                 + 2 * tk * tn * gate_w.dtype.itemsize
                 + tm * tn * out_dtype.itemsize) + 6 * tile + (4 << 20))
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
        return gated_gmm(rows, gate_w, up_w, group_sizes.astype(jnp.int32),
                         out_dtype=out_dtype, tiling=gmm_tiling(m, k, n),
                         interpret=interpret)
    gate = grouped_matmul_reference(rows, gate_w, group_sizes)
    up = grouped_matmul_reference(rows, up_w, group_sizes)
    return (jax.nn.silu(gate) * up).astype(out_dtype)
