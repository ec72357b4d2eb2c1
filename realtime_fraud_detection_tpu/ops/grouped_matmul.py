"""Grouped matmul for routed experts: ``[rows sorted by group, K] x [G, K, N]``.

Row ``i`` of ``lhs`` belongs to the group whose span of
``cumsum(group_sizes)`` holds ``i``; each group's rows are multiplied by
that group's own ``[K, N]`` matrix. Groups are ragged (sizes are data: what
the router chose), may be empty, and one may hold every row. bf16 operands,
f32 accumulation, f32 result — the precision the OLMoE configuration states.

Two forms, one entry point (``grouped_matmul``):

- the XLA form, ``jax.lax.ragged_dot``: what CPU hosts and the tests run,
  and what any shape the kernel does not take runs on the chip;
- the Pallas form: ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (a grid
  over row tiles, each tile multiplied by the matrix of the group that owns
  it, tiles that straddle two groups visited once per group with a row
  mask), with the tiling chosen here for the encoder's two shapes, K 2048 /
  N 1024 and K 1024 / N 2048 at thousands of rows a group. On the v5e it is
  a quarter to a third faster than XLA's own lowering of ``ragged_dot``
  (itself a grouped kernel, not per-group dense work), and — unlike that
  lowering, whose custom calls are named ``ragged-dot-none`` whatever scope
  they were traced under — it keeps the ``jax.named_scope`` path in its
  ``op_name``, so a device trace can attribute it.

``grouped_matmul_supported`` is the ONE predicate on shapes: the traced
guard below, the scorer's selector (``FraudScorer.effective_use_pallas``)
and the tests all ask it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

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
