"""Tensorized gradient-boosted tree inference.

The reference scores XGBoost per request on CPU
(model_manager.py:309-311, called one transaction at a time from
ensemble_predictor.py:185-215). Tree traversal is branchy and
data-dependent — the worst possible shape for XLA — so we re-represent every
tree as a *complete* binary tree of fixed depth D:

- ``feature``   i32[T, 2^D - 1]  split feature per internal node
- ``threshold`` f32[T, 2^D - 1]  split threshold (x < t goes left)
- ``leaf``      f32[T, 2^D]      leaf values (log-odds contributions)

Traversal is then D data-independent gather steps: ``node = 2*node + 1 +
(x[feature] >= threshold)``. All shapes static, no control flow — the whole
ensemble jits into a handful of fused gathers on TPU and batches trivially.
Nodes that the trainer left unsplit get ``threshold=+inf`` so every row routes
left toward the real leaf (right subtree duplicates it).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct


@struct.dataclass
class TreeEnsemble:
    """Complete-binary-tree GBDT parameters (pytree)."""

    feature: jax.Array    # i32[T, I] with I = 2^depth - 1
    threshold: jax.Array  # f32[T, I]
    leaf: jax.Array       # f32[T, L] with L = 2^depth
    base_score: jax.Array  # f32[] prior logit

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def depth(self) -> int:
        return int(np.log2(self.leaf.shape[1]))

    @classmethod
    def zeros(cls, n_trees: int, depth: int, prior: float = 0.0) -> "TreeEnsemble":
        n_internal = 2**depth - 1
        return cls(
            feature=jnp.zeros((n_trees, n_internal), jnp.int32),
            threshold=jnp.full((n_trees, n_internal), jnp.inf, jnp.float32),
            leaf=jnp.zeros((n_trees, 2**depth), jnp.float32),
            base_score=jnp.asarray(prior, jnp.float32),
        )


def descend_complete_trees(
    feature: jax.Array, threshold: jax.Array, x: jax.Array
) -> jax.Array:
    """Shared complete-tree traversal: leaf index per (row, tree).

    feature/threshold: [T, 2^D - 1]; x: f32[B, F]. Returns i32[B, T] leaf
    indices in [0, 2^D). D unrolled data-independent gather steps; the single
    split convention for the whole framework is **x >= threshold goes
    right** (GBDT forward, GBDT trainer, isolation forest all share it).
    """
    b = x.shape[0]
    t, n_internal = feature.shape
    depth = int(np.log2(n_internal + 1))

    feat_flat = feature.reshape(-1)      # [T * I]
    thr_flat = threshold.reshape(-1)     # [T * I]
    tree_offset = jnp.arange(t, dtype=jnp.int32) * n_internal  # [T]

    node = jnp.zeros((b, t), jnp.int32)
    for _ in range(depth):
        flat = node + tree_offset[None, :]               # [B, T]
        feat = feat_flat[flat]                           # [B, T]
        thr = thr_flat[flat]                             # [B, T]
        xv = jnp.take_along_axis(x, feat, axis=1)        # [B, T]
        node = 2 * node + 1 + (xv >= thr).astype(jnp.int32)
    return node - n_internal                              # [B, T] in [0, L)


def gather_leaf_values(leaf: jax.Array, leaf_idx: jax.Array) -> jax.Array:
    """leaf: [T, L], leaf_idx: i32[B, T] -> f32[B, T] values."""
    t, l = leaf.shape
    leaf_flat = leaf.reshape(-1)
    offset = jnp.arange(t, dtype=jnp.int32) * l
    return leaf_flat[leaf_idx + offset[None, :]]


# --------------------------------------------------------------------------
# GEMM-form traversal (Hummingbird, arXiv:2010.04804): the same complete
# trees re-expressed as batched tensor contractions the MXU actually likes.
# Selectable per branch via utils.config.QuantSettings; the gather path
# above stays the numerics oracle (leaf-index equality pinned in tests and
# by `rtfd quant-drill`).
# --------------------------------------------------------------------------

_EXACT = jax.lax.Precision.HIGHEST


@lru_cache(maxsize=None)
def _complete_tree_paths(depth: int) -> tuple:
    """Structure constants of a complete binary tree of ``depth``:

    ``C`` i8[I, L] — +1 where leaf ``l`` sits in the LEFT subtree of
    internal node ``i``, -1 for the right subtree, 0 when ``i`` is not an
    ancestor; ``d`` i32[L] — the number of left edges on the path to
    ``l``. Depends only on the depth, so it folds into the compiled
    program as a constant.
    """
    n_internal = 2 ** depth - 1
    n_leaf = 2 ** depth
    c = np.zeros((n_internal, n_leaf), np.int8)
    d = np.zeros((n_leaf,), np.int32)
    for leaf in range(n_leaf):
        node = leaf + n_internal
        while node:
            parent = (node - 1) // 2
            is_left = node == 2 * parent + 1
            c[parent, leaf] = 1 if is_left else -1
            if is_left:
                d[leaf] += 1
            node = parent
    return c, d


def gemm_leaf_onehot(
    feature: jax.Array, threshold: jax.Array, x: jax.Array,
) -> jax.Array:
    """One-hot leaf selection as batched matmuls. f32[B, T, L].

    Three contractions (the Hummingbird GEMM strategy): (1) a one-hot
    feature-selection tensor built from the runtime ``feature`` params
    routes ``x`` to every internal node at once, (2) the left-indicator
    matrix contracts with the ancestor-structure constants ``C``, and (3)
    the leaf whose count of satisfied ancestor conditions equals its
    left-edge count ``d`` lights up. The split convention matches
    ``descend_complete_trees`` EXACTLY — ``left = NOT (x >= t)`` — so
    unsplit nodes (threshold=+inf) route identically and the selected
    leaf indices are equal by construction on finite features (the §2.3
    feature contract; a non-finite feature would poison the selection
    contraction, where the gather path localizes it). All count
    arithmetic involves small integers (<= depth), exact in f32.

    Every contraction asks for ``Precision.HIGHEST``: the selection must
    hand each node its feature value EXACTLY, and a TPU's default matmul
    precision passes f32 operands to the MXU as single bf16 passes — on
    the v5e that rounded the features before the threshold compare and
    sent 86 of 25,600 (row, tree) pairs to a different leaf than the
    gather path (logits up to 0.48 apart; 0 and 5e-7 at HIGHEST; my chip
    run, PR 21). The CPU backend ignores the setting.
    """
    t, n_internal = feature.shape
    depth = int(np.log2(n_internal + 1))
    f_dim = x.shape[1]
    c, d = _complete_tree_paths(depth)
    sel = (feature[:, :, None]
           == jnp.arange(f_dim, dtype=feature.dtype)[None, None, :])
    xv = jnp.einsum("bf,tif->bti", x, sel.astype(x.dtype),
                    precision=_EXACT)                          # [B, T, I]
    left = 1.0 - (xv >= threshold[None, :, :]).astype(x.dtype)
    reach = jnp.einsum("bti,il->btl", left, jnp.asarray(c, x.dtype),
                       precision=_EXACT)                       # [B, T, L]
    return (reach == jnp.asarray(d, x.dtype)[None, None, :]).astype(x.dtype)


def gemm_leaf_index(
    feature: jax.Array, threshold: jax.Array, x: jax.Array,
) -> jax.Array:
    """GEMM-path leaf indices i32[B, T] — the oracle-comparison hook:
    equal to ``descend_complete_trees`` on every input, by test."""
    onehot = gemm_leaf_onehot(feature, threshold, x)
    return jnp.argmax(onehot, axis=2).astype(jnp.int32)


def gemm_leaf_contract(
    feature: jax.Array, threshold: jax.Array, values: jax.Array,
    x: jax.Array,
) -> jax.Array:
    """One-hot leaf selection contracted with per-leaf ``values`` [T, L]
    -> f32[B, T]: the GEMM-form replacement for descend+gather, shared by
    the GBDT (leaf log-odds) and the isolation forest (path lengths)."""
    onehot = gemm_leaf_onehot(feature, threshold, x)
    return jnp.einsum("btl,tl->bt", onehot, values, precision=_EXACT)


def tree_ensemble_logits(ensemble: TreeEnsemble, x: jax.Array,
                         kernel: str = "gather") -> jax.Array:
    """Raw log-odds for a feature batch. x: f32[B, F] -> f32[B].

    ``kernel`` selects the traversal: ``"gather"`` (the D-step gather
    oracle above) or ``"gemm"`` (batched contractions). Same signature,
    same split convention, identical leaves; leaf-value summation order
    differs, so logits agree to float tolerance, not bit-for-bit.
    """
    if kernel == "gemm":
        values = gemm_leaf_contract(ensemble.feature, ensemble.threshold,
                                    ensemble.leaf, x)
    elif kernel == "gather":
        leaf_idx = descend_complete_trees(ensemble.feature,
                                          ensemble.threshold, x)
        values = gather_leaf_values(ensemble.leaf, leaf_idx)
    else:
        raise ValueError(
            f"tree kernel must be 'gather' or 'gemm', got {kernel!r}")
    return ensemble.base_score + values.sum(axis=1)


@partial(jax.jit, static_argnames=("kernel",))
def tree_ensemble_predict(ensemble: TreeEnsemble, x: jax.Array,
                          kernel: str = "gather") -> jax.Array:
    """Fraud probability, the predict_proba[:, 1] equivalent. f32[B]."""
    return jax.nn.sigmoid(
        tree_ensemble_logits(ensemble, x, kernel=kernel))
