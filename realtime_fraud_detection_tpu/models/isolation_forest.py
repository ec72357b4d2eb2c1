"""Tensorized isolation forest.

The reference serves a sklearn IsolationForest (contamination 0.1, 100
estimators — config.py:186-198) and maps its ``decision_function`` through a
sigmoid to get fraud probability: ``1/(1+exp(score))``
(model_manager.py:338-346). Here each isolation tree becomes the same
complete-binary-tree tensor layout as the GBDT (models/trees.py), with leaves
holding the *path length* estimate h = depth + c(n_leaf); scoring is the
standard anomaly score s = 2^(-E[h]/c(psi)) and the sklearn-compatible
decision function 0.5 - s, so the reference's probability mapping carries
over unchanged.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct


def _c(n: float) -> float:
    """Average unsuccessful BST search length c(n) (Liu et al. 2008)."""
    if n <= 1:
        return 0.0
    h = math.log(n - 1) + 0.5772156649015329
    return 2.0 * h - 2.0 * (n - 1) / n


@struct.dataclass
class IsolationForest:
    """Complete-binary-tree isolation forest parameters (pytree)."""

    feature: jax.Array     # i32[T, I]
    threshold: jax.Array   # f32[T, I]
    path_length: jax.Array  # f32[T, L] — h estimate per leaf
    c_psi: jax.Array       # f32[] normalizer c(psi)

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]


def iforest_scores(forest: IsolationForest, x: jax.Array,
                   kernel: str = "gather") -> jax.Array:
    """Anomaly score s in (0, 1]; higher = more anomalous. f32[B].

    ``kernel`` selects the traversal (models/trees.py): ``"gather"`` (the
    oracle) or ``"gemm"`` (Hummingbird-style one-hot contractions over the
    same complete-tree layout — identical leaves, path lengths summed in
    a different order, so scores agree to float tolerance).
    """
    from realtime_fraud_detection_tpu.models.trees import (
        descend_complete_trees,
        gather_leaf_values,
        gemm_leaf_contract,
    )

    if kernel == "gemm":
        h = gemm_leaf_contract(forest.feature, forest.threshold,
                               forest.path_length, x)  # [B, T]
    elif kernel == "gather":
        leaf_idx = descend_complete_trees(forest.feature, forest.threshold, x)
        h = gather_leaf_values(forest.path_length, leaf_idx)  # [B, T]
    else:
        raise ValueError(
            f"iforest kernel must be 'gather' or 'gemm', got {kernel!r}")
    mean_h = h.mean(axis=1)
    return jnp.exp2(-mean_h / forest.c_psi)


@partial(jax.jit, static_argnames=("kernel",))
def iforest_predict(forest: IsolationForest, x: jax.Array,
                    kernel: str = "gather") -> jax.Array:
    """Fraud probability via the reference mapping (model_manager.py:338-346).

    decision_function = 0.5 - s (sklearn offset convention), then
    p = 1/(1+exp(decision)).
    """
    decision = 0.5 - iforest_scores(forest, x, kernel=kernel)
    return 1.0 / (1.0 + jnp.exp(decision))


@dataclasses.dataclass
class IsolationForestTrainer:
    """Fits isolation trees on subsamples with random splits."""

    n_estimators: int = 100
    max_samples: int = 256
    seed: int = 42

    def fit(self, x: np.ndarray) -> IsolationForest:
        rng = np.random.default_rng(self.seed)
        x = np.asarray(x, np.float32)
        n, f = x.shape
        psi = min(self.max_samples, n)
        depth = max(1, int(np.ceil(np.log2(psi))))
        n_internal = 2**depth - 1
        n_leaf = 2**depth

        feat = np.zeros((self.n_estimators, n_internal), np.int32)
        thr = np.full((self.n_estimators, n_internal), np.inf, np.float32)
        plen = np.zeros((self.n_estimators, n_leaf), np.float32)

        for t in range(self.n_estimators):
            idx = rng.choice(n, size=psi, replace=False)
            # node -> sample index list; grow breadth-first over the complete tree
            members: dict[int, np.ndarray] = {0: idx}
            for node in range(n_internal):
                rows = members.pop(node, None)
                if rows is None:
                    continue
                level = int(np.log2(node + 1))
                if len(rows) <= 1:
                    self._seal(node, level, depth, len(rows), thr[t], plen[t])
                    continue
                sub = x[rows]
                lo, hi = sub.min(axis=0), sub.max(axis=0)
                splittable = np.where(hi > lo)[0]
                if splittable.size == 0:
                    self._seal(node, level, depth, len(rows), thr[t], plen[t])
                    continue
                j = int(rng.choice(splittable))
                s = float(rng.uniform(lo[j], hi[j]))
                feat[t, node] = j
                thr[t, node] = s
                right = sub[:, j] >= s
                members[2 * node + 1] = rows[~right]
                members[2 * node + 2] = rows[right]
            # max-depth leaves
            for node, rows in members.items():
                leaf = node - n_internal
                plen[t, leaf] = depth + _c(len(rows))

        return IsolationForest(
            feature=jnp.asarray(feat),
            threshold=jnp.asarray(thr),
            path_length=jnp.asarray(plen),
            c_psi=jnp.asarray(_c(psi), jnp.float32),
        )

    @staticmethod
    def _seal(node: int, level: int, depth: int, n_rows: int,
              thr: np.ndarray, plen: np.ndarray) -> None:
        """Terminate a node early: inf thresholds route left to one leaf."""
        h = level + _c(n_rows)
        n_internal = thr.shape[0]
        # walk leftmost chain to the leaf, marking inf thresholds
        cur = node
        for _ in range(depth - level):
            thr[cur] = np.inf
            cur = 2 * cur + 1
        first_leaf = cur - n_internal
        span = 2 ** (depth - level)
        plen[first_leaf : first_leaf + span] = h
