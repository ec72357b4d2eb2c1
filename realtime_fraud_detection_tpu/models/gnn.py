"""GraphSAGE user-merchant network scorer.

The reference's "GNN" is a 3-layer MLP over the 64-feature vector
(model_manager.py:202-242) with graph statistics bolted on host-side
(graph_neural_network.py:244-315, last-100-transaction entity graph). The
baseline contract (BASELINE.json config 5) asks for a real **GraphSAGE
user-merchant network scorer**, so that is what this is:

- node features: user nodes and merchant nodes carry small profile-stat
  vectors (padded to a common node_dim);
- one SAGE layer per hop: h' = relu(W [h_self ; mean(h_neighbors)]) with
  mask-aware mean over a fixed fan-out K (padded neighbor tensors from
  state.EntityGraphStore — dense, static shapes, vmap-free batching);
- the scored edge (user u, merchant m) combines both embeddings with the
  transaction's 64-feature vector through an MLP head.

Two-hop batching: neighbors-of-neighbors arrive as [B, K, K] tensors; the
first SAGE layer embeds the 1-hop frontier using 2-hop aggregates, the
second embeds the centers. All gathers are host-prepared index tensors; the
device sees only dense matmuls and masked means (MXU + VPU, no scatter).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# Node-type tag slots in the node_dim feature row. Users carry no tag
# (their stat slots 0-7 are dense); merchant=8 predates the typed graph;
# device/ip joined with the heterogeneous entity graph (graph/store.py).
MERCHANT_TAG_SLOT = 8
DEVICE_TAG_SLOT = 9
IP_TAG_SLOT = 10
TYPED_MIN_NODE_DIM = 12     # 8 user stats + 3 type tags + 1 degree slot


def init_gnn_params(
    key: jax.Array,
    node_dim: int = 16,
    txn_dim: int = 64,
    hidden: int = 64,
    head_hidden: int = 64,
    typed: bool = False,
) -> Dict[str, jax.Array]:
    """GraphSAGE (2 layers) + head parameters (config.py:177-184: hidden 64,
    3 layers total counting the head, dropout 0.1).

    ``typed=True`` adds per-node-type projection weights (the
    heterogeneous-SAGE / R-GCN relation-weight idiom) consumed by
    :func:`typed_node_projection` ahead of every SAGE aggregation — the
    graph plane's device/IP node types carry degree features in a
    different basis than user/merchant profile stats, and one shared
    aggregation matrix would have to serve all four. The typed layout is
    detected STRUCTURALLY by :func:`gnn_logits` (the models/quant.py
    discipline: a scorer serves whatever parameter form it holds), and
    the checkpoint plane arch-stamps it (``checkpoint._derive_graph_mode``)
    so a cross-form restore is refused, never silent. The (D, D) squares
    follow parallel/layouts.leaf_storage_spec's largest-divisible-dim
    rule for mesh storage sharding like every other GNN leaf."""
    # split count is mode-dependent ON PURPOSE: threefry hashes the full
    # count into every derived key, so splitting 10 unconditionally would
    # silently re-seed the PRE-EXISTING bipartite init (every seed-pinned
    # untyped model would drift). typed=False keeps the committed stream.
    ks = jax.random.split(key, 10 if typed else 6)

    def glorot(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * float(
            np.sqrt(2.0 / (shape[0] + shape[1]))
        )

    params = {
        # layer 1: embeds the 1-hop frontier from raw node features
        "w_sage1": glorot(ks[0], (2 * node_dim, hidden)),
        "b_sage1": jnp.zeros((hidden,), jnp.float32),
        # layer 2: embeds the centers from (raw self, hidden neighbors)
        "w_sage2": glorot(ks[1], (node_dim + hidden, hidden)),
        "b_sage2": jnp.zeros((hidden,), jnp.float32),
        "w_head1": glorot(ks[2], (2 * hidden + txn_dim, head_hidden)),
        "b_head1": jnp.zeros((head_hidden,), jnp.float32),
        "w_head2": glorot(ks[3], (head_hidden, 1)),
        "b_head2": jnp.zeros((1,), jnp.float32),
    }
    if typed:
        if node_dim < TYPED_MIN_NODE_DIM:
            raise ValueError(
                f"typed GNN params need node_dim >= {TYPED_MIN_NODE_DIM} "
                f"(type tags at slots {MERCHANT_TAG_SLOT}/"
                f"{DEVICE_TAG_SLOT}/{IP_TAG_SLOT}), got {node_dim}")
        eye = jnp.eye(node_dim, dtype=jnp.float32)
        for i, name in enumerate(("user", "merchant", "device", "ip")):
            # near-identity init: an untrained typed GNN starts close to
            # the homogeneous one instead of scrambling the node basis
            params[f"w_node_{name}"] = (
                eye + 0.1 * glorot(ks[4 + i], (node_dim, node_dim)))
    return params


def is_typed_gnn(params: Dict[str, jax.Array]) -> bool:
    """Structural detection of the typed parameter layout (no static flag
    — the quant-plane discipline)."""
    return "w_node_user" in params


def typed_node_projection(params: Dict[str, jax.Array],
                          feat: jax.Array) -> jax.Array:
    """Per-node-type linear projection before aggregation.

    The node type is read from the feature row's own tag slots (one-hot
    by construction: the featurizers set exactly one of merchant/device/
    ip, users none), so no extra type tensor rides the batch — the
    projection blends the four relation weights by the tags, which for
    one-hot tags selects exactly one matrix."""
    tm = feat[..., MERCHANT_TAG_SLOT:MERCHANT_TAG_SLOT + 1]
    td = feat[..., DEVICE_TAG_SLOT:DEVICE_TAG_SLOT + 1]
    ti = feat[..., IP_TAG_SLOT:IP_TAG_SLOT + 1]
    tu = jnp.clip(1.0 - tm - td - ti, 0.0, 1.0)
    return (tu * (feat @ params["w_node_user"])
            + tm * (feat @ params["w_node_merchant"])
            + td * (feat @ params["w_node_device"])
            + ti * (feat @ params["w_node_ip"]))


def _masked_mean(x: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean over axis -2 where mask, else zeros. x: [..., K, D], mask [..., K]."""
    m = mask[..., None].astype(x.dtype)
    total = (x * m).sum(axis=-2)
    count = jnp.maximum(m.sum(axis=-2), 1.0)
    return total / count


def _sage(w, b, self_feat, neigh_feat, neigh_mask):
    agg = _masked_mean(neigh_feat, neigh_mask)
    z = jnp.concatenate([self_feat, agg], axis=-1)
    return jax.nn.relu(z @ w + b)


def _true_f32_matmuls(fn):
    """Trace ``fn`` with its f32 matmuls really in f32.

    The branch multiplies f32 params by RAW node/transaction features
    (amounts and velocity sums reach 1e4). A TPU's default matmul
    precision feeds f32 operands to the MXU as single bf16 passes, which
    rounds those features to three digits: on the v5e the branch's
    probability moved by up to 0.35 against the same program on the CPU
    backend (chip_smoke parity, PR 21), and by 4e-8 at "highest". The
    matmuls are tiny, so the extra passes cost nothing that shows."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


@_true_f32_matmuls
def gnn_logits(
    params: Dict[str, jax.Array],
    txn_features: jax.Array,     # f32[B, 64]
    user_feat: jax.Array,        # f32[B, node_dim] center user nodes
    merchant_feat: jax.Array,    # f32[B, node_dim] center merchant nodes
    user_neigh_feat: jax.Array,  # f32[B, K, node_dim] merchants around user
    user_neigh_mask: jax.Array,  # bool[B, K]
    merch_neigh_feat: jax.Array,  # f32[B, K, node_dim] users around merchant
    merch_neigh_mask: jax.Array,  # bool[B, K]
    user_neigh2_feat: jax.Array | None = None,   # f32[B, K, K, node_dim]
    user_neigh2_mask: jax.Array | None = None,   # bool[B, K, K]
    merch_neigh2_feat: jax.Array | None = None,  # f32[B, K, K, node_dim]
    merch_neigh2_mask: jax.Array | None = None,  # bool[B, K, K]
) -> jax.Array:
    """Fraud logit per scored (user, merchant, txn) edge. f32[B]."""
    def _empty_frontier(x):
        # [B, K, 1, D] zeros with an all-False mask -> masked mean yields 0
        return x[..., None, :] * 0.0, jnp.zeros(x.shape[:-1] + (1,), bool)

    if is_typed_gnn(params):
        # heterogeneous mode: the txn-feature input is clipped INSIDE the
        # program (the LSTM branch's serving-side-clip precedent,
        # build_sequence_dataset: raw velocity/amount features reach 1e4,
        # far outside a trainable range) — baking the clip into the typed
        # program means training (train_typed_gnn) and serving see
        # identical ranges by construction, with zero train/serve skew.
        # The bipartite program is untouched: its committed behavior
        # (and every score pinned against it) predates the clip.
        txn_features = jnp.clip(txn_features, -10.0, 10.0)
        # rotate every node-feature tensor through its type's projection
        # before any aggregation (the tags live in the rows themselves,
        # so padded/masked rows project to near-zero and the masks still
        # gate them out)
        proj = lambda x: typed_node_projection(params, x)   # noqa: E731
        user_feat, merchant_feat = proj(user_feat), proj(merchant_feat)
        user_neigh_feat = proj(user_neigh_feat)
        merch_neigh_feat = proj(merch_neigh_feat)
        if user_neigh2_feat is not None:
            user_neigh2_feat = proj(user_neigh2_feat)
        if merch_neigh2_feat is not None:
            merch_neigh2_feat = proj(merch_neigh2_feat)

    # layer 1: embed 1-hop frontier (uses 2-hop context when provided)
    if user_neigh2_feat is None:
        user_neigh2_feat, user_neigh2_mask = _empty_frontier(user_neigh_feat)
    if merch_neigh2_feat is None:
        merch_neigh2_feat, merch_neigh2_mask = _empty_frontier(merch_neigh_feat)
    u_frontier = _sage(params["w_sage1"], params["b_sage1"],
                       user_neigh_feat, user_neigh2_feat, user_neigh2_mask)
    m_frontier = _sage(params["w_sage1"], params["b_sage1"],
                       merch_neigh_feat, merch_neigh2_feat, merch_neigh2_mask)

    # layer 2: embed the centers from their (raw, embedded-frontier) context
    h_user = _sage(params["w_sage2"], params["b_sage2"],
                   user_feat, u_frontier, user_neigh_mask)
    h_merch = _sage(params["w_sage2"], params["b_sage2"],
                    merchant_feat, m_frontier, merch_neigh_mask)

    z = jnp.concatenate([h_user, h_merch, txn_features], axis=-1)
    z = jax.nn.relu(z @ params["w_head1"] + params["b_head1"])
    return (z @ params["w_head2"] + params["b_head2"])[:, 0]


@jax.jit
def gnn_predict(params, txn_features, user_feat, merchant_feat,
                user_neigh_feat, user_neigh_mask,
                merch_neigh_feat, merch_neigh_mask) -> jax.Array:
    """1-hop fraud probability (the serving path; 2-hop is a training option)."""
    return jax.nn.sigmoid(gnn_logits(
        params, txn_features, user_feat, merchant_feat,
        user_neigh_feat, user_neigh_mask, merch_neigh_feat, merch_neigh_mask,
    ))


def build_node_features(
    user_pool, merchant_pool, node_dim: int = 16
) -> tuple[np.ndarray, np.ndarray]:
    """Static node feature tables from the profile pools.

    user nodes:   [risk, log-avg-amount, freq, age/365, verified, weekend,
                   intl, online] zero-padded to node_dim
    merchant nodes: [risk_code/2, fraud_rate, log-avg-amount, blacklisted,
                   category/10, op_start/24, op_end/24] zero-padded; slot 8
                   is the merchant type tag, so node_dim must be >= 9.
    """
    if node_dim < 9:
        raise ValueError(f"node_dim must be >= 9 (8 stat slots + type tag), got {node_dim}")
    u = np.zeros((user_pool.n, node_dim), np.float32)
    u[:, 0] = user_pool.risk_score
    u[:, 1] = np.log1p(user_pool.avg_amount)
    u[:, 2] = user_pool.txn_frequency
    u[:, 3] = user_pool.account_age_days / 365.0
    u[:, 4] = (user_pool.kyc_code == 0)
    u[:, 5] = user_pool.weekend_activity
    u[:, 6] = user_pool.intl_ratio
    u[:, 7] = user_pool.online_preference

    m = np.zeros((merchant_pool.n, node_dim), np.float32)
    m[:, 0] = merchant_pool.risk_code / 2.0
    m[:, 1] = merchant_pool.fraud_rate
    m[:, 2] = np.log1p(merchant_pool.avg_amount)
    m[:, 3] = merchant_pool.is_blacklisted
    m[:, 4] = merchant_pool.category_code / 10.0
    m[:, 5] = merchant_pool.op_start / 24.0
    m[:, 6] = merchant_pool.op_end / 24.0
    m[:, 8] = 1.0  # type tag distinguishing merchant nodes from user nodes
    return u, m


def gather_neighbor_features(
    node_table: np.ndarray, idx: np.ndarray, mask: np.ndarray
) -> np.ndarray:
    """Safe gather: padded (-1) indices read row 0 but are masked out."""
    safe = np.where(mask, idx, 0)
    return node_table[safe]


def typed_entity_features(kind: str, degrees: np.ndarray, node_dim: int,
                          fanout: int) -> np.ndarray:
    """Node feature rows for the profile-less entity types (device / IP /
    cold merchant) of the typed graph (graph/store.py).

    These nodes have no profile store behind them; their learnable signal
    is STRUCTURAL — how many distinct users funnel through them, which is
    exactly the fraud-ring signature (a benign device serves one user; a
    ring device serves the cohort). One definition shared by the serving
    sampler AND the training dataset builder, so the GNN always sees the
    featurization it was trained on:

    - slot 0: ring occupancy / fanout  (bounded degree, in [0, 1])
    - slot 1: log1p(degree)            (unsaturated low-end resolution)
    - tag slot (8/9/10): 1.0 for merchant/device/ip respectively
    """
    tag = {"merchant": MERCHANT_TAG_SLOT, "device": DEVICE_TAG_SLOT,
           "ip": IP_TAG_SLOT}.get(kind)
    if tag is None:
        raise ValueError(f"typed_entity_features kind must be "
                         f"merchant|device|ip, got {kind!r}")
    if node_dim < TYPED_MIN_NODE_DIM:
        raise ValueError(
            f"typed entity features need node_dim >= {TYPED_MIN_NODE_DIM}, "
            f"got {node_dim}")
    deg = np.asarray(degrees, np.float32)
    rows = np.zeros((len(deg), node_dim), np.float32)
    rows[:, 0] = np.minimum(deg, float(fanout)) / max(float(fanout), 1.0)
    rows[:, 1] = np.log1p(deg)
    rows[:, tag] = 1.0
    return rows
