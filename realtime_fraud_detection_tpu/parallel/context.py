"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

The reference has no sequence parallelism anywhere (SURVEY.md §5.7) — its
longest context is the text branch's 512 tokens. This framework keeps
long-context first-class anyway: the same online-softmax accumulation that
the Pallas flash kernel (ops/attention.py) runs over k-blocks is run here
over *devices* — each device owns one sequence shard of K/V and rotates it
around the ring via ``ppermute`` while every device's Q shard stays put.
After ``seq_size()`` hops each Q block has seen every K/V block, with ICI
transfers overlapping compute hop by hop. Numerics are identical to dense
attention (softmax in f32, one global normalization at the end).

Layout convention matches ops/attention.py: q/k/v are [B, H, S, D] with a
bool ``key_mask`` [B, S] for padding; globally the batch dim is sharded over
``data`` and the sequence dim over ``seq``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from realtime_fraud_detection_tpu.core.mesh import DATA_AXIS, SEQ_AXIS
from realtime_fraud_detection_tpu.parallel.collectives import (
    ppermute_seq,
    seq_size,
    shard_map_over,
)

NEG_INF = -1e30


def _ring_attention_local(q, k, v, mask):
    """Per-device body (runs under shard_map, manual axes).

    q: [B, H, Sq, D] local query shard (stationary)
    k, v: [B, H, Sk, D] local key/value shard (rotates around the ring)
    mask: [B, Sk] validity of the local key shard (rotates with k/v)
    """
    d = q.shape[-1]
    qf = q.astype(jnp.float32) * (1.0 / float(np.sqrt(d)))
    n_hops = seq_size()

    def hop(_, carry):
        acc, m_prev, l_prev, k_cur, v_cur, mask_cur = carry
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32)
        )                                                   # [B,H,Sq,Sk] f32
        s = jnp.where(mask_cur[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))         # [B,H,Sq]
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32)
        )
        # rotate the K/V shard (and its mask) one step around the ring; the
        # final rotation returns them to their home device (no-op cost-wise
        # relative to the n-1 useful hops, keeps the loop branch-free)
        k_nxt = ppermute_seq(k_cur)
        v_nxt = ppermute_seq(v_cur)
        mask_nxt = ppermute_seq(mask_cur)
        return acc, m_new, l_new, k_nxt, v_nxt, mask_nxt

    b, h, sq, _ = q.shape
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc, _, l, _, _, _ = jax.lax.fori_loop(
        0, n_hops, hop, (acc0, m0, l0, k, v, mask)
    )
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    key_mask: jax.Array | None = None,
) -> jax.Array:
    """Context-parallel attention over global [B, H, S, D] arrays.

    B is sharded over ``data``, S over ``seq``; S must divide evenly by the
    seq-axis size. Works on any mesh built by ``core.mesh.build_mesh`` —
    with seq=1 it degrades to one local flash pass (identical code path).
    """
    b, _, s, _ = q.shape
    n_seq = mesh.shape[SEQ_AXIS]
    if s % n_seq:
        raise ValueError(f"seq len {s} not divisible by seq axis {n_seq}")
    if key_mask is None:
        key_mask = jnp.ones((b, s), bool)

    qkv_spec = P(DATA_AXIS, None, SEQ_AXIS, None)
    mask_spec = P(DATA_AXIS, SEQ_AXIS)
    fn = shard_map_over(
        mesh,
        _ring_attention_local,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, key_mask)


import functools


@functools.partial(jax.jit, static_argnames=("mesh", "config"))
def _cp_bert_forward(params, ids, mask, mesh, config):
    from realtime_fraud_detection_tpu.models.bert import bert_predict

    return bert_predict(
        params, ids, mask, config,
        attention_fn=lambda q, k, v, m: ring_attention(mesh, q, k, v, m),
    )


def bert_context_parallel_predict(
    mesh: Mesh,
    params,
    input_ids: jax.Array,       # i32[B, S]
    attention_mask: jax.Array,  # bool[B, S]
    config,
) -> jax.Array:
    """Long-context text-branch forward with the sequence dim sharded over
    the ``seq`` mesh axis.

    Attention runs as ring attention; every other op in the encoder
    (embeddings, layernorm, FFN matmuls, residuals) is per-token, so with
    the activations laid out P(data, seq, ...) XLA partitions them along S
    with no further annotation. Only the [CLS] pooling gathers across
    shards at the end. Numerics match the single-device encoder.

    At the reference's 512-token ceiling this is optional; it is the
    scaling path for long-context work (SURVEY.md §5.7).
    """
    from jax.sharding import NamedSharding

    from realtime_fraud_detection_tpu.models.bert import BertConfig

    if not isinstance(config, BertConfig):
        raise ValueError(
            "parallel/context.bert_context_parallel_predict rings "
            "DistilBERT's bidirectional attention; it does not hold a "
            f"{type(config).__name__} encoder (causal, RoPE)")
    ids = jax.device_put(input_ids, NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS)))
    mask = jax.device_put(
        attention_mask, NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS)))
    # replicate params onto THIS mesh: arrays restored from checkpoint (or
    # any earlier device_put) arrive committed to one device and would
    # clash with the mesh-sharded activations (same hazard FraudScorer.
    # set_models handles). No-op when already replicated, so repeated calls
    # don't re-copy; the forward itself is jitted (mesh/config static) so
    # layers trace once per (mesh, config, shapes).
    params = jax.device_put(params, NamedSharding(mesh, P()))
    return _cp_bert_forward(params, ids, mask, mesh, config)
