"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference scales only by data parallelism (Flink parallelism 12,
SURVEY.md §2.8); this framework adds pipeline parallelism as a first-class
mesh axis so models deeper than one chip's HBM (or latency budget) split by
LAYER SPAN instead of by tensor. Design, TPU-first:

- Stage parameters are stacked on a leading ``[n_stages, ...]`` dim and
  sharded over the pipeline axis — each device materializes only its own
  span's weights (1/S of the model).
- The schedule is a single ``lax.scan`` inside ``shard_map``: every tick,
  each device runs its stage on the activation it holds, then the
  activations rotate one hop along the ring via ``ppermute`` — the same
  compute/ICI-overlap pattern as ring attention (parallel/context.py), with
  the pipeline bubble (S-1 idle ticks) amortized by M microbatches.
- The last stage's outputs are replicated with a ``psum`` over the axis
  (every other device contributes zeros), so callers get a full [M, ...]
  result on every device — composable with data parallelism on ``data``.
- The whole schedule is differentiable (scan + ppermute have transposes),
  so ``jax.grad`` through ``pipeline_forward`` yields 1B1F-style reverse
  scheduling from XLA with no hand-written backward pass.

No counterpart exists in the reference; the contract here is numerical
equivalence with the sequential layer stack (tests/test_parallel.py).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from realtime_fraud_detection_tpu.core.mesh import MODEL_AXIS
from realtime_fraud_detection_tpu.parallel.collectives import shard_map_over

__all__ = ["pipeline_forward", "stack_stage_params", "bert_pipeline_encode",
           "PIPELINE_AXIS"]

# default pipeline axis: reuse the ``model`` mesh axis — tensor and pipeline
# parallelism partition the same weight dimension budget, pick per model
PIPELINE_AXIS = MODEL_AXIS


def stack_stage_params(per_stage_params: list) -> Any:
    """[p_0, ..., p_{S-1}] pytrees -> one pytree with leading stage dim S.

    The result is what ``pipeline_forward`` shards over the pipeline axis
    (each device holds rows of its own stage only)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def pipeline_forward(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    axis: str = PIPELINE_AXIS,
) -> jax.Array:
    """Run ``stage_fn`` S times over each of M microbatches, pipelined.

    mesh:        mesh containing ``axis`` (size S = number of stages)
    stage_fn:    (params_for_one_stage, h) -> h' where h is an array
                 [mb, ...] or a PYTREE of arrays (e.g. (hidden, mask) so
                 per-microbatch side inputs ride the pipeline); shapes must
                 be stage-invariant
    stage_params: pytree with leading dim S (see ``stack_stage_params``)
    microbatches: pytree of [M, mb, ...] arrays (replicated over ``axis``)

    Returns the same pytree with [M, mb, ...] outputs, replicated over
    ``axis``. Total ticks = M + S - 1; efficiency = M / (M + S - 1), so
    use M >= 4*S in earnest.
    """
    n_stages = mesh.shape[axis]
    n_micro = jax.tree.leaves(microbatches)[0].shape[0]

    def device_body(params, mb):
        # params: [S, ...] (replicated), mb: [M, mb, ...] (replicated).
        # Each device gathers its own stage's rows by axis index rather
        # than receiving a P(axis)-split input: jax 0.4.x's partitioner
        # miscompiles shard_map inputs split over a non-leading mesh axis
        # when the operand is a traced INTERMEDIATE (values arrive scaled
        # by the data-axis size — a spurious cross-axis reduction), and
        # callers like bert_pipeline_encode stack the stage params inside
        # their jit. Replicated-in + local gather is immune, at the cost
        # of each device holding all S stages' weights — revisit when the
        # models outgrow per-device HBM.
        stage = jax.lax.axis_index(axis)
        my_params = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(
                x, stage, axis=0, keepdims=False), params)
        is_first = stage == 0
        is_last = stage == n_stages - 1
        zero = jax.tree.map(lambda m: jnp.zeros_like(m[0]), mb)
        outputs0 = jax.tree.map(jnp.zeros_like, mb)

        def tick(carry, t):
            incoming, outputs = carry
            # stage 0 injects microbatch t while t < M; later stages use
            # the activation that arrived over the ring last tick
            t_idx = jnp.minimum(t, n_micro - 1)
            inj = jax.tree.map(
                lambda m: jax.lax.dynamic_index_in_dim(
                    m, t_idx, axis=0, keepdims=False), mb)
            h_in = jax.tree.map(
                lambda a, b: jnp.where(is_first, a, b), inj, incoming)
            h_out = stage_fn(my_params, h_in)
            # the last stage banks its result at slot t-(S-1) once the
            # pipeline has filled; everyone else banks zeros (psum later)
            slot = t - (n_stages - 1)
            valid = is_last & (slot >= 0) & (slot < n_micro)
            slot_c = jnp.maximum(slot, 0)
            outputs = jax.tree.map(
                lambda o, h: jax.lax.dynamic_update_index_in_dim(
                    o,
                    jnp.where(valid, h, jax.lax.dynamic_index_in_dim(
                        o, slot_c, axis=0, keepdims=False)),
                    slot_c, axis=0),
                outputs, h_out)
            # rotate activations one hop down the pipeline ring
            nxt = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (zero, outputs0), jnp.arange(n_micro + n_stages - 1))
        # replicate the last stage's banked outputs to every stage device
        return jax.lax.psum(
            jax.tree.map(
                lambda o: jnp.where(is_last, o, jnp.zeros_like(o)), outputs),
            axis)

    in_specs = (
        jax.tree.map(lambda _: P(), stage_params),   # replicated; see body
        P(),                                     # microbatches replicated
    )
    return shard_map_over(
        mesh, device_body, in_specs=in_specs, out_specs=P(),
    )(stage_params, microbatches)


def bert_pipeline_encode(
    mesh: Mesh,
    params: Any,
    input_ids: jax.Array,       # i32[B, S]
    attention_mask: jax.Array,  # bool[B, S]
    config: Any,                # models.bert.BertConfig
    n_micro: int = 4,
    axis: str = PIPELINE_AXIS,
    use_pallas: bool = False,
) -> jax.Array:
    """DistilBERT encoder with its layers PIPELINED over ``axis``.

    Each device holds ``num_layers / S`` transformer blocks; hidden states
    (with their attention mask riding along as a pytree leaf) flow through
    the GPipe schedule in ``n_micro`` microbatches. Embeddings and the
    mask are computed replicated (they are ~free next to the blocks).
    Numerics are identical to the sequential ``models.bert.bert_encode``
    (tests/test_parallel.py pins it).
    """
    from realtime_fraud_detection_tpu.models.bert import (
        BertConfig,
        bert_embed,
        bert_layer,
    )

    if not isinstance(config, BertConfig):
        raise ValueError(
            "parallel/pipeline.bert_pipeline_encode pipelines DistilBERT's "
            f"post-LN blocks; it does not hold a {type(config).__name__} "
            "encoder")
    n_stages = mesh.shape[axis]
    if config.num_layers % n_stages:
        raise ValueError(
            f"num_layers={config.num_layers} not divisible by the "
            f"{axis}-axis size {n_stages}")
    span = config.num_layers // n_stages
    b, s = input_ids.shape
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro={n_micro}")

    x = bert_embed(params, input_ids, config)

    stage_params = stack_stage_params([
        {"layers": params["layers"][i * span:(i + 1) * span]}
        for i in range(n_stages)
    ])
    mb = b // n_micro
    micro_x = x.reshape(n_micro, mb, s, config.hidden_size)
    micro_mask = attention_mask.reshape(n_micro, mb, s)

    def stage_fn(p, h):
        hid, mask = h
        for layer in p["layers"]:
            hid = bert_layer(layer, hid, mask, config,
                             use_pallas=use_pallas)
        return (hid, mask)

    out_x, _ = pipeline_forward(
        mesh, stage_fn, stage_params, (micro_x, micro_mask), axis=axis)
    return out_x.reshape(b, s, config.hidden_size)
