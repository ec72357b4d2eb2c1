"""Persistent ensemble megakernel: one Pallas program per microbatch.

PR 15's per-site kernels (dequant-matmul, fused epilogue) still leave the
ensemble as a CHAIN of XLA computations — five branch programs, the rule
program and the blend, each handing its intermediate back through HBM.
This kernel scores an entire packed microbatch end-to-end in ONE Pallas
program: the grid is persistent over batch blocks (TPU grids execute
sequentially on a core, so ``grid=(B/block,)`` IS the persistent loop),
the tree and isolation-forest branches run as Hummingbird GEMM-form
contractions (models/trees.py's compile-time ancestor-structure
constants, arXiv:2010.04804), per-branch probabilities accumulate in a
VMEM scratch lane, and the fused epilogue's combine math
(ops/epilogue.combine_matrix — one definition, two kernels) is inlined
as the final stage. The kernel's output IS the extended
[B, 8 + M + M + 2] packed matrix ``FraudScorer._build_responses``
already reads — branch intermediates never exist in HBM.

QoS ladder rungs arrive as ``mega_valid``: a compile-time tuple of
branch-validity booleans. Disabled branches are pruned at trace time
(their prediction lane is written as zero and their weight masked in the
blend, exactly like the runtime mask), so each rung is its own cached
program — the jit cache is the per-rung program cache, and rung changes
never retrace an already-visited rung.

``megakernel_reference`` is a verbatim composition of the very branch
functions the kernel replaces (same functions, same GEMM tree form, no
Pallas) — the parity oracle for the CPU interpreter drill. The
``mega_plan``/``mega_supported`` predicates are shared by the trace-time
guard in scoring/pipeline.py and the host-side fallback accounting in
FraudScorer, so a trace-time fallback to the PR 15 per-site kernels is
always mirrored by ``kernel_fallback_total``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realtime_fraud_detection_tpu.ops.epilogue import combine_matrix

# NOTE: model-branch modules (models/*, features/rules, scoring/pipeline)
# are imported lazily inside functions: models.bert imports ops.attention,
# so a module-level import here would cycle through ops/__init__ while
# models.bert is still initializing.

# Per-core VMEM is ~16 MiB; budget leaves headroom for Mosaic's own
# staging. The block-row working set (activations x block + resident
# params) must fit under this for a block size to be eligible.
_MEGA_VMEM_BUDGET = 14 * (1 << 20)

# Largest-first candidates; a block must divide the bucket size exactly
# (buckets are powers of two, core/batching.py) so the grid tiles B.
MEGA_BLOCK_CANDIDATES: Tuple[int, ...] = (128, 64, 32, 16, 8)

# Below this the launch chain is already cheap and padding waste dominates
# — bucket 1 stays on the per-site kernel path (an honest fallback).
MEGA_MIN_BATCH = 8

# What the TPU compiler says to this kernel (Mosaic, JAX 0.9.0 / libtpu
# 0.0.34, described v5e:2x2 topology — tests/test_aot_tpu.py re-asks on
# every run). With the rank-1 batch operands restaged as [B, 1] the block
# specs pass and the BODY is refused at its first branch, the GEMM-form
# tree contraction; the three constructs behind it are each refused alone
# as well. None of these is a restaging: the body inlines whole branch
# programs Mosaic has no lowering for, so on a TPU mesh the kernel is
# interpret-only (CPU) and FraudScorer refuses ``megakernel="pallas"``.
MEGA_TPU_REFUSAL = (
    "Mosaic cannot lower the kernel body: the tree/iforest contraction "
    "einsum('btl,tl->bt') has no TPU dot_dimension_numbers form "
    "(\"Unable to parse attribute ... lhs_non_contracting_dims\"); behind "
    "it the in-kernel embedding gather (\"Shape mismatch in input, "
    "indices and output\"), the LSTM lax.scan (NotImplementedError) and "
    "the 4-D attention einsums (\"'tpu.matmul' op Not implemented: Up to "
    "1 batch dim supported\") are refused too")


def _unwrap(fn):
    """The traceable body of a jitted branch function: calling the jit
    wrapper inside a Pallas kernel would nest dispatch; the unwrapped
    function is the same math."""
    return getattr(fn, "__wrapped__", fn)


def mega_param_bytes(models) -> int:
    """Resident parameter bytes for the whole 5-branch pytree. Shape/dtype
    only — works on tracers and concrete arrays alike."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(models):
        total += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    return int(total)


def mega_act_row_bytes(bert_config, *, text_len: int, seq_len: int,
                       feature_dim: int, tree_onehot: int) -> int:
    """Per-batch-row activation working set (bytes, f32) — the dominant
    simultaneous intermediates inside one block iteration:

    - BERT: hidden + residual + FFN activations ``S*(2H+F)`` plus the
      attention probability tensor ``heads*S^2`` per row;
    - trees + iforest: the GEMM one-hot leaf tensors ``T*L`` per ensemble
      (``tree_onehot`` = sum over both);
    - LSTM: the ``T*F`` history slab the scan walks.

    docs/kernels.md reproduces this budget math per bucket size.
    """
    h = bert_config.hidden_size
    f = bert_config.intermediate_size
    bert = text_len * (2 * h + f) * 4 + bert_config.num_heads * text_len * text_len * 4
    trees = tree_onehot * 4
    lstm = seq_len * feature_dim * 4
    return int(bert + trees + lstm + feature_dim * 4)


def mega_block(b: int, param_bytes: int, act_row_bytes: int) -> int:
    """Largest block size that divides ``b`` and fits the VMEM budget;
    0 when none does (caller must fall back)."""
    for cand in MEGA_BLOCK_CANDIDATES:
        if b % cand:
            continue
        if cand * act_row_bytes + param_bytes <= _MEGA_VMEM_BUDGET:
            return cand
    return 0


def mega_supported(b: int, param_bytes: int, act_row_bytes: int,
                   has_two_hop: bool = False) -> bool:
    """True when the megakernel handles a ``b``-row microbatch. Shared by
    the trace-time guard in scoring/pipeline.py and the host-side
    fallback counting in FraudScorer._record_kernel_dispatch, so the two
    always agree. Two-hop typed-graph frontiers ([B, K, K2, D]) blow the
    per-row budget and stay on the per-site path."""
    return (b >= MEGA_MIN_BATCH and not has_two_hop
            and mega_block(b, param_bytes, act_row_bytes) > 0)


def mega_plan(models, bert_config, *, b: int, text_len: int, seq_len: int,
              feature_dim: int, has_two_hop: bool) -> Dict[str, Any]:
    """One shared shape/VMEM plan for a dispatch: the same numbers feed
    the trace-time fallback and the host-side counters."""
    pb = mega_param_bytes(models)
    t1, l1 = models.trees.leaf.shape
    t2, l2 = models.iforest.path_length.shape
    arb = mega_act_row_bytes(bert_config, text_len=text_len,
                             seq_len=seq_len, feature_dim=feature_dim,
                             tree_onehot=t1 * l1 + t2 * l2)
    return {
        "param_bytes": pb,
        "act_row_bytes": arb,
        "block": mega_block(b, pb, arb),
        "has_two_hop": bool(has_two_hop),
        "supported": mega_supported(b, pb, arb, has_two_hop),
    }


def mega_launch_accounting(b: int, m: int,
                           mega_valid: Optional[Sequence[bool]] = None
                           ) -> Dict[str, int]:
    """Launch-count / HBM-traffic accounting: the chain dispatches one
    program per enabled branch plus the rule program and the blend; the
    megakernel dispatches ONE. ``intermediate_bytes_eliminated`` counts
    the branch-boundary tensors that previously round-tripped through
    HBM between those programs (per-branch prediction vectors, the
    stacked [B, M] matrix, the validity mask and the rule score)."""
    valid = tuple(mega_valid) if mega_valid is not None else (True,) * m
    branches = sum(1 for v in valid if v)
    programs_chain = branches + 2
    eliminated = (branches * b * 4    # per-branch f32[B] predictions
                  + b * m * 4         # stacked preds f32[B, M]
                  + b * m * 4         # validity mask f32[B, M]
                  + b * 4)            # rule score f32[B]
    return {
        "programs_chain": int(programs_chain),
        "programs_mega": 1,
        "launches_per_batch_chain": int(programs_chain),
        "launches_per_batch_mega": 1,
        "intermediate_bytes_eliminated": int(eliminated),
    }


def _branch_columns(models, batch, mega_valid: Tuple[bool, ...],
                    bert_config, tree_paths=None, iforest_paths=None) -> list:
    """The five branch probabilities, GEMM tree form, in registry order —
    the SAME composition inside the kernel body and in the reference.
    Rung-disabled branches are pruned at trace time (zero lane). The
    ``*_paths`` operands carry the ancestor-structure constants into the
    Pallas body (models/trees.py); None = the lru_cached defaults."""
    from realtime_fraud_detection_tpu.models.bert import bert_predict
    from realtime_fraud_detection_tpu.models.gnn import gnn_logits
    from realtime_fraud_detection_tpu.models.isolation_forest import (
        iforest_predict,
    )
    from realtime_fraud_detection_tpu.models.lstm import lstm_logits
    from realtime_fraud_detection_tpu.models.trees import tree_ensemble_predict

    features = batch.features
    zeros = jnp.zeros((features.shape[0],), jnp.float32)
    return [
        _unwrap(tree_ensemble_predict)(models.trees, features, kernel="gemm",
                                       paths=tree_paths)
        if mega_valid[0] else zeros,
        jax.nn.sigmoid(
            _unwrap(lstm_logits)(models.lstm, batch.history,
                                 batch.history_len))
        if mega_valid[1] else zeros,
        bert_predict(models.bert, batch.token_ids, batch.token_mask,
                     bert_config, use_pallas=False)
        if mega_valid[2] else zeros,
        jax.nn.sigmoid(
            gnn_logits(models.gnn, features, batch.user_feat,
                       batch.merchant_feat, batch.user_neigh_feat,
                       batch.user_neigh_mask, batch.merch_neigh_feat,
                       batch.merch_neigh_mask))
        if mega_valid[3] else zeros,
        _unwrap(iforest_predict)(models.iforest, features, kernel="gemm",
                                 paths=iforest_paths)
        if mega_valid[4] else zeros,
    ]


def _packed_tail(preds, ep, rule, txn, m: int) -> jax.Array:
    """Assemble the extended packed matrix from the blend output — the
    layout scoring/pipeline.py's OUT_COLUMNS + preds + EXT_COLUMNS."""
    from realtime_fraud_detection_tpu.scoring.pipeline import _key_factors

    kf = _key_factors(txn)
    head = jnp.concatenate([
        ep[:, 0:4],
        rule[:, None],
        kf["high_amount"].astype(jnp.float32)[:, None],
        kf["unusual_hour"].astype(jnp.float32)[:, None],
        kf["high_risk_payment"].astype(jnp.float32)[:, None],
    ], axis=1)
    return jnp.concatenate(
        [head, preds.astype(jnp.float32), ep[:, 4:4 + m],
         ep[:, 4 + m:6 + m]], axis=1)


def megakernel_reference(models, batch, params, *,
                         mega_valid: Tuple[bool, ...],
                         bert_config=None) -> jax.Array:
    """XLA oracle: the exact branch functions + combine the kernel fuses,
    composed as a plain chain -> the same extended packed f32[B, 2M+10]
    matrix. Rung-disabled branches are pruned identically."""
    from realtime_fraud_detection_tpu.features.rules import rule_score
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG

    bert_config = bert_config or TINY_CONFIG
    mega_valid = tuple(bool(v) for v in mega_valid)
    m = len(mega_valid)
    preds = jnp.stack(
        _branch_columns(models, batch, mega_valid, bert_config), axis=1)
    rule = rule_score(batch.txn)
    mvf = jnp.asarray(mega_valid, jnp.float32)
    vf = batch.valid.astype(jnp.float32)[:, None] * mvf[None, :]
    ep = combine_matrix(
        preds.astype(jnp.float32), vf, rule.astype(jnp.float32)[:, None],
        params.weights.astype(jnp.float32)[None, :],
        params.confidence_multipliers.astype(jnp.float32)[None, :],
        strategy=int(params.strategy),
        fraud_threshold=float(params.fraud_threshold),        # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        confidence_threshold=float(params.confidence_threshold),  # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        decline=float(params.decline_threshold),              # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        review=float(params.review_threshold),                # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        monitor=float(params.monitor_threshold))              # rtfd-lint: allow[d2h] static host field (pytree_node=False)
    return _packed_tail(preds, ep, rule, batch.txn, m)


def _row_block_map(nd: int):
    return lambda i, _nd=nd: (i,) + (0,) * (_nd - 1)


def _whole_map(nd: int):
    return lambda i, _nd=nd: (0,) * max(_nd, 1)


@functools.partial(jax.jit, static_argnames=(
    "mega_valid", "bert_config", "block", "strategy", "fraud_threshold",
    "confidence_threshold", "decline", "review", "monitor", "interpret"))
def _mega_call(models, batch, w2, cm2, *, mega_valid, bert_config, block,
               strategy, fraud_threshold, confidence_threshold, decline,
               review, monitor, interpret):
    from realtime_fraud_detection_tpu.features.rules import rule_score
    from realtime_fraud_detection_tpu.models.trees import _complete_tree_paths

    batch_leaves, batch_def = jax.tree_util.tree_flatten(batch)
    model_leaves, model_def = jax.tree_util.tree_flatten(models)
    b = int(batch_leaves[0].shape[0])
    m = len(mega_valid)
    width = 2 * m + 10  # OUT_COLUMNS(8) + preds(M) + contributions(M) + 2

    # A kernel body cannot close over concrete arrays, so everything it
    # reads rides as an operand: the branch params, the blend vectors,
    # the QoS validity mask, and the Hummingbird ancestor-structure
    # constants for both tree ensembles (models/trees.py).
    mvf2 = jnp.asarray(
        [1.0 if v else 0.0 for v in mega_valid], jnp.float32)[None, :]
    tc, td = _complete_tree_paths(int(np.log2(models.trees.leaf.shape[1])))
    ic, idx = _complete_tree_paths(
        int(np.log2(models.iforest.path_length.shape[1])))
    extra = [w2, cm2, mvf2, jnp.asarray(tc), jnp.asarray(td),
             jnp.asarray(ic), jnp.asarray(idx)]
    n_extra = len(extra)

    # Pallas operand staging: bools ride as i32 (restored inside), rank-1
    # batch leaves ride as [B, 1] (Mosaic refuses a row-blocked rank-1
    # operand; same restaging as ops/dequant_matmul.py's scale/bias), 0-d
    # param leaves (tree base_score, iforest c_psi) ride as shape-(1,).
    batch_meta = []
    staged_batch = []
    for leaf in batch_leaves:
        arr = jnp.asarray(leaf)
        batch_meta.append((arr.dtype, arr.ndim == 1))
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int32)
        staged_batch.append(arr[:, None] if arr.ndim == 1 else arr)
    param_meta = []
    staged_params = []
    for leaf in list(model_leaves) + extra:
        arr = jnp.asarray(leaf)
        param_meta.append(arr.ndim == 0)
        staged_params.append(arr.reshape(1) if arr.ndim == 0 else arr)

    nb = len(staged_batch)
    npar = len(staged_params)
    in_specs = (
        [pl.BlockSpec((block,) + a.shape[1:], _row_block_map(a.ndim))
         for a in staged_batch]
        + [pl.BlockSpec(a.shape, _whole_map(a.ndim)) for a in staged_params]
    )

    def body(*refs):
        b_refs, p_refs = refs[:nb], refs[nb:nb + npar]
        o_ref, preds_ref = refs[nb + npar], refs[nb + npar + 1]
        bl = []
        for ref, (dt, was_vector) in zip(b_refs, batch_meta):
            v = ref[...]
            if was_vector:
                v = v[:, 0]
            bl.append(v != 0 if dt == jnp.bool_ else v)
        blk_batch = jax.tree_util.tree_unflatten(batch_def, bl)
        pv = []
        for ref, was_scalar in zip(p_refs, param_meta):
            v = ref[...]
            pv.append(v.reshape(()) if was_scalar else v)
        blk_models = jax.tree_util.tree_unflatten(
            model_def, pv[:-n_extra])
        wv, cmv, mvf, k_tc, k_td, k_ic, k_id = pv[-n_extra:]

        # branch stage: each enabled branch writes its VMEM scratch lane
        cols = _branch_columns(blk_models, blk_batch, mega_valid,
                               bert_config, tree_paths=(k_tc, k_td),
                               iforest_paths=(k_ic, k_id))
        for j in range(m):
            preds_ref[:, j] = cols[j].astype(jnp.float32)
        preds = preds_ref[...]

        # epilogue stage, inlined (ops/epilogue.combine_matrix)
        rule = _unwrap(rule_score)(blk_batch.txn).astype(jnp.float32)
        vf = blk_batch.valid.astype(jnp.float32)[:, None] * mvf
        ep = combine_matrix(
            preds, vf, rule[:, None], wv, cmv, strategy=strategy,
            fraud_threshold=fraud_threshold,
            confidence_threshold=confidence_threshold, decline=decline,
            review=review, monitor=monitor)
        o_ref[...] = _packed_tail(preds, ep, rule, blk_batch.txn, m)

    return pl.pallas_call(
        body,
        grid=(b // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, m), jnp.float32)],
        interpret=interpret,
    )(*staged_batch, *staged_params)


def fused_megakernel(models, batch, params, *,
                     mega_valid: Tuple[bool, ...], bert_config=None,
                     interpret: bool = False,
                     block: Optional[int] = None) -> jax.Array:
    """Score a whole microbatch in one persistent Pallas program.

    Returns the extended packed f32[B, 2M+10] matrix (OUT_COLUMNS, model
    predictions, contributions, rule_decision/rule_risk) — exactly what
    ``FraudScorer._build_responses`` reads. ``mega_valid`` is the QoS
    rung as a static branch-validity tuple; each distinct rung compiles
    (and caches) its own pruned program. Callers must pre-check
    ``mega_supported``/``mega_plan`` — unsupported shapes raise.
    """
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG

    bert_config = bert_config or TINY_CONFIG
    mega_valid = tuple(bool(v) for v in mega_valid)
    b = int(batch.features.shape[0])
    if block is None:
        plan = mega_plan(
            models, bert_config, b=b,
            text_len=int(batch.token_ids.shape[1]),
            seq_len=int(batch.history.shape[1]),
            feature_dim=int(batch.features.shape[1]),
            has_two_hop=batch.user_neigh2_feat is not None)
        if not plan["supported"]:
            raise ValueError(
                f"unsupported megakernel dispatch b={b} plan={plan} "
                "(callers must pre-check mega_supported)")
        block = plan["block"]
    if b % block:
        raise ValueError(f"block {block} does not tile batch {b}")
    return _mega_call(
        models, batch,
        params.weights.astype(jnp.float32)[None, :],
        params.confidence_multipliers.astype(jnp.float32)[None, :],
        mega_valid=mega_valid, bert_config=bert_config, block=int(block),
        strategy=int(params.strategy),
        fraud_threshold=float(params.fraud_threshold),        # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        confidence_threshold=float(params.confidence_threshold),  # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        decline=float(params.decline_threshold),              # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        review=float(params.review_threshold),                # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        monitor=float(params.monitor_threshold),              # rtfd-lint: allow[d2h] static host field (pytree_node=False)
        interpret=interpret)
