"""The five-branch ensemble with JoyAI-LLM-Flash's block as its text branch:
the architecture of a configuration file that names ``"builder":
"joyai_builder"``.

The file's keys are ``jdopensource/JoyAI-LLM-Flash``'s own (DeepSeek-V3's,
letter for letter). The scorer is built through the seam ``rtfd serve``
uses; the only things made here are the weights, on the device in one jitted
call from the seed (bfloat16, tensor by tensor: no float32 copy of the 5.3 B
parameters exists).

The construction seam is ``olmoe_builder.py``'s with the model module's
config class ``models/joyai.JoyaiConfig``: the CLASS of the text
configuration picks the encoder (``scoring/pipeline.text_predict``,
``routed_text``); there is no flag. Every routed expert of a layer is held
(``ep_size`` 1, as published: no ``expert_share``), so the program's second
small output is ``i32[sparse layers]``, the largest group of each sparse
layer (``StreamJob.counters['expert_peak_rows']``), and ``['expert_rows']``
is the host's mask count, beside ``['attn_visible_pairs_full']`` from the
rows' lengths. One of the source's names means something else to the
program's routed-encoder seam (``scoring/pipeline.RoutedText`` reads
``intermediate_size`` as ONE expert's width): the file keeps the source's
meaning (``intermediate_size`` 7,168 is layer 0's dense MLP) and
``joyai_config`` hands it over as ``dense_intermediate_size``. What the file
holds and the program does not run is refused by value, not ignored:
``rope_scaling`` must be null, ``n_group`` and ``topk_group`` 1,
``scoring_func`` sigmoid, ``topk_method`` noaux_tc (``JoyaiConfig`` raises);
``num_nextn_predict_layers`` is the one key read by nothing (``not_run`` in
the file says why).

A program without that module (the parent of the PR that added it) cannot
run this configuration: loading this builder then stops the run at once,
before JAX is imported, with a non-zero exit.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

import numpy as np

from benchmarks.harness import system

if importlib.util.find_spec(
        "realtime_fraud_detection_tpu.models.joyai") is None:
    raise SystemExit(
        "benchmark spec error: builder 'joyai_builder' needs "
        "realtime_fraud_detection_tpu/models/joyai.py, which this program "
        "does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` the JoyAI block
# (``attn_latent`` is what latent attention puts in front of the
# projections; ``ffn`` is layer 0's dense MLP; the sparse layers have
# ``router``, ``experts`` and ``shared_expert``)
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "attn_latent": {}, "attn_proj": {}, "attn_core": {}, "ln": {},
            "ffn": {}, "router": {}, "shared_expert": {},
            "experts": {"dispatch": {}, "matmul": {}, "combine": {}},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# The head keeps its three unlike widths (scores over 32 + 16, values of
# 32); small enough that a loaded CPU completes several batches of 32 rows
# in a rehearsal's three seconds
TINY = {"hidden_size": 128, "intermediate_size": 256,
        "moe_intermediate_size": 64, "q_lora_rank": 96, "kv_lora_rank": 32,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "qk_head_dim": 48,
        "v_head_dim": 32, "head_dim": 16, "num_attention_heads": 2,
        "num_key_value_heads": 2, "n_routed_experts": 16}

_SAME = ("vocab_size", "hidden_size", "moe_intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
         "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
         "moe_layer_freq", "n_group", "topk_group", "norm_topk_prob",
         "routed_scaling_factor", "scoring_func", "topk_method",
         "rope_theta", "rope_interleave", "rope_scaling", "rms_norm_eps",
         "max_position_embeddings")


def joyai_config(cfg: Dict[str, Any]):
    """``JoyaiConfig`` from the published ``config.json`` keys of the
    file."""
    from realtime_fraud_detection_tpu.models.joyai import JoyaiConfig

    if cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["ep_size"] != 1:
        raise ValueError("joyai_builder: attention biases, an activation "
                         "other than silu or experts spread over ranks "
                         "(ep_size) are not what the equations hold")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"] \
            or cfg["head_dim"] != cfg["qk_rope_head_dim"]:
        raise ValueError(
            f"joyai_builder: qk_head_dim {cfg['qk_head_dim']} and head_dim "
            f"{cfg['head_dim']} against qk_nope_head_dim "
            f"{cfg['qk_nope_head_dim']} + qk_rope_head_dim "
            f"{cfg['qk_rope_head_dim']}")
    return JoyaiConfig(dense_intermediate_size=cfg["intermediate_size"],
                       **{k: cfg[k] for k in _SAME})


def make_models(cfg: Dict[str, Any], seed: int, sample_features: np.ndarray):
    """All five branches, made on the device in one jitted call from the
    seed; trees and isolation forest then replaced by seeded ensembles of
    the same sizes split at quantiles of ``sample_features``."""
    import jax

    from realtime_fraud_detection_tpu.scoring import ScorerConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    sc = ScorerConfig()
    a = cfg["assumed"]
    config = joyai_config(cfg)

    def init_joyai_scoring_models(key):
        # a named program: the compile ledger reads jit(<this name>)
        return init_scoring_models(
            key, bert_config=config, feature_dim=sc.feature_dim,
            node_dim=sc.node_dim, n_trees=a["n_trees"],
            tree_depth=a["tree_depth"])

    return system.seeded_forests(
        jax.jit(init_joyai_scoring_models)(jax.random.PRNGKey(seed)), cfg,
        seed, sample_features)


def make_scorer(cfg: Dict[str, Any], seed: int, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0   # no fixed-port listener
    scorer = FraudScorer(
        config, models=models, bert_config=joyai_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def text_matmul_flops_per_row(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one row of ``text_len`` real tokens needs in the layers
    run, by part: 2 x M x N x K per matmul."""
    t, h = cfg["text_len"], cfg["hidden_size"]
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    heads, qk, dv = (cfg["num_attention_heads"], cfg["qk_head_dim"],
                     cfg["v_head_dim"])
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    width = cfg["moe_intermediate_size"]
    return {
        # W_qa and W_kva: into the two latents
        "latent": n * 2.0 * t * h * (q_rank + kv_rank
                                     + cfg["qk_rope_head_dim"]),
        # W_qb, W_kvb out of them, and W_o
        "projections": n * 2.0 * t * heads * (
            q_rank * qk + kv_rank * (cfg["qk_nope_head_dim"] + dv) + dv * h),
        # a visible pair: a score over qk dims and a value of dv, a head
        "cores": n * 2.0 * heads * (qk + dv) * (t * (t + 1) // 2),
        "dense_mlp": dense * 6.0 * t * h * cfg["intermediate_size"],
        "router": (n - dense) * 2.0 * t * h * cfg["n_routed_experts"],
        "experts": (n - dense) * 6.0 * t * h * width
        * cfg["num_experts_per_tok"],
        "shared_expert": (n - dense) * 6.0 * t * h * width
        * cfg["n_shared_experts"],
    }


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs with
    every slot real (``matmul_util_pct``): the latent down-projections, the
    up-projections and ``W_o``, the cores' visible (query, key) pairs (the
    kernel skips what a causal query cannot see), layer 0's dense MLP, the
    routers, the eight routed experts a token and the shared one — plus the
    LSTM and GNN as ``harness/flops.py`` counts them. **The stale kind**
    (PERF.md section 7, PR 29 (i)): the interface hands a builder the
    configuration alone, not what a batch launched, so this charges padding
    slots as real ones; the roofline shares of this configuration's kernels
    follow the program's counters instead."""
    from benchmarks.harness import flops

    b = cfg["job"]["max_batch"]
    text = sum(text_matmul_flops_per_row(cfg).values())
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["moe_intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=cfg["text_len"], batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
