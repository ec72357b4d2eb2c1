"""The five-branch ensemble with ZAYA1-8B's block as its text branch: the
architecture of a configuration file that names ``"builder":
"zaya1_builder"``.

The file's keys are ``Zyphra/ZAYA1-8B``'s own (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``cca_time0``, ``cca_time1``, ``partial_rotary_factor``,
``rope_parameters`` — every layer is ``hybrid``, so that entry's
``rope_theta`` — ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size`` — one expert's width — ``router_hidden_size``,
``rms_norm_eps``, ``vocab_size``, ``max_position_embeddings``). The scorer
is built through the seam ``rtfd serve`` uses; the only things made here are
the weights, on the device in one jitted call from the seed (bfloat16, tensor
by tensor: no float32 copy of the 5.5 B parameters exists).

The construction seam is ``olmoe_builder.py``'s with the model module's
config class ``models/zaya.ZayaConfig`` in ``OlmoeConfig``'s place: the
CLASS of the text configuration picks the encoder
(``scoring/pipeline.text_predict``, ``routed_text``); there is no flag. The
program returns the routed encoders' second small output (``i32[layers]``,
the largest expert group) that ``FraudScorer`` turns into
``StreamJob.counters['expert_peak_rows']`` beside ``['expert_rows']``,
``['expert_token_slots']`` and ``['compact_batches']``.

A program without that module (the parent of the PR that added it) cannot
run this configuration: loading this builder then stops the run at once,
before JAX is imported, with a non-zero exit.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmarks.harness import system

if importlib.util.find_spec("realtime_fraud_detection_tpu.models.zaya") is None:
    raise SystemExit(
        "benchmark spec error: builder 'zaya1_builder' needs "
        "realtime_fraud_detection_tpu/models/zaya.py, which this program "
        "does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` the ZAYA1 block
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "attn_proj": {}, "attn_mix": {}, "attn_core": {}, "ln": {},
            "router": {},
            "experts": {"dispatch": {}, "matmul": {}, "combine": {}},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# As ``olmoe_builder``'s, less tiny than the dense encoder's: the rehearsal
# sizes a mix's backlog for 300 txn/s, which has to outlast the window on a
# CPU
TINY = {"hidden_size": 256, "moe_intermediate_size": 256,
        "num_hidden_layers": 4, "head_dim": 32, "router_hidden_size": 64}

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "cca_time0", "cca_time1", "partial_rotary_factor", "num_experts",
         "num_experts_per_tok", "moe_intermediate_size",
         "router_hidden_size", "rms_norm_eps", "max_position_embeddings")


def zaya_config(cfg: Dict[str, Any]):
    """``ZayaConfig`` from the published ``config.json`` keys of the file."""
    from realtime_fraud_detection_tpu.models.zaya import ZayaConfig

    layers = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(layers) != {"hybrid"}:
        raise ValueError(f"zaya1_builder holds hybrid layers only: {layers}")
    return ZayaConfig(
        rope_theta=cfg["rope_parameters"]["hybrid"]["rope_theta"],
        **{k: cfg[k] for k in _KEYS})


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
    init = jax.jit(functools.partial(
        init_scoring_models, bert_config=zaya_config(cfg),
        feature_dim=sc.feature_dim, node_dim=sc.node_dim,
        n_trees=a["n_trees"], tree_depth=a["tree_depth"]))
    return system.seeded_forests(init(jax.random.PRNGKey(seed)), cfg, seed,
                                 sample_features)


def make_scorer(cfg: Dict[str, Any], seed: int, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0   # no fixed-port listener
    scorer = FraudScorer(
        config, models=models, bert_config=zaya_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def text_matmul_flops_per_token(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one (row, position) slot needs in one layer, attention
    core aside: 2 x M x N x K per matmul."""
    h, d, r = cfg["hidden_size"], cfg["head_dim"], cfg["router_hidden_size"]
    q_w = cfg["num_attention_heads"] * d
    kv_w = cfg["num_key_value_heads"] * d
    return {
        # W_Q, W_K, the two value heads' W_V, W_O
        "projections": 2.0 * h * (q_w + 2 * kv_w + q_w),
        # every tap of every head's grouped convolution
        "convolution": 2.0 * cfg["cca_time1"] * d * (q_w + kv_w),
        "router": 2.0 * (h * r + 2 * r * r + r * cfg["num_experts"]),
        "experts": (2.0 * 3 * h * cfg["moe_intermediate_size"]
                    * cfg["num_experts_per_tok"]),
    }


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs with
    every slot real (``matmul_util_pct``): the sparse encoder's count — only
    the expert a token is routed to is charged — plus the attention core
    (the whole T x T scores and weighted sums of the 8 query heads: the
    program computes the masked half too) and the LSTM and GNN as
    ``harness/flops.py`` counts them. The interface hands a builder the
    configuration alone, not what a batch launched: where the routed blocks
    run at a narrow capacity this charges slots they did not touch, as
    ``olmoe_builder``'s does (PERF.md section 7)."""
    from benchmarks.harness import flops

    t, b = cfg["text_len"], cfg["job"]["max_batch"]
    per_token = sum(text_matmul_flops_per_token(cfg).values())
    attn = (2.0 * 2 * t * t * cfg["num_attention_heads"] * cfg["head_dim"])
    text = cfg["num_hidden_layers"] * (t * per_token + attn)
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["moe_intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=t, batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
