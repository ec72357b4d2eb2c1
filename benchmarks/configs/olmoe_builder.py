"""The five-branch ensemble with OLMoE-1B-7B's sparse-expert block as its
text branch: the architecture of a configuration file that names
``"builder": "olmoe_builder"``.

The file's keys are ``allenai/OLMoE-1B-7B-0125-Instruct``'s own
(``hidden_size``, ``intermediate_size`` — one expert's width —
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``num_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``rms_norm_eps``, ``rope_theta``, ``vocab_size``,
``max_position_embeddings``). The scorer is built through the seam ``rtfd
serve`` uses; the only things made here are the weights, on the device in
one jitted call from the seed (bfloat16, tensor by tensor: no float32 copy of
the 3.4 B parameters exists).

The construction seam this builder uses, beside what
``ensemble_builder.py`` names (``init_scoring_models``, ``FraudScorer``,
``ScorerConfig``, ``Config``, ``build_mesh``, ``harness/system.py``): the
model module's config class ``models/olmoe.OlmoeConfig``, handed to
``init_scoring_models(bert_config=...)`` and ``FraudScorer(bert_config=...)``
— the CLASS of the text configuration picks the encoder
(``scoring/pipeline.text_predict``); there is no flag. The MoE program
returns a second small output (``i32[layers]``, the largest expert group)
that ``FraudScorer`` turns into ``StreamJob.counters['expert_peak_rows']``
beside ``['expert_rows']``. (``benchmarks/README.md`` asks a builder to name
a new seam there; editing it is a ``benchmark`` issue's, so it is named here
and in ``PERF.md`` section 3.)

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

if importlib.util.find_spec("realtime_fraud_detection_tpu.models.olmoe") is None:
    raise SystemExit(
        "benchmark spec error: builder 'olmoe_builder' needs "
        "realtime_fraud_detection_tpu/models/olmoe.py, which this program "
        "does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` the MoE block
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "attn_proj": {}, "attn_core": {}, "ln": {}, "router": {},
            "experts": {"dispatch": {}, "matmul": {}, "combine": {}},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# Less tiny than the dense encoder's: the rehearsal sizes a new mix's backlog
# for 300 txn/s, which has to outlast the window on a CPU
TINY = {"hidden_size": 256, "intermediate_size": 128, "num_hidden_layers": 4,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_experts": 16, "num_experts_per_tok": 4}


def olmoe_config(cfg: Dict[str, Any]):
    """``OlmoeConfig`` from the published ``config.json`` keys of the file."""
    from realtime_fraud_detection_tpu.models.olmoe import OlmoeConfig

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "rope_theta",
            "max_position_embeddings")
    return OlmoeConfig(**{k: cfg[k] for k in keys})


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
        init_scoring_models, bert_config=olmoe_config(cfg),
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
        config, models=models, bert_config=olmoe_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def text_matmul_flops_per_token(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one (row, position) slot needs in one layer, attention
    core aside: 2 x M x N x K per matmul."""
    h, i_ = cfg["hidden_size"], cfg["intermediate_size"]
    return {
        "projections": 2.0 * 4 * h * h,                       # q, k, v, o
        "router": 2.0 * h * cfg["num_experts"],
        "experts": 2.0 * 3 * h * i_ * cfg["num_experts_per_tok"],
    }


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs
    (``matmul_util_pct``): the sparse encoder's count — only the experts a
    token is routed to are charged — plus the attention core (the whole
    T x T scores and weighted sums: the program computes the masked half
    too) and the LSTM and GNN as ``harness/flops.py`` counts them."""
    from benchmarks.harness import flops

    t, b = cfg["text_len"], cfg["job"]["max_batch"]
    per_token = sum(text_matmul_flops_per_token(cfg).values())
    attn = 2.0 * 2 * t * t * cfg["hidden_size"]
    text = cfg["num_hidden_layers"] * (t * per_token + attn)
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=t, batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
