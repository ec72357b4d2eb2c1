"""The five-branch ensemble with NVIDIA-Nemotron-3-Nano-30B-A3B's stack as
its text branch: the architecture of a configuration file that names
``"builder": "nemotron3_builder"``.

The file's keys are ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s own,
every one of them, and ``models/nemotron_h.NemotronHConfig`` holds each
under the same name: ``nemotron3_config`` hands the file's values over key
for key. The scorer is built through the seam ``rtfd serve`` uses; the only
things made here are the weights, on the device in one jitted call from the
seed (bfloat16, tensor by tensor: no float32 copy of the 5.7 B parameters
exists).

The construction seam is ``olmoe_builder.py``'s: the CLASS of the text
configuration picks the encoder (``scoring/pipeline.TEXT_ENCODERS``); there
is no flag. This encoder's layers are ONE mixer each by
``hybrid_override_pattern`` — ``M`` a Mamba-2 mixer, ``E`` routed experts
beside a shared one, ``*`` attention — so it is routed (capacity rungs, the
program's second small output ``i32[3, E layers]``,
``StreamJob.counters['expert_rows']`` ...) AND state-space
(``['ssm_chunks']``, counted over the ``M`` layers) at once. What the file
holds and the program does not run is refused by value, not ignored
(``NemotronHConfig`` raises on a bias, a window, groups of experts, a
pattern letter it does not know ...); the keys read by nothing are listed
under ``not_run`` in the file, each with its reason.

A program without that module (the parent of the PR that added it) cannot
run this configuration: loading this builder then stops the run at once,
before JAX is imported, with a non-zero exit.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

import numpy as np

from benchmarks.harness import spec, system

if importlib.util.find_spec(
        "realtime_fraud_detection_tpu.models.nemotron_h") is None:
    raise SystemExit(
        "benchmark spec error: builder 'nemotron3_builder' needs "
        "realtime_fraud_detection_tpu/models/nemotron_h.py, which this "
        "program does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` a layer's one
# norm (``ln``) and the scopes of its KIND: a Mamba-2 layer ``ssm_proj``,
# ``ssm_conv``, ``ssm_scan``; an attention layer ``attn_proj``,
# ``attn_core``; a routed layer ``router``, ``experts``, ``shared_expert``
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "ln": {},
            "ssm_proj": {}, "ssm_conv": {}, "ssm_scan": {},
            "attn_proj": {}, "attn_core": {},
            "router": {}, "shared_expert": {},
            "experts": {"dispatch": {}, "matmul": {}, "combine": {}},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# The odd shapes stay odd (an expert width and a hidden size that are no
# whole lane or sublane tiles, heads of 64 in groups of 8, sixteen query
# heads a key-value head); a chunk of 32 so that a rehearsal's 128
# positions are four chunks
TINY = {"hidden_size": 384, "intermediate_size": 144,
        "moe_intermediate_size": 144,
        "moe_shared_expert_intermediate_size": 288, "n_routed_experts": 16,
        "num_experts_per_tok": 4, "mamba_num_heads": 16, "n_groups": 2,
        "ssm_state_size": 32, "chunk_size": 32, "head_dim": 16}


def nemotron3_config(cfg: Dict[str, Any]):
    """``NemotronHConfig`` from the published ``config.json`` keys of the
    file: every key of ``published``, under its own name."""
    from realtime_fraud_detection_tpu.models.nemotron_h import (
        NemotronHConfig,
    )

    if cfg["tie_word_embeddings"]:
        raise ValueError("nemotron3_builder: tied embeddings are not what "
                         "the file's not_run says of the language-model head")
    return NemotronHConfig(**{key: cfg[key] for key in cfg["published"]})


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
    config = nemotron3_config(cfg)

    def init_nemotron3_scoring_models(key):
        # a named program: the compile ledger reads jit(<this name>)
        return init_scoring_models(
            key, bert_config=config, feature_dim=sc.feature_dim,
            node_dim=sc.node_dim, n_trees=a["n_trees"],
            tree_depth=a["tree_depth"])

    return system.seeded_forests(
        jax.jit(init_nemotron3_scoring_models)(jax.random.PRNGKey(seed)), cfg,
        seed, sample_features)


def make_scorer(cfg: Dict[str, Any], seed: int, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0   # no fixed-port listener
    scorer = FraudScorer(
        config, models=models, bert_config=nemotron3_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def text_matmul_flops_per_row(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one row of ``text_len`` real tokens needs in the layers
    run, by part: 2 x M x N x K per matmul, each part times the layers of
    its kind."""
    t, h = cfg["text_len"], cfg["hidden_size"]
    pattern = cfg["hybrid_override_pattern"]
    mamba, routed, attn = (pattern.count(kind) for kind in "ME*")
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    in_proj = (2 * d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
               + cfg["mamba_num_heads"])
    width = cfg["moe_intermediate_size"]
    return {
        # W_in and W_out of the mixer
        "ssm_proj": mamba * 2.0 * t * h * (in_proj + d_inner),
        # the chunked algorithm's count, kept with the scan's roofline share
        "ssm_scan": mamba * t * spec.kernel(
            "nemotron3_ssd_scan").flops_per_slot(cfg),
        # q, k, v and o
        "attn_proj": attn * 2.0 * t * h * d * (2 * heads + 2 * kv),
        # a visible pair: a score and a weighted value over d dims, a head
        "cores": attn * 2.0 * 2.0 * heads * d * (t * (t + 1) // 2),
        "router": routed * 2.0 * t * h * cfg["n_routed_experts"],
        # up and down: no gate
        "experts": routed * 4.0 * t * h * width * cfg["num_experts_per_tok"],
        "shared_expert": routed * 4.0 * t * h
        * cfg["moe_shared_expert_intermediate_size"],
    }


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs with
    every slot real (``matmul_util_pct``): the mixers' two projections and
    their scans, attention's four projections and its core's visible
    (query, key) pairs, the routers, the six routed experts a token and the
    shared one — plus the LSTM and GNN as ``harness/flops.py`` counts them.
    **The stale kind** (PERF.md section 7, PR 29 (i)): the interface hands
    a builder the configuration alone, not what a batch launched, so this
    charges padding slots as real ones, which is right of the ``M`` layers
    (they compute every slot) and not of the others; the roofline shares of
    this configuration's kernels follow the program's counters instead."""
    from benchmarks.harness import flops

    b = cfg["job"]["max_batch"]
    text = sum(text_matmul_flops_per_row(cfg).values())
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["moe_intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=cfg["text_len"], batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
