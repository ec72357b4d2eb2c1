"""The five-branch ensemble with Falcon-H1's parallel hybrid block as its
text branch: the architecture of a configuration file that names
``"builder": "falconh1_builder"``.

The file's keys are ``tiiuae/Falcon-H1-34B-Instruct``'s own, every one of
them, and ``models/falcon_h1.FalconH1Config`` holds each under the same
name: ``falconh1_config`` hands the file's values over key for key (the two
lists as tuples). The scorer is built through the seam ``rtfd serve`` uses;
the only things made here are the weights, on the device in one jitted call
from the seed (bfloat16, tensor by tensor: no float32 copy of the 3.9 B
parameters exists).

The construction seam is ``olmoe_builder.py``'s: the CLASS of the text
configuration picks the encoder (``scoring/pipeline.text_predict``,
``causal_text``); there is no flag. This encoder is causal and DENSE: no
router, so no capacity rungs (one program a bucket, every slot computed),
no second output, and the counters ``expert_*``, ``routed_pairs`` and
``compact_batches`` stay 0; ``StreamJob.counters['attn_visible_pairs_full']``
comes from the rows' lengths as the routed encoders' does, and
``['ssm_chunks']`` counts the chunks its scans walked. What the file holds
and the program does not run is refused by value, not ignored
(``FalconH1Config`` raises on a bias, a norm before the gate, a
``rope_scaling``...); ``lm_head_multiplier`` and ``num_logits_to_keep`` are
the keys read by nothing (``not_run`` in the file says why).

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
        "realtime_fraud_detection_tpu.models.falcon_h1") is None:
    raise SystemExit(
        "benchmark spec error: builder 'falconh1_builder' needs "
        "realtime_fraud_detection_tpu/models/falcon_h1.py, which this "
        "program does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` the Falcon-H1
# block (``ssm_proj``, ``ssm_conv`` and ``ssm_scan`` are the Mamba-2 mixer
# that runs beside attention; ``ffn`` is the SwiGLU MLP)
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "attn_proj": {}, "attn_core": {}, "ffn": {}, "ln": {},
            "ssm_proj": {}, "ssm_conv": {}, "ssm_scan": {},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# Two groups, more heads than groups, five query heads a key-value head,
# and a chunk of 32 so that a rehearsal's 128 positions are four chunks
TINY = {"hidden_size": 128, "intermediate_size": 256, "head_dim": 16,
        "num_attention_heads": 10, "num_key_value_heads": 2,
        "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_ssm": 64,
        "mamba_d_state": 32, "mamba_n_groups": 2, "mamba_chunk_size": 32}

_LISTS = ("ssm_multipliers", "mlp_multipliers")


def falconh1_config(cfg: Dict[str, Any]):
    """``FalconH1Config`` from the published ``config.json`` keys of the
    file: every key of ``published``, under its own name."""
    from realtime_fraud_detection_tpu.models.falcon_h1 import FalconH1Config

    if cfg["tie_word_embeddings"]:
        raise ValueError("falconh1_builder: tied embeddings are not what "
                         "the file's not_run says of the language-model head")
    return FalconH1Config(**{
        key: tuple(cfg[key]) if key in _LISTS else cfg[key]
        for key in cfg["published"]})


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
    config = falconh1_config(cfg)

    def init_falconh1_scoring_models(key):
        # a named program: the compile ledger reads jit(<this name>)
        return init_scoring_models(
            key, bert_config=config, feature_dim=sc.feature_dim,
            node_dim=sc.node_dim, n_trees=a["n_trees"],
            tree_depth=a["tree_depth"])

    return system.seeded_forests(
        jax.jit(init_falconh1_scoring_models)(jax.random.PRNGKey(seed)), cfg,
        seed, sample_features)


def make_scorer(cfg: Dict[str, Any], seed: int, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0   # no fixed-port listener
    scorer = FraudScorer(
        config, models=models, bert_config=falconh1_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def text_matmul_flops_per_row(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one row of ``text_len`` real tokens needs in the layers
    run, by part: 2 x M x N x K per matmul."""
    t, h, n = cfg["text_len"], cfg["hidden_size"], cfg["num_hidden_layers"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    d_ssm = cfg["mamba_d_ssm"]
    in_proj = (2 * d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
               + cfg["mamba_n_heads"])
    return {
        # W_in and W_out of the mixer
        "ssm_proj": n * 2.0 * t * h * (in_proj + d_ssm),
        # the chunked algorithm's count, kept with the scan's roofline share
        "ssm_scan": n * t * spec.kernel("falconh1_ssd_scan").flops_per_slot(
            cfg),
        # q, k, v and o
        "attn_proj": n * 2.0 * t * h * d * (2 * heads + 2 * kv),
        # a visible pair: a score and a weighted value over d dims, a head
        "cores": n * 2.0 * 2.0 * heads * d * (t * (t + 1) // 2),
        "mlp": n * 6.0 * t * h * cfg["intermediate_size"],
    }


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs with
    every slot real (``matmul_util_pct``): the mixer's two projections and
    its scan, attention's four projections and the cores' visible (query,
    key) pairs (the kernel skips what a causal query cannot see), the MLP —
    plus the LSTM and GNN as ``harness/flops.py`` counts them. A dense
    encoder computes every slot, so charging padding slots as real ones is
    right for all of it but the cores (the interface hands a builder the
    configuration alone, not what a batch launched: PERF.md section 7, PR
    29 (i)); the roofline shares of this configuration's kernels follow the
    program's counters instead."""
    from benchmarks.harness import flops

    b = cfg["job"]["max_batch"]
    text = sum(text_matmul_flops_per_row(cfg).values())
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=cfg["text_len"], batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
