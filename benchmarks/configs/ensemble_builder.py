"""The five-branch ensemble with a DistilBERT-keyed text branch: the
architecture of every configuration file that names no ``builder``.

The file's width keys are ``distilbert-base-uncased``'s own (``dim``,
``n_layers``, ``n_heads``, ``hidden_dim``, ``vocab_size``,
``max_position_embeddings``). The scorer is built through the seam ``rtfd
serve`` and ``chip_smoke.make_scorer`` use; the only things made here are
the weights, on the device in one jitted call from the seed.

A builder uses only the program's construction seam —
``init_scoring_models``, ``FraudScorer``, ``ScorerConfig``, ``Config``,
``build_mesh`` and the model modules' config classes — and the harness's
common parts (``harness/system.py``, ``harness/flops.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from benchmarks.harness import flops, scopes, system

# the device scopes this architecture's program writes (obs/scopes.py)
VOCABULARY = scopes.ENSEMBLE_VOCABULARY

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths
TINY = {"dim": 128, "n_layers": 2, "n_heads": 2, "hidden_dim": 256}


def bert_config(cfg: Dict[str, Any]):
    """``BertConfig`` from the published ``config.json`` keys of the file."""
    from realtime_fraud_detection_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["dim"],
        num_layers=cfg["n_layers"], num_heads=cfg["n_heads"],
        intermediate_size=cfg["hidden_dim"],
        max_position_embeddings=cfg["max_position_embeddings"])


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
        init_scoring_models, bert_config=bert_config(cfg),
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
        config, models=models, bert_config=bert_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs
    (``matmul_util_pct``): a dense encoder's count."""
    return flops.ensemble_matmul_flops(
        hidden=cfg["dim"], intermediate=cfg["hidden_dim"],
        layers=cfg["n_layers"], text_len=cfg["text_len"],
        batch=cfg["job"]["max_batch"])["total"]
