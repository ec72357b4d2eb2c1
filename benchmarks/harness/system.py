"""Build the system under test from a configuration file.

The scorer is built through the seam ``rtfd serve`` and
``chip_smoke.make_scorer`` use, the job with the ``JobConfig`` the file
spells out (what ``rtfd run-job`` builds with no flags, plus the pool
switches for the four-chip deployment). The only things made here are the
weights: ``init_scoring_models`` leaves trees and isolation forest at zero
(every row in one leaf), so a seeded ensemble split at feature quantiles of
this run's own events stands in, as ``chip_smoke.check_gemm_trees`` does.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import numpy as np

# the tracer keeps its newest completed traces; large enough that a stage
# median is over several seconds of the window, not its last batches
TRACER_RING = 32768


def bert_config(cfg: Dict[str, Any]):
    """``BertConfig`` from the published ``config.json`` keys of the file."""
    from realtime_fraud_detection_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["dim"],
        num_layers=cfg["n_layers"], num_heads=cfg["n_heads"],
        intermediate_size=cfg["hidden_dim"],
        max_position_embeddings=cfg["max_position_embeddings"])


def event_features(events: Sequence[Dict[str, Any]], users, merchants
                   ) -> np.ndarray:
    """The 64 features of ``events`` with their profiles joined, through
    the program's own encoder and extractor; no scorer state is touched."""
    from realtime_fraud_detection_tpu.features.extract import (
        extract_features_host,
    )
    from realtime_fraud_detection_tpu.features.schema import (
        encode_transactions,
    )

    return np.asarray(extract_features_host(
        encode_transactions(list(events), users, merchants)), np.float32)


def make_models(cfg: Dict[str, Any], seed: int, sample_features: np.ndarray):
    """All five branches, made on the device in one jitted call from the
    seed; trees and isolation forest then replaced by seeded ensembles of
    the same sizes split at quantiles of ``sample_features``."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.isolation_forest import (
        IsolationForest,
    )
    from realtime_fraud_detection_tpu.models.trees import TreeEnsemble
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
    models = init(jax.random.PRNGKey(seed))
    if models.iforest.feature.shape != (a["n_trees"],
                                        2 ** a["iforest_depth"] - 1):
        raise ValueError(
            f"init_scoring_models makes an isolation forest of shape "
            f"{models.iforest.feature.shape}; the configuration assumes "
            f"{a['n_trees']} trees of depth {a['iforest_depth']}")

    rng = np.random.default_rng(seed)
    x = np.sort(np.asarray(sample_features, np.float32), axis=0)

    def splits(n_internal: int):
        feature = rng.integers(0, x.shape[1], (a["n_trees"], n_internal))
        rank = (rng.uniform(0.05, 0.95, feature.shape)
                * (len(x) - 1)).astype(np.int64)
        return feature.astype(np.int32), x[rank, feature].astype(np.float32)

    f_t, th_t = splits(2 ** a["tree_depth"] - 1)
    f_i, th_i = splits(2 ** a["iforest_depth"] - 1)
    trees = TreeEnsemble(
        feature=jnp.asarray(f_t), threshold=jnp.asarray(th_t),
        leaf=jnp.asarray(rng.normal(0.0, 0.1, (
            a["n_trees"], 2 ** a["tree_depth"])).astype(np.float32)),
        base_score=jnp.asarray(-2.0, jnp.float32))
    iforest = IsolationForest(
        feature=jnp.asarray(f_i), threshold=jnp.asarray(th_i),
        path_length=jnp.asarray(rng.uniform(4.0, 12.0, (
            a["n_trees"], 2 ** a["iforest_depth"])).astype(np.float32)),
        c_psi=models.iforest.c_psi)
    return models.replace(trees=trees, iforest=iforest)


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


def make_job(cfg: Dict[str, Any], scorer, traced: bool, broker=None):
    """``StreamJob`` with the file's ``job`` settings over ``broker`` (a new
    ``InMemoryBroker`` when none is given); ``JobConfig.tracing`` on in the
    traced run only."""
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )
    from realtime_fraud_detection_tpu.utils.config import TracingSettings

    if broker is None:
        broker = InMemoryBroker()
    tracing = (TracingSettings(enabled=True, ring_size=TRACER_RING)
               if traced else None)
    job = StreamJob(broker, scorer, JobConfig(tracing=tracing, **cfg["job"]))
    return broker, job


def buckets_hit(cfg: Dict[str, Any], mode: str) -> List[int]:
    """Device buckets a cell's traffic can reach: every bucket up to
    ``max_batch`` below the knee (the batcher decides), only the full one
    from a backlog."""
    from realtime_fraud_detection_tpu.core.batching import BATCH_BUCKETS

    top = cfg["job"]["max_batch"]
    reach = [b for b in BATCH_BUCKETS if b <= top]
    return reach if mode == "open_loop" else reach[-1:]
