"""What every architecture's system under test shares.

Models and scorer are made by the configuration's builder
(``configs/<builder>.py``, ``spec.builder``); here is what is the same for
all of them: the job with the ``JobConfig`` the file spells out (what ``rtfd
run-job`` builds with no flags, plus the pool switches for the four-chip
deployment), the buckets a cell's traffic reaches, the features of a sample
of events, and the tree weights: ``init_scoring_models`` leaves trees and
isolation forest at zero (every row in one leaf), so a seeded ensemble
split at feature quantiles of this run's own events stands in, as
``chip_smoke.check_gemm_trees`` does.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

# the tracer keeps its newest completed traces; large enough that a stage
# median is over several seconds of the window, not its last batches
TRACER_RING = 32768


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


def seeded_forests(models, cfg: Dict[str, Any], seed: int,
                   sample_features: np.ndarray):
    """``models`` with trees and isolation forest replaced by seeded
    ensembles of the configuration's ``assumed`` sizes, split at quantiles
    of ``sample_features``. Every builder calls this: the two branches are
    the same whatever the text or sequence architecture."""
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.isolation_forest import (
        IsolationForest,
    )
    from realtime_fraud_detection_tpu.models.trees import TreeEnsemble

    a = cfg["assumed"]
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
