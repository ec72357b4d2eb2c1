"""Transfer packing (core/packing.py) and the packed scoring seam.

The packed path turns a 65-leaf microbatch transfer into three dense
buffers; correctness requirement: byte-exact round trip and score
equivalence with the unpacked ``score_fused`` program.
"""

import jax
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.packing import (
    PackSpec,
    pack_tree,
    unpack_tree,
)
from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
from realtime_fraud_detection_tpu.scoring.pipeline import (
    MODEL_NAMES,
    OUT_COLUMNS,
    ScorerConfig,
    init_scoring_models,
    make_example_batch,
    score_fused,
    score_fused_packed,
)
from realtime_fraud_detection_tpu.utils.config import Config


@pytest.fixture(scope="module")
def batch():
    return make_example_batch(8, ScorerConfig(), rng=np.random.default_rng(7))


def test_pack_unpack_round_trip_exact(batch):
    blobs, spec = pack_tree(batch)
    assert set(blobs) == {"f32", "i32", "u8", "bf16"}
    assert blobs["bf16"].shape == (8, 0)  # nothing opted into bf16 transfer
    assert all(b.shape[0] == 8 for b in blobs.values())
    restored = unpack_tree(blobs, spec)
    orig_leaves = jax.tree_util.tree_flatten(batch)[0]
    new_leaves = jax.tree_util.tree_flatten(restored)[0]
    assert len(orig_leaves) == len(new_leaves)
    for a, b in zip(orig_leaves, new_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_pack_spec_hashable_and_stable(batch):
    _, s1 = pack_tree(batch)
    _, s2 = pack_tree(batch)
    assert isinstance(s1, PackSpec)
    assert s1 == s2 and hash(s1) == hash(s2)


def test_packed_scoring_matches_dict_path(batch):
    models = init_scoring_models(jax.random.PRNGKey(0))
    params = EnsembleParams.from_config(Config(), list(MODEL_NAMES))
    valid = np.ones((len(MODEL_NAMES),), bool)

    ref = score_fused(models, batch, params, jax.numpy.asarray(valid),
                      bert_config=TINY_CONFIG)
    blobs, spec = pack_tree(batch)
    mat = np.asarray(score_fused_packed(
        models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
        params=params, model_valid=jax.numpy.asarray(valid),
        bert_config=TINY_CONFIG))

    assert mat.shape == (8, len(OUT_COLUMNS) + len(MODEL_NAMES))
    for j, name in enumerate(OUT_COLUMNS):
        np.testing.assert_allclose(
            mat[:, j], np.asarray(ref[name], np.float32), rtol=1e-5,
            atol=1e-6, err_msg=name)
    np.testing.assert_allclose(
        mat[:, len(OUT_COLUMNS):], np.asarray(ref["model_predictions"]),
        rtol=1e-5, atol=1e-6)


def test_bf16_transfer_scores_close_to_f32():
    """transfer_bf16 halves the big tensors on the wire; scores must stay
    within bf16 resolution of the f32 path."""
    import ml_dtypes

    from realtime_fraud_detection_tpu.scoring.scorer import FraudScorer
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator

    gen = TransactionGenerator(num_users=64, num_merchants=16, seed=5)
    records = gen.generate_batch(16)

    def scores(bf16: bool):
        scorer = FraudScorer(seed=0)
        scorer.sc.transfer_bf16 = bf16
        scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        return np.asarray(
            [r["fraud_probability"] for r in scorer.score_batch(records)])

    f32_scores, bf16_scores = scores(False), scores(True)
    np.testing.assert_allclose(bf16_scores, f32_scores, atol=0.02)


def test_bf16_leaves_ride_the_half_width_blob():
    import ml_dtypes

    tree = {
        "big": np.ones((4, 8), np.float32).astype(ml_dtypes.bfloat16),
        "small": np.ones((4, 2), np.float32),
    }
    blobs, spec = pack_tree(tree)
    assert blobs["bf16"].shape == (4, 8)
    assert blobs["bf16"].dtype == np.dtype(ml_dtypes.bfloat16)
    assert blobs["f32"].shape == (4, 2)
    restored = unpack_tree(blobs, spec)
    assert restored["big"].dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(np.asarray(restored["small"]),
                                  tree["small"])


def test_pack_rejects_ragged_leading_dim():
    tree = {"a": np.zeros((4, 3), np.float32), "b": np.zeros((5,), np.int32)}
    with pytest.raises(ValueError):
        pack_tree(tree)
