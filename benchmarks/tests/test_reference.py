"""The plain reference against the program computed in float32, branch by
branch, on the CPU: the two share no code, so agreement to float32
rounding says both implement the same model. (Against the program as it is
served — bfloat16 in two branches — the gap is what ``parity_atol`` in the
configuration files is set from.)"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("ensemble_reference")


def _rng(seed=0):
    return np.random.default_rng(seed)


def test_text_branch_is_the_programs_at_float32():
    from realtime_fraud_detection_tpu.models.bert import (
        TINY_CONFIG,
        bert_predict,
        init_bert_params,
    )

    params = init_bert_params(jax.random.PRNGKey(3), TINY_CONFIG)
    rng = _rng(3)
    ids = rng.integers(1000, 30000, (6, 48)).astype(np.int32)
    mask = np.arange(48)[None, :] < rng.integers(4, 49, 6)[:, None]
    got = ref.text_branch(jax.device_get(params), ids, mask,
                          n_heads=TINY_CONFIG.num_heads)
    want = bert_predict(params, jnp.asarray(ids), jnp.asarray(mask),
                        TINY_CONFIG, compute_dtype=jnp.float32)
    # the program's GELU is the tanh approximation, the reference's the
    # published erf form: worth ~1e-5 on the probability
    assert np.abs(got - np.asarray(want)).max() < 5e-5


def test_quantized_weights_are_refused_not_compared():
    from realtime_fraud_detection_tpu.models.bert import (
        TINY_CONFIG,
        init_bert_params,
    )
    from realtime_fraud_detection_tpu.models.quant import quantize_bert_params

    q = jax.device_get(quantize_bert_params(
        init_bert_params(jax.random.PRNGKey(0), TINY_CONFIG)))
    with pytest.raises((ValueError, KeyError, TypeError)):
        ref.text_branch(q, np.zeros((2, 8), np.int32), np.ones((2, 8), bool),
                        n_heads=TINY_CONFIG.num_heads)


@pytest.mark.parametrize("scale", [1.0, 1e3])
def test_sequence_branch_is_the_programs_at_float32(scale):
    from realtime_fraud_detection_tpu.models.lstm import (
        init_lstm_params,
        lstm_logits,
    )

    params = init_lstm_params(jax.random.PRNGKey(1))
    rng = _rng(1)
    seq = (rng.normal(size=(16, 10, 64)) * scale).astype(np.float32)
    length = rng.integers(0, 11, 16).astype(np.int32)
    got = ref.sequence_branch(jax.device_get(params), seq, length)
    want = jax.nn.sigmoid(lstm_logits(params, jnp.asarray(seq),
                                      jnp.asarray(length),
                                      compute_dtype=jnp.float32))
    assert np.abs(got - np.asarray(want)).max() < 1e-6


def test_trees_and_isolation_forest_reach_the_programs_leaves():
    from realtime_fraud_detection_tpu.models.isolation_forest import (
        IsolationForest,
        iforest_predict,
    )
    from realtime_fraud_detection_tpu.models.trees import (
        TreeEnsemble,
        tree_ensemble_predict,
    )

    rng = _rng(2)
    x = rng.normal(size=(64, 20)).astype(np.float32)
    x[:8, 3] = 0.25                     # rows ON a threshold go right

    def splits(depth):
        feature = rng.integers(0, 20, (30, 2 ** depth - 1)).astype(np.int32)
        threshold = rng.normal(size=feature.shape).astype(np.float32)
        threshold[feature == 3] = 0.25
        return jnp.asarray(feature), jnp.asarray(threshold)

    f, t = splits(5)
    trees = TreeEnsemble(
        feature=f, threshold=t, base_score=jnp.asarray(-1.0, jnp.float32),
        leaf=jnp.asarray(rng.normal(0, 0.2, (30, 32)).astype(np.float32)))
    assert np.abs(ref.trees_branch(jax.device_get(trees), x) - np.asarray(
        tree_ensemble_predict(trees, jnp.asarray(x)))).max() < 1e-6
    f, t = splits(6)
    forest = IsolationForest(
        feature=f, threshold=t, c_psi=jnp.asarray(8.0, jnp.float32),
        path_length=jnp.asarray(rng.uniform(3, 12, (30, 64)), jnp.float32))
    assert np.abs(ref.isolation_branch(jax.device_get(forest), x) - np.asarray(
        iforest_predict(forest, jnp.asarray(x)))).max() < 1e-6


def test_graph_branch_is_the_programs():
    from realtime_fraud_detection_tpu.models.gnn import (
        gnn_logits,
        init_gnn_params,
    )

    params = init_gnn_params(jax.random.PRNGKey(4), node_dim=16, txn_dim=64)
    rng = _rng(4)
    b, k = 12, 16
    batch = types.SimpleNamespace(
        features=rng.normal(size=(b, 64)).astype(np.float32) * 0.1,
        user_feat=rng.normal(size=(b, 16)).astype(np.float32) * 0.1,
        merchant_feat=rng.normal(size=(b, 16)).astype(np.float32) * 0.1,
        user_neigh_feat=rng.normal(size=(b, k, 16)).astype(np.float32) * 0.1,
        user_neigh_mask=rng.random((b, k)) < 0.5,
        merch_neigh_feat=rng.normal(size=(b, k, 16)).astype(np.float32) * 0.1,
        merch_neigh_mask=rng.random((b, k)) < 0.5,
        user_neigh2_feat=None)
    batch.user_neigh_mask[0] = False            # a user with no neighbours
    want = jax.nn.sigmoid(gnn_logits(
        params, *(jnp.asarray(getattr(batch, n)) for n in (
            "features", "user_feat", "merchant_feat", "user_neigh_feat",
            "user_neigh_mask", "merch_neigh_feat", "merch_neigh_mask"))))
    got = ref.graph_branch(jax.device_get(params), batch)
    assert 0.01 < float(np.std(got))            # not saturated: a real test
    assert np.abs(got - np.asarray(want)).max() < 1e-6


def test_blend_and_ladder_are_the_programs():
    from realtime_fraud_detection_tpu.ensemble.combine import (
        EnsembleParams,
        combine_predictions,
    )
    from realtime_fraud_detection_tpu.scoring.pipeline import MODEL_NAMES
    from realtime_fraud_detection_tpu.utils.config import Config

    assert tuple(MODEL_NAMES) == ref.BRANCHES
    params = EnsembleParams.from_config(Config(), list(MODEL_NAMES))
    rng = _rng(5)
    preds = rng.random((400, 5)).astype(np.float32) ** 0.3   # reach the rungs
    valid = rng.random((400, 5)) < 0.8
    valid[0] = False                                # no branch answered
    want = combine_predictions(jnp.asarray(preds), jnp.asarray(valid), params)
    got = ref.blend(preds, valid, params)
    for name in ("fraud_probability", "confidence"):
        assert np.abs(got[name] - np.asarray(want[name])).max() < 1e-6
    assert len(set(got["decision"].tolist())) >= 3
    near = np.abs(got["fraud_probability"][:, None] - np.array(
        [0.6, 0.8, 0.95])[None, :]).min(axis=1) < 1e-5
    near |= np.abs(got["confidence"] - 0.7) < 1e-5
    assert (got["decision"] == np.asarray(want["decision"]))[~near].all()


def test_rule_table_is_the_programs_on_generated_events():
    import json

    from benchmarks.harness import events as E
    from realtime_fraud_detection_tpu.features.rules import rule_score
    from realtime_fraud_detection_tpu.features.schema import (
        encode_transactions,
    )

    traffic = json.loads(
        (spec.BENCH / "traffic" / "s64-saturated.json").read_text())
    traffic["pool_events"] = 512
    rng = _rng(6)
    pop = E.Population(300, 60, rng)
    pool = E.build_pool(pop, traffic, rng)
    events = pool.materialize(range(512), np.arange(512) * 200.0)
    users, merchants = pop.user_profiles(), pop.merchant_profiles()
    for uid in list(users)[:40]:
        del users[uid]                  # unknown users take the other arm
    txn = encode_transactions(events, users, merchants)
    got = ref.rule_score(jax.device_get(txn))
    assert float(np.std(got)) > 0.02
    assert np.abs(got - np.asarray(rule_score(txn))).max() < 1e-6


def test_score_reads_its_sizes_from_the_configuration_file():
    """``score(..., cfg)`` returns what composing the branches by hand with
    ``n_heads`` passed in returns (the signature it had while the harness
    read the widths): on a TINY scorer made by the default builder, from
    the assembled inputs of generated events."""
    import json

    from benchmarks.harness import events as E
    from benchmarks.harness import system

    cfg = json.loads(
        (spec.BENCH / "configs" / "distilbert-s64.json").read_text())
    builder = spec.builder(cfg)
    cfg.update(builder.TINY)
    traffic = json.loads(
        (spec.BENCH / "traffic" / "s64-saturated.json").read_text())
    traffic["pool_events"] = 64
    rng = _rng(11)
    pop = E.Population(300, 40, rng)
    pool = E.build_pool(pop, traffic, rng)
    users, merchants = pop.user_profiles(), pop.merchant_profiles()
    recs = pool.materialize(range(16), np.zeros(16))
    models = builder.make_models(
        cfg, 11, system.event_features(recs, users, merchants))
    scorer = builder.make_scorer(cfg, 11, models, users, merchants)
    models, batch = jax.device_get((scorer.models, scorer.assemble(recs)))
    params, valid = scorer.ensemble_params, scorer.effective_model_valid()

    got = ref.score(models, batch, params, valid, cfg)
    preds = np.stack([
        ref.trees_branch(models.trees, batch.features),
        ref.sequence_branch(models.lstm, batch.history, batch.history_len),
        ref.text_branch(models.bert, batch.token_ids, batch.token_mask,
                        n_heads=cfg["n_heads"]),
        ref.graph_branch(models.gnn, batch),
        ref.isolation_branch(models.iforest, batch.features)], axis=1)
    want = ref.blend(preds, np.asarray(valid, bool)[None, :]
                     & np.asarray(batch.valid, bool)[:, None], params)
    assert set(got) == {"fraud_probability", "confidence", "decision",
                        "rungs", "branches", "rule_score"}
    np.testing.assert_array_equal(got["branches"], preds)
    for name in ("fraud_probability", "confidence", "decision"):
        np.testing.assert_array_equal(got[name], want[name])
    assert got["branches"][:, 2].std() > 0.0     # the text rows differ
    with pytest.raises(KeyError):
        ref.score(models, batch, params, valid, {"num_attention_heads": 2})
