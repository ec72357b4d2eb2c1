"""``configs/zaya1_reference.py`` against the program computed in float32 on
the CPU (``test_reference.py``'s pattern, for the third reference): the two
share no code, so agreement to float32 rounding says both implement the
same block; and ``score`` reads what it needs from the configuration
file."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("zaya1_reference")
CFG_FILE = json.loads(
    (spec.BENCH / "configs" / "zaya1-8b-s128.json").read_text())
BUILDER = spec.builder(CFG_FILE)
TINY = {**CFG_FILE, **BUILDER.TINY}


def _stirred(params, seed):
    """Norm weights, temperatures, gamma and the balancing bias moved off
    the one / zero a random initialisation leaves them at, and the router's
    matrices scaled up: at TINY's 64-wide router initializer_range 0.02
    sends every token of a layer to one expert."""
    rng = np.random.default_rng(seed)

    def stir(path, x):
        if path[-1].key == "router_bias":
            return x + jnp.asarray(rng.normal(0, 0.05, x.shape), x.dtype)
        if path[-1].key in ("router_w1", "router_w2", "router_w3"):
            return (x.astype(jnp.float32) * 8.0).astype(x.dtype)
        if x.ndim == 1:
            return x * jnp.asarray(rng.uniform(0.5, 1.5, x.shape), x.dtype)
        return x

    params = dict(params)
    params["layers"] = [jax.tree_util.tree_map_with_path(stir, layer)
                        for layer in params["layers"]]
    return params


@pytest.mark.parametrize("seed", [3, 3000000011])
def test_text_branch_is_the_programs_at_float32(seed):
    from realtime_fraud_detection_tpu.models.zaya import (
        init_zaya_params,
        zaya_predict,
    )

    config = BUILDER.zaya_config(TINY)
    params = _stirred(init_zaya_params(
        jax.random.PRNGKey(seed % 2 ** 31), config), seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(1000, 30000, (6, 48)).astype(np.int32)
    mask = np.arange(48)[None, :] < rng.integers(1, 49, 6)[:, None]
    trace = []
    got = ref.text_branch(jax.device_get(params), ids, mask, TINY,
                          trace=trace)
    with jax.default_matmul_precision("highest"):
        want = zaya_predict(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params),
            jnp.asarray(ids), jnp.asarray(mask), config)
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    assert got.std() > 0.0
    assert len(trace) == TINY["num_hidden_layers"]
    assert all(t.shape == (6 * 48, 1) for t in trace)
    # random routing at 16 experts spreads the tokens
    assert len(np.unique(np.concatenate(trace))) > 4


def test_the_lowering_seam_reaches_every_matmul_but_the_routers():
    """``_matmul`` is what ``zaya1_control.py`` lowers: every projection,
    convolution tap and expert matmul goes through it, the router does
    not."""
    from realtime_fraud_detection_tpu.models.zaya import init_zaya_params

    # a router width and an expert width no other matrix has
    tiny = {**TINY, "num_hidden_layers": 1, "router_hidden_size": 40,
            "moe_intermediate_size": 96}
    config = BUILDER.zaya_config(tiny)
    params = jax.device_get(init_zaya_params(jax.random.PRNGKey(0), config))
    ids = np.arange(12, dtype=np.int32).reshape(2, 6) + 1000
    mask = np.ones((2, 6), bool)
    shapes, plain = [], ref._matmul
    ref._matmul = lambda x, w: (shapes.append(w.shape), plain(x, w))[1]
    try:
        ref.text_branch(params, ids, mask, tiny)
    finally:
        ref._matmul = plain
    h, d, r = tiny["hidden_size"], tiny["head_dim"], 40
    assert shapes.count((d, d)) == 10 * TINY["cca_time1"]
    for w in ((h, 8 * d), (h, 2 * d), (h, d), (8 * d, h)):
        assert w in shapes, w
    assert all(r not in w for w in shapes)
    # gate and up, then down, for each expert that got a token
    assert shapes.count((h, 96)) == 2 * shapes.count((96, h)) > 0


def test_score_composes_the_branches_and_reads_the_configuration_file():
    from benchmarks.harness import events as E
    from benchmarks.harness import system

    traffic = json.loads(
        (spec.BENCH / "traffic" / "s128-memo-saturated.json").read_text())
    traffic["pool_events"] = 64
    rng = np.random.default_rng(11)
    pop = E.Population(300, 40, rng)
    pool = E.build_pool(pop, traffic, rng)
    users, merchants = pop.user_profiles(), pop.merchant_profiles()
    recs = pool.materialize(range(8), np.zeros(8))
    models = BUILDER.make_models(
        TINY, 11, system.event_features(recs, users, merchants))
    scorer = BUILDER.make_scorer(TINY, 11, models, users, merchants)
    models, batch = jax.device_get((scorer.models, scorer.assemble(recs)))
    params, valid = scorer.ensemble_params, scorer.effective_model_valid()
    got = ref.score(models, batch, params, valid, TINY)
    assert set(got) == {"fraud_probability", "confidence", "decision",
                        "rungs", "branches", "rule_score"}
    np.testing.assert_array_equal(
        got["branches"][:, 2],
        ref.text_branch(models.bert, batch.token_ids, batch.token_mask, TINY))
    assert got["branches"].shape == (len(batch.valid), len(ref.BRANCHES))
    assert got["branches"][:8, 2].std() > 0.0
    with pytest.raises(KeyError):
        ref.score(models, batch, params, valid, {"num_attention_heads": 8})
