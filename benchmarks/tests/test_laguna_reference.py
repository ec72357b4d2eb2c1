"""``configs/laguna_reference.py`` against the program computed in float32 on
the CPU (``test_reference.py``'s pattern, for the fourth reference): the two
share no code, so agreement to float32 rounding says both implement the
same block — unlike layers, both kinds of RoPE, the window, the gate, the
share of the experts; the lowering seam reaches every matmul but the
router's; and ``score`` reads what it needs from the configuration file."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("laguna_reference")
CFG_FILE = json.loads(
    (spec.BENCH / "configs" / "laguna-s-2.1-s2048.json").read_text())
BUILDER = spec.builder(CFG_FILE)
TINY = {**CFG_FILE, **BUILDER.TINY}


@pytest.mark.parametrize("index", [0, 3])
@pytest.mark.parametrize("seed", [3, 3300000011])
def test_text_branch_is_the_programs_at_float32(seed, index):
    from realtime_fraud_detection_tpu.models.laguna import (
        init_laguna_params,
        laguna_predict,
    )

    tiny = {**TINY, "expert_share": {"chips": 4, "index": index}}
    config = BUILDER.laguna_config(tiny)
    params = init_laguna_params(jax.random.PRNGKey(seed % 2 ** 31), config)
    rng = np.random.default_rng(seed)
    # 80 positions: past the rehearsal's window of 32
    ids = rng.integers(1000, 30000, (6, 80)).astype(np.int32)
    mask = np.arange(80)[None, :] < rng.integers(1, 81, 6)[:, None]
    trace = []
    got = ref.text_branch(jax.device_get(params), ids, mask, tiny,
                          trace=trace)
    with jax.default_matmul_precision("highest"):
        want = laguna_predict(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params),
            jnp.asarray(ids), jnp.asarray(mask), config)
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    assert got.std() > 0.0
    # one entry a SPARSE layer, top-10 of the router's 32, sorted
    assert len(trace) == 4
    assert all(t.shape == (6 * 80, 10) for t in trace)
    assert (np.diff(trace[0], axis=-1) > 0).all()
    assert len(np.unique(np.concatenate(trace))) > 24


def test_the_lowering_seam_reaches_every_matmul_but_the_routers():
    """``_matmul`` is what ``laguna_control.py`` lowers: every projection,
    both contractions of the core, the dense MLP, the routed and the shared
    experts go through it, the router does not."""
    from realtime_fraud_detection_tpu.models.laguna import init_laguna_params

    # widths no other matrix has: the router's (8 x 5 = 40), an expert's,
    # the shared expert's, the dense MLP's
    tiny = {**TINY, "num_hidden_layers": 2, "num_experts": 8,
            "expert_share": {"chips": 5, "index": 0},
            "moe_intermediate_size": 96,
            "shared_expert_intermediate_size": 160, "intermediate_size": 224}
    config = BUILDER.laguna_config(tiny)
    params = jax.device_get(init_laguna_params(jax.random.PRNGKey(0), config))
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) + 1000
    mask = np.ones((2, 12), bool)
    shapes, plain = [], ref._matmul
    ref._matmul = lambda x, w: (shapes.append(w.shape), plain(x, w))[1]
    try:
        ref.text_branch(params, ids, mask, tiny)
    finally:
        ref._matmul = plain
    h, d = tiny["hidden_size"], tiny["head_dim"]
    for heads in (4, 6):                       # layer 0 full, layer 1 sliding
        for w in ((h, heads * d), (heads * d, h), (h, heads)):
            assert w in shapes, w
    assert shapes.count((h, 2 * d)) == 4       # k and v of both layers
    assert shapes.count((h, 224)) == 2 and shapes.count((224, h)) == 1
    assert shapes.count((h, 160)) == 2 and shapes.count((160, h)) == 1
    assert all(40 not in w for w in shapes)    # the router stays float32
    # gate and up, then down, for each held expert that got a token
    assert shapes.count((h, 96)) == 2 * shapes.count((96, h)) > 0
    # the core: q k^T and p v of each (row, key head, query block)
    assert shapes.count((d, 12)) == 2 * 2 * 2 and (12, d) in shapes


def test_yarn_frequencies_are_step_twos():
    rope = CFG_FILE["rope_parameters"]["full_attention"]
    inv = ref.inv_freq(rope, 64)
    f = 500000.0 ** (np.arange(32) * 2 / 64)
    np.testing.assert_allclose(inv[:10], 1 / f[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[18:], 1 / (128 * f[18:]), rtol=1e-12)
    np.testing.assert_allclose(inv[13], (5 / 9) / f[13]
                               + (4 / 9) / (128 * f[13]), rtol=1e-12)
    sliding = CFG_FILE["rope_parameters"]["sliding_attention"]
    np.testing.assert_allclose(ref.inv_freq(sliding, 128),
                               10000.0 ** (-np.arange(64) * 2 / 128))


def test_score_composes_the_branches_and_reads_the_configuration_file():
    from benchmarks.harness import events as E
    from benchmarks.harness import system

    traffic = json.loads(
        (spec.BENCH / "traffic" / "s2048-remit-saturated.json").read_text())
    traffic["pool_events"] = 64
    traffic["text_tokens"].update(median=60, min=16, max=128)
    rng = np.random.default_rng(11)
    pop = E.Population(300, 40, rng)
    pool = E.build_pool(pop, traffic, rng)
    users, merchants = pop.user_profiles(), pop.merchant_profiles()
    recs = pool.materialize(range(8), np.zeros(8))
    tiny = {**TINY, "text_len": 128}
    models = BUILDER.make_models(
        tiny, 11, system.event_features(recs, users, merchants))
    scorer = BUILDER.make_scorer(tiny, 11, models, users, merchants)
    models, batch = jax.device_get((scorer.models, scorer.assemble(recs)))
    params, valid = scorer.ensemble_params, scorer.effective_model_valid()
    got = ref.score(models, batch, params, valid, tiny)
    assert set(got) == {"fraud_probability", "confidence", "decision",
                        "rungs", "branches", "rule_score"}
    np.testing.assert_array_equal(
        got["branches"][:, 2],
        ref.text_branch(models.bert, batch.token_ids, batch.token_mask, tiny))
    assert got["branches"].shape == (len(batch.valid), len(ref.BRANCHES))
    assert got["branches"][:8, 2].std() > 0.0
    with pytest.raises(KeyError):
        ref.score(models, batch, params, valid, {"num_key_value_heads": 2})
