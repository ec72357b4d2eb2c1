"""``configs/joyai_reference.py`` against the program computed in float32 on
the CPU (``test_reference.py``'s pattern, for the fifth reference): the two
share no code, so agreement to float32 rounding says both implement the
same block — the two latents with their norms, a score over a head's own
dims plus one shared interleaved-rotated key, values narrower than a score,
the leading dense layer, the sigmoid router whose bias moves the choice
alone; the lowering seam reaches every matmul but the router's; and
``score`` reads what it needs from the configuration file."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("joyai_reference")
CFG_FILE = json.loads(
    (spec.BENCH / "configs" / "joyai-llm-flash-s2048.json").read_text())
BUILDER = spec.builder(CFG_FILE)
TINY = {**CFG_FILE, **BUILDER.TINY}


@pytest.mark.parametrize("seed", [3, 4300000011])
def test_text_branch_is_the_programs_at_float32(seed):
    from realtime_fraud_detection_tpu.models.joyai import (
        init_joyai_params,
        joyai_predict,
    )

    config = BUILDER.joyai_config(TINY)
    params = init_joyai_params(jax.random.PRNGKey(seed % 2 ** 31), config)
    rng = np.random.default_rng(seed)
    ids = rng.integers(1000, 30000, (6, 80)).astype(np.int32)
    mask = np.arange(80)[None, :] < rng.integers(1, 81, 6)[:, None]
    trace = []
    got = ref.text_branch(jax.device_get(params), ids, mask, TINY,
                          trace=trace)
    with jax.default_matmul_precision("highest"):
        want = joyai_predict(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params),
            jnp.asarray(ids), jnp.asarray(mask), config)
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    assert got.std() > 0.0
    # one entry a SPARSE layer, top-8 of 16, sorted, beside what the scores
    # alone would have chosen
    assert len(trace) == 4
    assert all(t["chosen"].shape == t["unbiased"].shape == (6 * 80, 8)
               for t in trace)
    assert (np.diff(trace[0]["chosen"], axis=-1) > 0).all()
    assert len(np.unique(np.concatenate(
        [t["chosen"] for t in trace]))) > 12
    real = mask.reshape(-1)
    moved = np.mean([(t["chosen"][real] != t["unbiased"][real]).any(-1)
                     for t in trace])
    assert 0.0 < moved < 1.0


def test_the_lowering_seam_reaches_every_matmul_but_the_routers():
    """``_matmul`` is what ``joyai_control.py`` lowers: the four latent
    projections, W_o, both contractions of a score, the weighted sum, the
    dense MLP, the routed and the shared experts go through it, the router
    does not."""
    from realtime_fraud_detection_tpu.models.joyai import init_joyai_params

    # widths no other matrix has: the router's 40 experts, an expert's 96,
    # the dense MLP's 224, latents of 160 and 48
    tiny = {**TINY, "num_hidden_layers": 2, "n_routed_experts": 40,
            "moe_intermediate_size": 96, "intermediate_size": 224,
            "q_lora_rank": 160, "kv_lora_rank": 48}
    config = BUILDER.joyai_config(tiny)
    params = jax.device_get(init_joyai_params(jax.random.PRNGKey(0), config))
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) + 1000
    mask = np.ones((2, 12), bool)
    shapes, plain = [], ref._matmul
    ref._matmul = lambda x, w: (shapes.append(w.shape), plain(x, w))[1]
    try:
        ref.text_branch(params, ids, mask, tiny)
    finally:
        ref._matmul = plain
    h, heads = tiny["hidden_size"], tiny["num_attention_heads"]
    n, r, dv = 32, 16, 32
    for w in ((h, 160), (160, heads * (n + r)), (h, 48 + r),
              (48, heads * (n + dv)), (heads * dv, h)):
        assert shapes.count(w) == 2, w                 # both layers
    assert shapes.count((h, 224)) == 2 and shapes.count((224, h)) == 1
    assert all(40 not in w for w in shapes)            # the router: float32
    # the shared expert and every routed expert that got a token: gate and
    # up, then down
    assert shapes.count((h, 96)) == 2 * shapes.count((96, h)) > 2
    # the core, a (row, head, query block): q_nope k_nope^T, q_pe k_pe^T, p v
    calls = 2 * 2 * heads
    assert shapes.count((n, 12)) == calls and shapes.count((r, 12)) == calls
    assert shapes.count((12, dv)) == calls


def test_the_reference_refuses_what_its_equations_do_not_hold():
    for change in ({"n_group": 8}, {"scoring_func": "softmax"},
                   {"rope_scaling": {"type": "yarn"}},
                   {"rope_interleave": False}, {"topk_method": "greedy"}):
        with pytest.raises(ValueError, match="noaux_tc router of one group"):
            ref.text_branch({}, np.zeros((1, 4), np.int32),
                            np.ones((1, 4), bool), {**TINY, **change})


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
        ref.score(models, batch, params, valid, {"num_attention_heads": 4})
