"""``configs/nemotron3_reference.py`` against the program computed in float32
on the CPU (``test_reference.py``'s pattern, for the seventh reference): the
two share no code — the reference walks the recurrence a position at a time,
materialises the softmax and runs every expert over every token; the program
runs the chunked scan and sorted, grouped, capacity-compacted experts — so
agreement to float32 rounding says both implement the same stack: one mixer
a layer by the pattern, the gate before the grouped norm, no rotation, the
bias in the choice and in no weight, relu^2 with no gate. The lowering seam
reaches every matmul of the site it is told; the text column costs four
compilations; and ``score`` reads what it needs from the configuration
file."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("nemotron3_reference")
CFG_FILE = json.loads(
    (spec.BENCH / "configs" / "nemotron-3-nano-30b-s2048.json").read_text())
BUILDER = spec.builder(CFG_FILE)
TINY = {**CFG_FILE, **BUILDER.TINY}


@pytest.mark.parametrize("seed", [3, 5000000011])
def test_nemotron3_text_branch_is_the_programs_at_float32(seed):
    from realtime_fraud_detection_tpu.models.nemotron_h import (
        init_nemotron_h_params,
        nemotron_h_predict,
    )

    config = BUILDER.nemotron3_config(TINY)
    assert config.chunk_size == 32 and config.layer_kinds == tuple(
        "MEMEM*EME")
    params = init_nemotron_h_params(jax.random.PRNGKey(seed % 2 ** 31),
                                    config)
    rng = np.random.default_rng(seed)
    ids = rng.integers(1000, 30000, (6, 80)).astype(np.int32)
    mask = np.arange(80)[None, :] < rng.integers(1, 81, 6)[:, None]
    got, parts = ref.text_branch(jax.device_get(params), ids, mask, TINY,
                                 parts=True)
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      params)
    with jax.default_matmul_precision("highest"):
        for capacity in (None, 384):
            want = nemotron_h_predict(params32, jnp.asarray(ids),
                                      jnp.asarray(mask), config,
                                      capacity=capacity)
            assert np.abs(got - np.asarray(want)).max() < 1e-5, capacity
    assert got.std() > 0.0
    # every layer's update and the residual it is added to, at each row's
    # last real token; an E layer's routed part; the tokens the bias moved
    update, residual, routed, moved = (parts[:, i] for i in range(4))
    assert parts.shape == (9, 4, 6) and (update > 0.0).all() \
        and (residual > 0.0).all()
    sparse = [i for i, kind in enumerate("MEMEM*EME") if kind == "E"]
    others = [i for i in range(9) if i not in sparse]
    assert (routed[sparse] > 0.0).all() and not routed[others].any()
    assert not moved[others].any() and (moved <= 1.0).all()


def test_nemotron3_lowering_seam_reaches_the_site_it_is_told():
    """``operand`` is what ``nemotron3_control.py`` lowers: called on both
    operands of the six projections, of both contractions of the core, on
    ``x``, ``B``, ``C`` and the state each position reads, and on both
    matmuls of the routed and of the shared experts — each under its site's
    name alone; never on the router's."""
    from realtime_fraud_detection_tpu.models.nemotron_h import (
        init_nemotron_h_params,
    )

    tiny = {**TINY, "num_hidden_layers": 3, "hybrid_override_pattern": "ME*"}
    params = jax.device_get(init_nemotron_h_params(
        jax.random.PRNGKey(0), BUILDER.nemotron3_config(tiny)))
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) + 1000
    mask = np.ones((2, 12), bool)
    h, width, shared = 384, 144, 288
    heads, kv, d = 32, 2, 16
    d_inner, gn, m_heads, t = 16 * 64, 2 * 32, 16, 12
    expected = {
        "projections": {(h, 2 * d_inner + 2 * gn + m_heads), (d_inner, h),
                        (h, heads * d), (h, kv * d), (heads * d, h),
                        (t, h), (t, d_inner), (t, heads * d)},
        "core": {(t, heads, d), (heads, t, t)},
        "scan": {(t, d_inner), (t, gn), (m_heads, 64, 32)},
        "routed": {(t, h), (ref.EXPERT_BLOCK, h, width),
                   (ref.EXPERT_BLOCK, t, width),
                   (ref.EXPERT_BLOCK, width, h)},
        "shared": {(t, h), (h, shared), (t, shared), (shared, h)},
    }
    assert set(expected) == ref.SITES
    for site, shapes in expected.items():
        seen = set()

        def operand(x):
            seen.add(tuple(x.shape))
            return x

        ref.text_branch(params, ids, mask, tiny, operand=operand,
                        sites=frozenset((site,)))
        assert seen == shapes, (site, seen ^ shapes)
        # the router's matrix is no operand of any site
        assert (h, 16) not in seen


def test_nemotron3_text_column_costs_four_compilations():
    """One jitted function for an ``M`` layer, one for a ``*`` layer and two
    for an ``E`` layer (``route``, and ``experts`` for a block of experts,
    so that no whole layer of them stands on the device), each called at
    one shape; the embedding's widening and the head are NumPy; no eager
    ``jax.numpy`` call beside them (each would be a program of its own in a
    run's ``setup_programs``)."""
    from realtime_fraud_detection_tpu.models.nemotron_h import (
        init_nemotron_h_params,
    )
    from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

    # sizes no other test compiles: nothing answers from a cache of traces
    tiny = {**TINY, "moe_shared_expert_intermediate_size": 192}
    params = jax.device_get(init_nemotron_h_params(
        jax.random.PRNGKey(1), BUILDER.nemotron3_config(tiny)))
    ids = np.arange(3 * 40, dtype=np.int32).reshape(3, 40) + 1000
    mask = np.arange(40)[None, :] < np.array([40, 7, 23])[:, None]
    ledger = compile_ledger()
    before = len([r for r in ledger.records() if r["phase"] == "compile"])
    ref.text_branch(params, ids, mask, tiny)
    programs = [r["program"] for r in ledger.records()
                if r["phase"] == "compile"][before:]
    assert sorted(programs) == ["jit(attention)", "jit(experts)",
                                "jit(mamba)", "jit(route)"]


def test_nemotron3_reference_refuses_what_its_equations_do_not_hold():
    blank = ({}, np.zeros((1, 4), np.int32), np.ones((1, 4), bool))
    for change in ({"hybrid_override_pattern": "MEMEM-EME"},
                   {"hybrid_override_pattern": "MEMEM*EM"}):
        with pytest.raises(ValueError, match="kinds M, E and"):
            ref.text_branch(*blank, {**TINY, **change})
    for change in ({"attention_bias": True}, {"use_conv_bias": False},
                   {"mlp_hidden_act": "silu"}, {"sliding_window": 512},
                   {"n_group": 2}, {"topk_group": 2},
                   {"norm_topk_prob": False}, {"n_routed_experts": 12}):
        with pytest.raises(ValueError, match="relu2 experts without a gate"):
            ref.text_branch(*blank, {**TINY, **change})
    with pytest.raises(ValueError, match="two readings"):
        ref.text_branch(*blank, {**TINY, "hidden_size": 512})


def test_nemotron3_reference_imports_nothing_from_the_package():
    source = (spec.BENCH / "configs" / "nemotron3_reference.py").read_text()
    assert "import realtime_fraud_detection_tpu" not in source
    assert "from realtime_fraud_detection_tpu" not in source
    code = source.split('"""', 2)[2]
    assert "lax.scan(position" in code and "chunk" not in code
    assert "rope" not in code and "ragged" not in code


def test_nemotron3_score_composes_the_branches_and_reads_the_file():
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
