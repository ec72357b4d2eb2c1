"""``configs/qwen3next_reference.py`` against the program computed in float32
on the CPU (``test_reference.py``'s pattern, for the eighth reference): the
two share no code — the reference walks the delta rule a position at a time,
materialises the softmax and runs every held expert over every token; the
program runs the chunked WY scan and sorted, grouped, capacity-compacted
experts — so agreement to float32 rounding says both implement the same
stack: three delta layers to one attention layer by the interval, zero-
centred norms, the gated norm's order, a quarter of a head rotated, an
elementwise gate, the shared expert under its gate, the same share of the
experts. The lowering seam reaches every matmul of the site it is told; the
text column costs four compilations; and ``score`` reads what it needs from
the configuration file."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("qwen3next_reference")
CFG_FILE = json.loads(
    (spec.BENCH / "configs" / "qwen3-next-80b-a3b-s2048.json").read_text())
BUILDER = spec.builder(CFG_FILE)
TINY = {**CFG_FILE, **BUILDER.TINY}


@pytest.mark.parametrize("seed", [3, 5000000011])
def test_qwen3next_text_branch_is_the_programs_at_float32(seed):
    from realtime_fraud_detection_tpu.models.qwen3_next import (
        init_qwen3_next_params,
        qwen3_next_predict,
    )

    config = BUILDER.qwen3next_config(TINY)
    assert config.delta_chunk == 16 and config.layer_kinds == tuple("LLLFLL")
    assert (config.router_experts, config.num_experts,
            config.expert_offset) == (32, 16, 0)
    params = init_qwen3_next_params(jax.random.PRNGKey(seed % 2 ** 31),
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
            want = qwen3_next_predict(params32, jnp.asarray(ids),
                                      jnp.asarray(mask), config,
                                      capacity=capacity)
            assert np.abs(got - np.asarray(want)).max() < 1e-5, capacity
    assert got.std() > 0.0
    # both halves of every layer's update and the residual the first is
    # added to, at each row's last real token; the routed part of the second
    mixer, residual, sparse, routed, held, margin = (parts[:, i]
                                                     for i in range(6))
    assert parts.shape == (6, 6, 6) and (mixer > 0.0).all() \
        and (residual > 0.0).all() and (sparse > 0.0).all()
    assert (routed >= 0.0).all() and routed.max() > 0.0
    # half the router's experts are held: a token's held mass lies in [0, 1]
    # and differs by the token, and no routed part comes of no held mass
    assert (held >= 0.0).all() and (held <= 1.0 + 1e-6).all() \
        and held.std() > 0.0 and (routed[held == 0.0] < 1e-6).all()
    # the tenth rank against the eleventh: a positive gap of logits where
    # the two straddle the share, infinity where they do not; both occur
    assert (margin > 0.0).all() and np.isinf(margin).any() \
        and np.isfinite(margin).any()


def test_qwen3next_lowering_seam_reaches_the_site_it_is_told():
    """``operand`` is what ``qwen3next_control.py`` lowers: called on both
    operands of the projections of both mixers, of both contractions of the
    core, on ``q``, ``k``, ``v`` and the state each position reads, and on
    the three matmuls of the routed and of the shared experts — each under
    its site's name alone; never on the router's or the shared gate's."""
    from realtime_fraud_detection_tpu.models.qwen3_next import (
        init_qwen3_next_params,
    )

    tiny = {**TINY, "num_hidden_layers": 2, "full_attention_interval": 2}
    params = jax.device_get(init_qwen3_next_params(
        jax.random.PRNGKey(0), BUILDER.qwen3next_config(tiny)))
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) + 1000
    mask = np.ones((2, 12), bool)
    h, width, shared, t = 256, 128, 128, 12
    heads, kv, d = 16, 2, 32
    hk, hv, dk, dv = 4, 8, 32, 32
    expected = {
        "projections": {(h, 2 * hk * dk + 2 * hv * dv), (h, 2 * hv),
                        (hv * dv, h), (h, 2 * heads * d), (h, kv * d),
                        (heads * d, h), (t, h), (t, hv * dv),
                        (t, heads * d)},
        "core": {(t, heads, d), (heads, t, t)},
        "scan": {(t, hk, dk), (t, hv, dv), (hv, dk, dv)},
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
        # the shared gate's vector is an operand of no site (nor is the
        # router's matrix, which at these widths has k_proj's shape)
        assert (h, 1) not in seen


def test_qwen3next_text_column_costs_four_compilations():
    """One jitted function for an ``L`` layer's mixer half, one for an
    ``F`` layer's and two for a sparse half (``route``, and ``experts`` for
    a block of held experts, so that no whole layer of them stands on the
    device), each called at one shape; the embedding's widening and the head
    are NumPy; no eager ``jax.numpy`` call beside them (each would be a
    program of its own in a run's ``setup_programs``)."""
    from realtime_fraud_detection_tpu.models.qwen3_next import (
        init_qwen3_next_params,
    )
    from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

    # sizes no other test compiles: nothing answers from a cache of traces
    tiny = {**TINY, "shared_expert_intermediate_size": 96}
    params = jax.device_get(init_qwen3_next_params(
        jax.random.PRNGKey(1), BUILDER.qwen3next_config(tiny)))
    ids = np.arange(3 * 40, dtype=np.int32).reshape(3, 40) + 1000
    mask = np.arange(40)[None, :] < np.array([40, 7, 23])[:, None]
    ledger = compile_ledger()
    before = len([r for r in ledger.records() if r["phase"] == "compile"])
    ref.text_branch(params, ids, mask, tiny)
    programs = [r["program"] for r in ledger.records()
                if r["phase"] == "compile"][before:]
    assert sorted(programs) == ["jit(experts)", "jit(full)", "jit(linear)",
                                "jit(route)"]


def test_qwen3next_reference_refuses_what_its_equations_do_not_hold():
    blank = ({}, np.zeros((1, 4), np.int32), np.ones((1, 4), bool))
    for change in ({"decoder_sparse_step": 2}, {"mlp_only_layers": [0]},
                   {"hidden_act": "gelu"}, {"norm_topk_prob": False},
                   {"rope_scaling": {"factor": 2}},
                   {"use_sliding_window": True}, {"num_experts": 12}):
        with pytest.raises(ValueError, match="every layer sparse"):
            ref.text_branch(*blank, {**TINY, **change})
    with pytest.raises(ValueError, match="sites"):
        ref.text_branch(*blank, TINY, sites=frozenset(("ffn",)))


def test_qwen3next_reference_imports_nothing_from_the_package():
    source = (spec.BENCH / "configs" / "qwen3next_reference.py").read_text()
    assert "import realtime_fraud_detection_tpu" not in source
    assert "from realtime_fraud_detection_tpu" not in source
    code = source.split('"""', 2)[2]
    assert "lax.scan(position" in code and "chunk" not in code
    assert "ragged" not in code and "cumsum" not in code


def test_qwen3next_score_composes_the_branches_and_reads_the_file():
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
