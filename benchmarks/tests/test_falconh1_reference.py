"""``configs/falconh1_reference.py`` against the program computed in float32
on the CPU (``test_reference.py``'s pattern, for the sixth reference): the
two share no code — the reference walks the recurrence a position at a time
and materialises the softmax, the program runs the chunked scan — so
agreement to float32 rounding says both implement the same block: two mixers
on one normed input summed into one residual add, the µP vector by segment,
the causal convolution, the gate before the grouped norm, grouped keys; the
lowering seam reaches every matmul and both products of the scan; the text
column costs three compilations; and ``score`` reads what it needs from the
configuration file."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec

ref = spec.reference("falconh1_reference")
CFG_FILE = json.loads(
    (spec.BENCH / "configs" / "falcon-h1-34b-s2048.json").read_text())
BUILDER = spec.builder(CFG_FILE)
TINY = {**CFG_FILE, **BUILDER.TINY}


@pytest.mark.parametrize("seed", [3, 4600000011])
def test_text_branch_is_the_programs_at_float32(seed):
    from realtime_fraud_detection_tpu.models.falcon_h1 import (
        falcon_h1_predict,
        init_falcon_h1_params,
    )

    config = BUILDER.falconh1_config(TINY)
    assert config.mamba_chunk_size == 32 and config.num_hidden_layers == 6
    params = init_falcon_h1_params(jax.random.PRNGKey(seed % 2 ** 31), config)
    rng = np.random.default_rng(seed)
    ids = rng.integers(1000, 30000, (6, 80)).astype(np.int32)
    mask = np.arange(80)[None, :] < rng.integers(1, 81, 6)[:, None]
    got, norms = ref.text_branch(jax.device_get(params), ids, mask, TINY,
                                 parts=True)
    with jax.default_matmul_precision("highest"):
        want = falcon_h1_predict(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params),
            jnp.asarray(ids), jnp.asarray(mask), config)
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    assert got.std() > 0.0
    # the three paths' updates at each row's last real token, every layer
    assert norms.shape == (6, 3, 6) and (norms > 0.0).all()


def test_the_lowering_seam_reaches_every_matmul_and_the_scan():
    """``operand`` is what ``falconh1_control.py`` lowers: called on both
    operands of the seven projections and the MLP's three matmuls, of both
    contractions of the core, on ``x``, ``B``, ``C`` and on the state each
    position reads."""
    from realtime_fraud_detection_tpu.models.falcon_h1 import (
        init_falcon_h1_params,
    )

    tiny = {**TINY, "num_hidden_layers": 1}
    config = BUILDER.falconh1_config(tiny)
    params = jax.device_get(init_falcon_h1_params(jax.random.PRNGKey(0),
                                                  config))
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) + 1000
    mask = np.ones((2, 12), bool)
    shapes = []

    def seen(x):
        shapes.append(tuple(x.shape))
        return x

    ref.text_branch(params, ids, mask, tiny, operand=seen)
    h, f = tiny["hidden_size"], tiny["intermediate_size"]
    heads, kv, d = 10, 2, 16
    d_ssm, gn, m_heads = 64, 2 * 32, 4
    t = 12
    # traced once for the one shape: the weights of the ten matmuls...
    for w in ((h, 2 * d_ssm + 2 * gn + m_heads), (d_ssm, h), (h, heads * d),
              (heads * d, h), (h, f), (f, h)):
        assert w in shapes, w
    assert shapes.count((h, kv * d)) == 2 and shapes.count((h, f)) == 2
    # ... both contractions of the core (q, k; weights, v) ...
    assert shapes.count((t, heads, d)) == 3 and (heads, t, t) in shapes
    # ... and the scan: x, B, C, and the state a position reads
    # (at these sizes d_ssm = G N = 64: x, B, C and W_out's normed input)
    assert d_ssm == gn and shapes.count((t, d_ssm)) == 4
    assert (m_heads, 16, 32) in shapes


def test_the_text_column_costs_three_compilations():
    """One jitted function for a layer, called for every layer and row at
    one shape, one for the embedding and one for the head; no eager
    ``jax.numpy`` call beside them (each would be a program of its own in a
    run's ``setup_programs``)."""
    from realtime_fraud_detection_tpu.models.falcon_h1 import (
        init_falcon_h1_params,
    )
    from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

    # sizes no other test compiles: nothing answers from a cache of traces
    tiny = {**TINY, "num_hidden_layers": 3, "intermediate_size": 192}
    params = jax.device_get(init_falcon_h1_params(
        jax.random.PRNGKey(1), BUILDER.falconh1_config(tiny)))
    ids = np.arange(3 * 40, dtype=np.int32).reshape(3, 40) + 1000
    mask = np.arange(40)[None, :] < np.array([40, 7, 23])[:, None]
    ledger = compile_ledger()
    before = len([r for r in ledger.records() if r["phase"] == "compile"])
    ref.text_branch(params, ids, mask, tiny)
    programs = [r["program"] for r in ledger.records()
                if r["phase"] == "compile"][before:]
    assert sorted(programs) == ["jit(embed)", "jit(head)", "jit(layer)"]


def test_the_reference_refuses_what_its_equations_do_not_hold():
    for change in ({"attention_bias": True}, {"mamba_conv_bias": False},
                   {"mamba_norm_before_gate": True}, {"hidden_act": "gelu"},
                   {"rope_scaling": {"type": "yarn"}},
                   {"attn_layer_indices": [0]}):
        with pytest.raises(ValueError, match="gated group norm after the "
                                             "gate"):
            ref.text_branch({}, np.zeros((1, 4), np.int32),
                            np.ones((1, 4), bool), {**TINY, **change})


def test_the_reference_imports_nothing_from_the_package():
    source = (spec.BENCH / "configs" / "falconh1_reference.py").read_text()
    assert "import realtime_fraud_detection_tpu" not in source
    assert "from realtime_fraud_detection_tpu" not in source
    assert "lax.scan(position" in source and "chunk" not in source.split(
        '"""')[2]


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
