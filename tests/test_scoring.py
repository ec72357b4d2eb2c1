"""Tests for the fused scoring pipeline + host orchestrator."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
from realtime_fraud_detection_tpu.features.rules import DECISIONS, RISK_LEVEL_NAMES
from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
from realtime_fraud_detection_tpu.scoring import (
    MODEL_NAMES,
    FraudScorer,
    ScorerConfig,
    init_scoring_models,
    make_example_batch,
    score_fused,
)
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.utils.config import Config


@pytest.fixture(scope="module")
def models():
    return init_scoring_models(jax.random.PRNGKey(0), bert_config=TINY_CONFIG)


@pytest.fixture(scope="module")
def ens_params():
    return EnsembleParams.from_config(Config(), list(MODEL_NAMES))


def test_score_fused_shapes(models, ens_params):
    b = 8
    batch = make_example_batch(b)
    out = score_fused(
        models, batch, ens_params, jnp.ones((len(MODEL_NAMES),), bool),
        bert_config=TINY_CONFIG,
    )
    assert out["fraud_probability"].shape == (b,)
    assert out["model_predictions"].shape == (b, len(MODEL_NAMES))
    assert out["decision"].shape == (b,)
    p = np.asarray(out["fraud_probability"])
    assert np.all((p >= 0) & (p <= 1))


def test_score_fused_model_failure_mask(models, ens_params):
    """A disabled/failed branch is excluded and the rest renormalize
    (ensemble_predictor.py:175-182)."""
    batch = make_example_batch(4)
    all_valid = score_fused(models, batch, ens_params,
                            jnp.ones((5,), bool), bert_config=TINY_CONFIG)
    no_bert = score_fused(models, batch, ens_params,
                          jnp.asarray([True, True, False, True, True]),
                          bert_config=TINY_CONFIG)
    preds = np.asarray(all_valid["model_predictions"])
    w = np.asarray(ens_params.weights)
    mask = np.asarray([1.0, 1.0, 0.0, 1.0, 1.0])
    expect = (preds * w * mask).sum(1) / (w * mask).sum()
    np.testing.assert_allclose(
        np.asarray(no_bert["fraud_probability"]), expect, rtol=1e-5
    )


def test_fraud_scorer_end_to_end():
    gen = TransactionGenerator(num_users=50, num_merchants=20, seed=1)
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    records = gen.generate_batch(12)
    results = scorer.score_batch(records, now=1000.0)
    assert len(results) == 12
    for r in results:
        assert 0.0 <= r["fraud_probability"] <= 1.0
        assert r["decision"] in DECISIONS
        assert r["risk_level"] in RISK_LEVEL_NAMES
        assert set(r["model_predictions"]) == set(MODEL_NAMES)
        assert "model_contributions" in r["explanation"]


def test_fraud_scorer_state_accumulates():
    """Velocity and history state must accumulate across calls."""
    gen = TransactionGenerator(num_users=3, num_merchants=3, seed=2)
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    recs = gen.generate_batch(6)
    scorer.score_batch(recs, now=1000.0)
    uid = str(recs[0]["user_id"])
    vel = scorer.velocity.get_all(uid, now=1001.0)
    assert vel["5min"]["count"] >= 1
    assert len(scorer.history) >= 1
    scorer.score_batch(gen.generate_batch(4), now=1010.0)
    assert scorer.stats["scored"] == 10


def test_processing_time_excludes_pipeline_queue_wait():
    """Under pipelining, the gap between dispatch() returning and finalize()
    being called is queue wait, not processing — reported processing_time_ms
    must not include it (ADVICE r2, scorer.py elapsed_ms)."""
    import time

    gen = TransactionGenerator(num_users=10, num_merchants=5, seed=4)
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    recs = gen.generate_batch(4)
    # warm up compile so the timed run measures steady state
    scorer.score_batch(recs[:1], now=999.0)

    pending = scorer.dispatch(recs, now=1000.0)
    jax.block_until_ready(pending.out)   # device done BEFORE the queue wait
    time.sleep(0.3)                      # simulated pipeline queue wait
    results = scorer.finalize(pending, now=1000.0)
    assert results[0]["processing_time_ms"] * len(recs) < 250.0


def test_fraud_scorer_padding_invariance():
    """Bucket padding must not change real-row scores."""
    gen = TransactionGenerator(num_users=20, num_merchants=10, seed=3)
    recs = gen.generate_batch(8)

    def run(batch_records):
        s = FraudScorer(scorer_config=ScorerConfig(text_len=32), seed=0)
        s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        return s.score_batch(batch_records, now=1000.0)

    r5 = run(recs[:5])   # pads 5 -> bucket 8
    r8 = run(recs[:8])   # exact bucket
    for a, b in zip(r5, r8[:5]):
        assert a["fraud_probability"] == pytest.approx(b["fraud_probability"], rel=1e-5)


def test_enable_explanation_config_gates_explanations():
    from realtime_fraud_detection_tpu.scoring import FraudScorer
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
    from realtime_fraud_detection_tpu.utils.config import Config

    gen = TransactionGenerator(num_users=16, num_merchants=8, seed=2)
    cfg = Config()
    cfg.ensemble.enable_explanation = False
    s = FraudScorer(config=cfg)
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    res = s.score_batch(gen.generate_batch(4))
    assert all(r["explanation"] == {} for r in res)
    assert all("fraud_probability" in r for r in res)


# ---- text widths of a batch (scoring/text_split.py, ISSUE 27) -------------

NARROW = 128      # ops.attention.narrowest_supported_len at TINY's heads


def _records_of_lengths(gen, probe, lengths):
    """Records whose combined text tokenises to exactly ``lengths`` real
    tokens ([CLS] and [SEP] included), measured through ``probe`` (a scorer
    of its own: assembling touches history and graph state)."""
    recs = gen.generate_batch(len(lengths))
    for r in recs:
        r["description"] = "x"
    base = probe.assemble(recs, now=1000.0).token_mask.sum(axis=1)
    for r, have, want in zip(recs, base, lengths):
        assert want >= have, (want, have)
        r["description"] = " ".join(["x"] * (1 + want - have))
    return recs


def _unsplit_reference(scorer, batch, n):
    """The same assembled batch through ``score_fused_packed`` called
    directly, one launch at the full ``text_len``: what the parent did."""
    from realtime_fraud_detection_tpu.core.batching import pad_to_bucket
    from realtime_fraud_detection_tpu.core.mesh import local_mesh_size
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )

    padded, mask, _ = pad_to_bucket(
        batch, n, multiple_of=local_mesh_size(scorer.mesh))
    blobs, spec = pack_tree(padded.replace(valid=mask))
    out = score_fused_packed(
        scorer.models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
        params=scorer.ensemble_params,
        model_valid=jnp.asarray(scorer.effective_model_valid()),
        blob_bf16=blobs["bf16"], bert_config=scorer.bert_config,
        use_pallas=False, **scorer.quant_static(), **scorer.kernel_static())
    return np.asarray(out)[:n]


# (text_len, real tokens per row, launches as (bucket of n rows?, width)):
# "b" stands for the batch's own bucket, a number for a long part's bucket
SPLIT_CASES = {
    "all-short": (256, [20, 128, 64, 19, 33, 127, 22, 40, 25, 18, 90, 21],
                  [("b", NARROW)]),
    "all-long": (256, [129, 200, 256, 130, 180], [("b", 256)]),
    "one-long-row": (256, [18] * 7 + [200] + [22] * 12,
                     [("b", NARROW), (8, 256)]),
    "threshold": (256, [NARROW - 1, NARROW, NARROW + 1] + [30] * 9,
                  [("b", NARROW), (8, 256)]),
    "too-many-long": (256, [200] * 5 + [30] * 7, [("b", 256)]),
    "n=1-short": (256, [NARROW], [("b", NARROW)]),
    "n=1-long": (256, [NARROW + 1], [("b", 256)]),
    "text_len-64": (64, [20, 64, 33, 19, 50], [("b", 64)]),
    "text_len-128": (128, [20, 128, 33, 19, 127], [("b", 128)]),
}


@pytest.mark.parametrize("case", list(SPLIT_CASES))
def test_split_launches_equal_the_unsplit_program(models, case):
    """A batch launched at two text widths answers what one launch at
    ``text_len`` answers, row for record; where no narrower width exists
    (``text_len`` <= 128) the launch and ``PendingScore.out`` are the
    parent's."""
    from realtime_fraud_detection_tpu.core.batching import bucket_for
    from realtime_fraud_detection_tpu.core.mesh import local_mesh_size
    from realtime_fraud_detection_tpu.scoring.pipeline import OUT_COLUMNS

    text_len, lengths, want_launches = SPLIT_CASES[case]
    gen = TransactionGenerator(num_users=40, num_merchants=15, seed=27)

    def scorer():
        s = FraudScorer(models=models,
                        scorer_config=ScorerConfig(text_len=text_len))
        s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        return s

    recs = _records_of_lengths(gen, scorer(), lengths)
    n = len(recs)
    ref_scorer, s = scorer(), scorer()
    batch = ref_scorer.assemble(recs, now=1000.0)
    assert batch.token_mask.sum(axis=1).tolist() == lengths
    want = _unsplit_reference(ref_scorer, batch, n)

    pending = s.dispatch(recs, now=1000.0)
    multiple = local_mesh_size(s.mesh)
    b = bucket_for(n, multiple_of=multiple)
    launches = [(b if rows == "b" else bucket_for(rows, multiple_of=multiple),
                 width) for rows, width in want_launches]
    c = pending.counters
    assert c["token_slots"] == sum(r * w for r, w in launches)
    assert c["token_slots_sq"] == sum(r * w * w for r, w in launches)
    assert c["real_tokens"] == sum(lengths)
    short = sum(1 for t in lengths if t <= NARROW) \
        if launches[0][1] < text_len else 0
    assert (c["short_text_rows"], c["long_text_rows"],
            c["split_batches"]) == (short, n - short, int(len(launches) > 1))
    if len(launches) == 1:
        assert isinstance(pending.out, jax.Array)     # the parent's output
        assert pending.out.shape[0] == b
    # what benchmarks/harness/correct.parity reads, before finalize
    got = np.asarray(pending.out)[:n]
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    j = OUT_COLUMNS.index("decision")
    assert got[:, j].tolist() == want[:, j].tolist()

    results = s.finalize(pending, now=1000.0)
    assert [r["transaction_id"] for r in results] == \
        [str(r["transaction_id"]) for r in recs]
    p = OUT_COLUMNS.index("fraud_probability")
    np.testing.assert_allclose([r["fraud_probability"] for r in results],
                               got[:, p], rtol=0, atol=1e-6)
    assert [r["decision"] for r in results] == \
        [DECISIONS[int(d)] for d in got[:, j]]
    snap = s.kernel_snapshot()
    # the attention site once per launch, at the launched width (a CPU
    # scorer asks for no kernel: every launch is a counted fallback)
    assert snap["fallback"]["attention"] == len(launches)
    fam = s.host_stats()["text_split"]["families"]
    if launches[0][1] == text_len:
        assert fam == {}                  # nothing but the parent's program
    else:
        assert set(launches) <= set(fam[b])


def test_planes_that_cannot_take_a_second_width_keep_the_unsplit_launch(
        models):
    s = FraudScorer(models=models, scorer_config=ScorerConfig(text_len=256))
    assert s.plane_refusal("text_split") is None
    assert s.host_stats()["text_split"]["width"] == NARROW

    class StandInPool:
        batch_multiple = None

    s._pool = StandInPool()
    assert "StandInPool" in s.plane_refusal("text_split")
    assert s._narrow_text_len(256) is None
    assert s.host_stats()["text_split"]["width"] is None
    s._pool = None
    # at or under the narrowest width there is nothing narrower
    assert s._narrow_text_len(NARROW) is None
    assert s._narrow_text_len(64) is None
    # a head layout the kernel does not take has no narrow width at all
    from realtime_fraud_detection_tpu.ops import narrowest_supported_len

    assert narrowest_supported_len(64, 12) == NARROW
    assert narrowest_supported_len(128, 16) is None
    assert narrowest_supported_len(32, 4) is None


# ------------------------------- one list of the program's static arguments
@pytest.mark.parametrize("executor", ["scorer", "DevicePool", "MeshExecutor"])
def test_every_launch_passes_the_one_list_of_static_arguments(
        models, executor, monkeypatch):
    """``pipeline._PACKED_STATIC`` is the program's static arguments, once:
    what the impl's signature takes beside its arrays, what a launch passes
    (``spec``, ``bert_config``, ``use_pallas`` and the two planes' memoized
    dicts; ``text_capacity`` with the MoE text encoder only), and — plus the
    two the re-gather reads — the mesh entry's."""
    import inspect

    from realtime_fraud_detection_tpu.scoring import (
        device_pool,
        mesh_executor,
        pipeline,
        scorer as scorer_mod,
    )

    arrays = {"models", "blob_f32", "blob_i32", "blob_u8", "blob_bf16",
              "params", "model_valid"}
    static = pipeline._PACKED_STATIC
    assert len(set(static)) == len(static)
    assert set(static) == set(inspect.signature(
        pipeline._score_fused_packed_impl).parameters) - arrays

    s = FraudScorer(models=models, scorer_config=ScorerConfig())
    # ``text_capacity`` is the MoE text encoder's (models/olmoe.py): a dense
    # launch leaves it out, as before there was one (tests/test_text_split.py
    # holds the MoE launch)
    routed = {"text_capacity"}
    assert set(static) == ({"spec", "bert_config", "use_pallas"} | routed
                           | set(s.quant_static()) | set(s.kernel_static()))
    if executor == "scorer":
        module, names, extra = scorer_mod, ("score_fused_packed",), ()
    elif executor == "DevicePool":
        device_pool.DevicePool(s, devices=jax.devices()[:2])
        # the pool imports the two entries where it launches
        module, extra = pipeline, ()
        names = ("score_fused_packed", "score_fused_packed_donated")
    else:
        mesh_executor.MeshExecutor(s, devices=jax.devices()[:2])
        module, extra = mesh_executor, ("gather_fields", "mesh")
        names = ("mesh_score_packed", "mesh_score_packed_donated")
        assert mesh_executor._MESH_STATIC == static + extra
        assert set(inspect.signature(
            mesh_executor._mesh_score_packed_impl).parameters) == (
            arrays | set(extra) | {"statics"})
    passed = []
    for name in names:
        real = getattr(module, name)

        def spy(*args, _real=real, **kwargs):
            passed.append(set(kwargs) - arrays)
            return _real(*args, **kwargs)

        monkeypatch.setattr(module, name, spy)
    gen = TransactionGenerator(num_users=50, num_merchants=20, seed=3)
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    assert len(s.score_batch(gen.generate_batch(8), now=1000.0)) == 8
    assert passed and all(p == set(static + extra) - routed for p in passed)
