"""The seam between the scorer, the stream job and the seven text encoders
(models/text_encoder.py): what the benchmark reads through it — the job's
counter names, the snapshot's kernel sites, the counters' values, the
programs a bucket's first batch builds — pinned for every encoder at its
TINY configuration."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
from realtime_fraud_detection_tpu.models.falcon_h1 import TINY_FALCON_H1
from realtime_fraud_detection_tpu.models.joyai import TINY_JOYAI
from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA
from realtime_fraud_detection_tpu.models.nemotron_h import TINY_NEMOTRON_H
from realtime_fraud_detection_tpu.models.qwen3_next import TINY_QWEN3_NEXT
from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
from realtime_fraud_detection_tpu.models.text_encoder import (
    LAUNCH_COUNTERS,
    visible_pairs,
)
from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.scoring import (
    FraudScorer,
    ScorerConfig,
    pipeline,
)
from realtime_fraud_detection_tpu.scoring import scorer as scorer_mod
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.stream import (
    InMemoryBroker,
    JobConfig,
    StreamJob,
)

# (configuration, text_len, rows of the first batch): DistilBERT at a width
# that splits, the routed five at the smallest launch with two rungs
# (Nemotron-3-Nano is routed AND state-space: a chunk of 128 over rows of
# 32), Falcon-H1 at a width that crosses chunk boundaries
ENCODERS = {
    "distilbert": (TINY_CONFIG, 256, 32),
    "olmoe": (TINY_OLMOE, 32, 128),
    "zaya1": (TINY_ZAYA, 32, 128),
    "laguna": (TINY_LAGUNA, 32, 128),
    "joyai": (TINY_JOYAI, 32, 128),
    "falconh1": (TINY_FALCON_H1, 64, 8),
    "nemotron3": (TINY_NEMOTRON_H, 32, 128),
    "qwen3next": (TINY_QWEN3_NEXT, 32, 128),
}
ROUTED = ("olmoe", "zaya1", "laguna", "joyai", "nemotron3", "qwen3next")
SCANNED = ("falconh1", "nemotron3")

# what StreamJob.counters held at the parent (PR 48), letter for letter:
# benchmarks/kernels/*.py and benchmarks/readers/*.py read these names
# (and since PR 53 the two of the experts' way out)
JOB_COUNTERS = (
    "scored", "alerts", "batches", "duplicates_skipped", "errors", "shed",
    "token_slots", "token_slots_sq", "real_tokens",
    "expert_rows", "expert_peak_rows", "expert_tile_rows",
    "expert_token_slots", "compact_batches",
    "dispatch_rows", "dispatch_kernel_rows",
    "routed_pairs", "attn_visible_pairs_full", "attn_visible_pairs_sliding",
    "ssm_chunks", "short_text_rows", "long_text_rows", "split_batches",
    "delta_chunks")


def _scorer(name):
    config, text_len, _ = ENCODERS[name]
    return FraudScorer(bert_config=config,
                       scorer_config=ScorerConfig(text_len=text_len),
                       mesh=build_mesh(devices=jax.devices()[:1]))


def _records(gen, rows):
    recs = gen.generate_batch(rows)
    for r in recs:
        r["description"] = "x x x"
    recs[5]["description"] = " ".join(["x"] * 200)          # one long row
    return recs


@pytest.fixture(scope="module", params=list(ENCODERS))
def first_batch(request):
    """An encoder's scorer after its first batch: the launches it made (the
    family's members, then the batch's own), the ``build_programs`` spans it
    opened, the batch's row lengths and its pending, finalized."""
    name = request.param
    scorer = _scorer(name)
    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=49)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    recs = _records(gen, ENCODERS[name][2])
    launched, built = [], []
    launch_packed, span = FraudScorer._launch_packed, scorer.spans.span

    def spy_launch(self, launch, mv):
        launched.append((launch.size, launch.width, launch.capacity))
        return launch_packed(self, launch, mv)

    def spy_span(span_name, **ids):
        if span_name == scopes.BUILD_PROGRAMS:
            built.append(ids)
        return span(span_name, **ids)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(FraudScorer, "_launch_packed", spy_launch)
        patch.setattr(scorer.spans, "span", spy_span)
        batch = scorer.assemble(recs, now=1000.0)
        pending = scorer.dispatch_assembled(batch, recs)
        results = scorer.finalize(pending, now=1000.0)
    assert len(results) == len(recs)
    lengths = np.count_nonzero(np.asarray(batch.token_mask), axis=1)
    return dict(name=name, scorer=scorer, gen=gen, pending=pending,
                lengths=lengths, launched=launched, built=built)


def test_the_snapshot_counts_each_encoders_own_sites(first_batch):
    name, scorer = first_batch["name"], first_batch["scorer"]
    sites = {"dequant_matmul", "epilogue", "attention"}
    if name in ROUTED:
        sites |= {"expert_gate_up", "expert_dispatch", "expert_combine"}
    if name in SCANNED:
        sites |= {"ssm_scan", "causal_conv"}
    if name == "qwen3next":
        sites |= {"delta_scan", "causal_conv"}
    snap = scorer.kernel_snapshot()
    assert set(snap) == {"modes", "interpret", "dispatch", "fallback",
                         "refused"}
    assert set(snap["dispatch"]) == set(snap["fallback"]) == sites
    assert set(snap["modes"]) == {"dequant_matmul", "epilogue", "attention"}
    # the reasons the snapshot can name with no launch in hand
    assert set(snap["refused"]) == {"attention"} | (
        sites & {"ssm_scan", "delta_scan", "causal_conv"})
    assert all("cpu mesh" in why or "head_dim" in why or "seq_len" in why
               or "key_dim" in why or "lane tiles" in why
               for why in snap["refused"].values())
    # the convolution's site is theirs alone: the seven encoders without a
    # causal mixer count nothing at it
    assert ("causal_conv" in snap["dispatch"]) == (
        name in SCANNED or name == "qwen3next")
    # a CPU mesh is never asked for a kernel: every launch of the first
    # batch is a fallback at each of the encoder's own sites
    own = sites - {"dequant_matmul", "epilogue"}
    launches = len(first_batch["launched"])
    assert {snap["fallback"][site] for site in own} == {
        launches - sum(ids["programs"] for ids in first_batch["built"])}
    assert not any(snap["dispatch"].values())


def test_a_batchs_counters_against_a_hand_count(first_batch):
    name, lengths = first_batch["name"], first_batch["lengths"]
    config, width, rows = ENCODERS[name]
    c = first_batch["pending"].counters
    assert tuple(c) == LAUNCH_COUNTERS
    tokens = int(lengths.sum())
    want = dict.fromkeys(LAUNCH_COUNTERS, 0)
    want.update(real_tokens=tokens, token_slots=rows * width,
                token_slots_sq=rows * width * width, long_text_rows=rows)
    if name == "distilbert":
        # 31 short rows at 128 on their own bucket, the long one on 8 rows
        assert (lengths > 128).sum() == 1 and lengths.max() <= width
        want.update(token_slots=32 * 128 + 8 * 256,
                    token_slots_sq=32 * 128 ** 2 + 8 * 256 ** 2,
                    short_text_rows=31, long_text_rows=1, split_batches=1)
    else:
        full = sum(int(n) * (int(n) + 1) // 2 for n in lengths)
        want.update(attn_visible_pairs_full=full)
    if name in ROUTED:
        # the mix fits three quarters of the bucket's 4,096 slots
        assert tokens <= 3072
        pairs = tokens * config.num_experts_per_tok * config.num_sparse_layers
        # every pair row of the rung goes out through XLA's gather: a CPU
        # mesh is never asked for the row fetch
        want.update(expert_token_slots=3072, compact_batches=1,
                    routed_pairs=pairs, expert_rows=pairs,
                    dispatch_rows=3072 * config.num_experts_per_tok
                    * config.num_sparse_layers)
        if name == "laguna":
            # a window of 8 positions; a quarter of the router's experts
            # are held here, so fewer pairs entered a group than were chosen
            want.update(attn_visible_pairs_sliding=sum(
                sum(min(i + 1, 8) for i in range(n)) for n in lengths))
            assert 0 < c["expert_rows"] < pairs
            want.update(expert_rows=c["expert_rows"])
        if name == "qwen3next":
            # a quarter of the router's experts are held here, as Laguna's
            assert 0 < c["expert_rows"] < pairs
            want.update(expert_rows=c["expert_rows"])
        # the largest group of each layer, times the experts held; nothing
        # visited by a kernel's grid in the XLA form
        assert c["expert_peak_rows"] % config.num_experts == 0
        assert c["expert_peak_rows"] >= c["expert_rows"]
        want.update(expert_peak_rows=c["expert_peak_rows"])
    if name == "falconh1":
        want.update(ssm_chunks=rows * width // 16 * 2)
    if name == "nemotron3":
        # the two M layers of MEM*E, over every slot of the launch
        want.update(ssm_chunks=rows * width // 128 * 2)
    if name == "qwen3next":
        # the four L layers of LLLFL, over every slot of the launch
        want.update(delta_chunks=rows * width // 8 * 4)
    assert c == want
    assert visible_pairs(config, lengths)[0] == sum(
        int(n) * (int(n) + 1) // 2 for n in lengths)
    # host_stats()["text_split"] is filled from the same mapping
    split = first_batch["scorer"].host_stats()["text_split"]
    for key in ("short_text_rows", "long_text_rows", "split_batches",
                "expert_token_slots", "compact_batches"):
        assert split[key] >= c[key]
    assert set(split) == {"short_text_rows", "long_text_rows",
                          "split_batches", "expert_token_slots",
                          "compact_batches", "width", "refused", "families"}


def test_the_programs_a_first_batch_builds(first_batch):
    name, scorer = first_batch["name"], first_batch["scorer"]
    launched, built = first_batch["launched"], first_batch["built"]
    families = scorer.host_stats()["text_split"]["families"]
    if name == "distilbert":
        # the bucket left the unsplit launch: its whole family, the unsplit
        # member first, then the batch's own two launches
        family = [(32, 256, None), (32, 128, None), (8, 256, None)]
        assert launched == family + [(32, 128, None), (8, 256, None)]
        assert built == [{"rows": 32, "programs": 3}]
        assert families == {32: [member[:2] for member in family]}
    elif name in ROUTED:
        # both rungs of the bucket, narrowest first, then the batch at the
        # rung that holds it; no tiles where no kernel is asked for
        family = [(128, 32, 3072), (128, 32, 4096)]
        assert launched == family + [(128, 32, 3072)]
        assert built == [{"rows": 128, "programs": 2}]
        assert families == {128: family}
    else:
        # one launch at text_len, nothing to build
        assert launched == [(8, 64, None)]
        assert built == [] and families == {}


def test_the_jobs_counters_are_the_same_names_for_every_encoder(first_batch):
    # (after the tests of the first batch alone: this one launches more)
    scorer, gen = first_batch["scorer"], first_batch["gen"]
    assert scorer_mod.LAUNCH_COUNTERS is LAUNCH_COUNTERS
    assert len(JOB_COUNTERS) == 24
    broker = InMemoryBroker()
    job = StreamJob(broker, scorer, JobConfig(max_batch=8))
    # every name from the start, at 0, whichever the encoder fills
    assert tuple(job.counters) == JOB_COUNTERS
    assert set(job.counters.values()) == {0}
    broker.produce_batch_keyed(JobConfig.transactions_topic, [
        (str(r["user_id"]), r) for r in gen.generate_batch(8)])
    job.run_until_drained()
    job.close()
    assert tuple(job.counters) == JOB_COUNTERS
    width = scorer.sc.text_len
    short = job.counters["short_text_rows"]
    assert job.counters["scored"] == 8 and job.counters["errors"] == 0
    assert job.counters["token_slots"] == 8 * (128 if short else width)
    assert short + job.counters["long_text_rows"] == 8
    assert job.counters["real_tokens"] > 0


@pytest.mark.parametrize("name", list(ENCODERS))
def test_text_predict_refuses_from_the_rows_own_facts(name):
    config = ENCODERS[name][0]
    ids = jnp.zeros((2, 32), jnp.int32)
    mask = jnp.ones((2, 32), bool)
    row = pipeline.text_encoder(config)
    assert row is pipeline.TEXT_ENCODERS[type(config)]
    assert pipeline.text_layers(config) == row.depth(config) >= 2
    takes_dequant = contextlib.nullcontext() if name == "distilbert" \
        else pytest.raises(ValueError, match="dequant_matmul")
    takes_capacity = contextlib.nullcontext() if name in ROUTED \
        else pytest.raises(ValueError, match="text_capacity")
    params = jax.eval_shape(lambda key: row.init(key, config),
                            jax.random.PRNGKey(0))
    with takes_dequant:
        jax.eval_shape(lambda p: pipeline.text_predict(
            p, ids, mask, config, dequant_kernel="pallas"), params)
    with takes_capacity:
        _, stats = jax.eval_shape(lambda p: pipeline.text_predict(
            p, ids, mask, config, capacity=64), params)
        assert stats.shape == (3, config.num_sparse_layers)


def test_distilberts_row_reaches_bert_predict_when_it_is_traced(monkeypatch):
    """``pipeline.bert_predict`` replaced after import changes the answer:
    the benchmark's rehearsal makes its wrong program that way
    (benchmarks/tests/rehearsal.py)."""
    scorer = _scorer("distilbert")
    gen = TransactionGenerator(num_users=20, num_merchants=8, seed=49)
    recs = gen.generate_batch(8)

    def text_answers():
        pipeline.score_fused_packed.clear_cache()
        return np.array([r["model_predictions"]["bert_text"]
                         for r in scorer.score_batch(recs, now=1000.0)])

    sound = pipeline.bert_predict
    want = text_answers()
    monkeypatch.setattr(pipeline, "bert_predict", lambda *a, **k: jnp.clip(
        sound(*a, **k) + 0.25, 0.0, 1.0))
    np.testing.assert_allclose(text_answers(), np.clip(want + 0.25, 0, 1),
                               atol=1e-6)
    monkeypatch.undo()
    np.testing.assert_allclose(text_answers(), want, atol=1e-6)
    pipeline.score_fused_packed.clear_cache()
