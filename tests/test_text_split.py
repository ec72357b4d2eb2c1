"""The family of programs a row bucket can launch is closed, and compiled
before it is needed (scoring/text_split.py, ISSUE 27)."""

import jax
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.batching import BATCH_BUCKETS, bucket_for
from realtime_fraud_detection_tpu.scoring import text_split

# (narrow, full): the deployed pair, the tests' pair, and the pairs with
# nothing to split (no narrower width; text_len at or under it)
WIDTHS = [(128, 512), (128, 256), (None, 512), (128, 128), (128, 64)]


@pytest.mark.parametrize("multiple", [1, 8], ids=["one-device", "data-axis-8"])
@pytest.mark.parametrize("bucket", BATCH_BUCKETS)
def test_every_launch_lies_in_the_family_of_its_bucket(bucket, multiple):
    def bucket_of(n):
        return bucket_for(n, BATCH_BUCKETS, multiple_of=multiple)

    size = bucket_of(bucket)
    sizes = [n for n in range(1, size + 1) if bucket_of(n) == size]
    for narrow, full in WIDTHS:
        fam = text_split.family(size, narrow, full, bucket_of)
        assert fam[0] == (size, full)
        unsplit_slots = size * full
        for n in sizes:
            for n_long in range(n + 1):
                launches = text_split.plan(n - n_long, n_long, size, narrow,
                                           full, bucket_of)
                assert {(r, w) for _, r, w in launches} <= set(fam)
                slots = sum(r * w for _, r, w in launches)
                if launches != ((text_split.ALL, size, full),):
                    # a batch leaves the one launch only for fewer slots
                    assert slots < unsplit_slots
                    assert narrow is not None and narrow < full
                held = {which: r for which, r, _ in launches}
                if text_split.LONG in held:
                    assert held[text_split.LONG] >= n_long
                    assert held[text_split.SHORT] == size
                # a short row never runs wider than it must, a long row
                # never narrower than its text
                if n_long and launches[0][0] == text_split.ALL:
                    assert launches[0][2] == full


def test_the_deployed_family_is_the_one_the_issue_names():
    fam = text_split.family(256, 128, 512, bucket_for)
    assert set(fam) == {(256, 512), (256, 128), (8, 512), (32, 512)}
    # at bucket 8 one long row already costs more in two launches
    assert set(text_split.family(8, 128, 512, bucket_for)) == {
        (8, 512), (8, 128)}
    assert set(text_split.family(1, 128, 512, bucket_for)) == {
        (1, 512), (1, 128)}
    # OLMoE's 128 tokens, the parked 64-token configurations: the parent's
    for full in (128, 64):
        assert text_split.family(256, 128, full, bucket_for) == ((256, full),)
    assert text_split.plan(248, 8, 256, 128, 512, bucket_for) == (
        (text_split.SHORT, 256, 128), (text_split.LONG, 8, 512))
    assert text_split.plan(247, 9, 256, 128, 512, bucket_for) == (
        (text_split.SHORT, 256, 128), (text_split.LONG, 32, 512))
    assert text_split.plan(223, 33, 256, 128, 512, bucket_for) == (
        (text_split.ALL, 256, 512),)
    assert text_split.plan(256, 0, 256, 128, 512, bucket_for) == (
        (text_split.ALL, 256, 128),)


class _Compiles:
    """XLA compilations, cache loads included: what
    ``benchmarks/harness/correct.CompileCounter`` counts."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def test_one_warming_batch_compiles_everything_longtail_traffic_launches():
    """One batch that leaves the unsplit launch compiles its bucket's whole
    family; 200 seeded batches of long-tailed text (median 20 tokens, 3%
    over the narrow width, as ``s512-longtail-saturated``) then compile
    nothing, whichever member each one needs."""
    from realtime_fraud_detection_tpu.core.mesh import local_mesh_size
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    rows, text_len, batches = 32, 256, 200
    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=27)
    s = FraudScorer(scorer_config=ScorerConfig(text_len=text_len))
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    rng = np.random.default_rng(27)
    want = np.clip(rng.lognormal(np.log(20.0), 1.0, (batches + 1, rows)),
                   4, text_len).astype(int)
    want[0, 5] = 200          # the warming batch splits
    want[50, :6] = 200        # too many long rows: the unsplit member
    want[100] = 20            # all short: one launch at the narrow width

    def batch(i):
        recs = gen.generate_batch(rows)
        for r, t in zip(recs, want[i]):
            r["description"] = " ".join(["x"] * int(t))
        return recs

    multiple = local_mesh_size(s.mesh)
    size = bucket_for(rows, multiple_of=multiple)
    # another test file on this worker may have launched the same programs:
    # the count below is of what THIS scorer's first split makes the
    # process compile or load
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )

    score_fused_packed.clear_cache()
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    launched = set()
    try:
        s.finalize(s.dispatch(batch(0), now=1000.0), now=1000.0)
        fam = s.host_stats()["text_split"]["families"]
        assert fam == {size: list(text_split.family(
            size, 128, text_len,
            lambda n: bucket_for(n, multiple_of=multiple)))}
        # the listener hears this process's programs, loaded or compiled
        assert compiles.count >= len(fam[size])
        compiles.count = 0
        for i in range(1, batches + 1):
            p = s.dispatch(batch(i), now=1000.0 + i)
            launched.add((p.counters["token_slots"],
                          p.counters["split_batches"]))
            s.finalize(p, now=1000.0 + i)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert compiles.count == 0
    # all three answers of the rule were launched
    assert (size * text_len, 0) in launched
    assert (size * 128, 0) in launched
    assert any(split for _, split in launched)
    counts = s.host_stats()["text_split"]
    assert counts["short_text_rows"] + counts["long_text_rows"] == \
        (batches + 1) * rows == s.stats["scored"]
    assert 0.9 < counts["short_text_rows"] / s.stats["scored"] < 1.0
    assert s.host_stats()["text_split"]["families"] == fam


# ------------------------- the capacity of a sparse encoder's routed block
@pytest.mark.parametrize("width", [32, 128, 512])
@pytest.mark.parametrize("bucket", BATCH_BUCKETS)
def test_the_rung_picked_is_the_narrowest_that_holds_the_tokens(bucket, width):
    slots = bucket * width
    rungs = text_split.capacities(slots)
    assert rungs[-1] == slots and list(rungs) == sorted(set(rungs))
    assert len(rungs) == (2 if slots >= text_split.MIN_COMPACT_SLOTS else 1)
    for rung in rungs[:-1]:
        # whole 128-row tiles once each token has its eight experts
        assert rung % 16 == 0 and (rung * 8) % 128 == 0
        assert 4 * rung <= 3 * slots < 4 * (rung + 16)
    for tokens in {0, 1, rungs[0] - 1, rungs[0], rungs[0] + 1, slots}:
        if not 0 <= tokens <= slots:
            continue
        got = text_split.capacity(tokens, slots)
        assert got in rungs and got >= tokens
        assert all(r < tokens for r in rungs if r < got)
    # by construction no launch holds more tokens than slots
    with pytest.raises(ValueError, match="text_split.capacity"):
        text_split.capacity(slots + 1, slots)


def test_the_deployed_rungs_are_the_ones_the_issue_names():
    # olmoe-1b-7b-s128: 256 rows of 128 positions, 65% of them real
    assert text_split.capacities(256 * 128) == (24576, 32768)
    assert text_split.capacity(21300, 256 * 128) == 24576
    assert text_split.capacity(24576, 256 * 128) == 24576
    assert text_split.capacity(24577, 256 * 128) == 32768   # all-full rows
    assert text_split.capacity(256 * 128, 256 * 128) == 32768
    # the parity sample's bucket of 8 rows keeps the one program
    assert text_split.capacities(8 * 128) == (1024,)
    assert text_split.capacities(32 * 128) == (3072, 4096)


def _moe_scorer(text_len=32):
    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig

    return FraudScorer(bert_config=TINY_OLMOE,
                       scorer_config=ScorerConfig(text_len=text_len),
                       mesh=build_mesh(devices=jax.devices()[:1]))


def _worded(gen, rows, words):
    recs = gen.generate_batch(rows)
    for r in recs:
        r["description"] = " ".join(["x"] * words)
    return recs


def test_both_rungs_are_compiled_by_a_buckets_first_batch(monkeypatch):
    """128 rows x 32 positions is the smallest launch with a narrow rung.
    Its first batch compiles both programs; batches of either kind then
    compile nothing, each launched with the capacity the rule names."""
    from realtime_fraud_detection_tpu.scoring import scorer as scorer_mod
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    rows, text_len = 128, 32
    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=29)
    s = _moe_scorer(text_len)
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    narrow, full = text_split.capacities(rows * text_len)
    assert (narrow, full) == (3072, 4096)
    passed = []
    real = scorer_mod.score_fused_packed

    def spy(*args, **kwargs):
        passed.append(kwargs["text_capacity"])
        return real(*args, **kwargs)

    monkeypatch.setattr(scorer_mod, "score_fused_packed", spy)
    score_fused_packed.clear_cache()
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        first = s.dispatch(_worded(gen, rows, 3), now=1000.0)
        s.finalize(first, now=1000.0)
        fam = s.host_stats()["text_split"]["families"]
        assert fam == {rows: [(rows, text_len, narrow),
                              (rows, text_len, full)]}
        assert compiles.count >= 2
        assert passed == [narrow, full, narrow]      # the family, the batch
        compiles.count = 0
        del passed[:]
        pendings = []
        for i, words in enumerate([3, 40, 3, 40]):
            p = s.dispatch(_worded(gen, rows, words), now=1001.0 + i)
            pendings.append(p)
            s.finalize(p, now=1001.0 + i)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert compiles.count == 0
    assert passed == [narrow, full, narrow, full]
    for p, rung in zip(pendings, passed):
        c = p.counters
        assert c["real_tokens"] <= rung == c["expert_token_slots"]
        assert c["token_slots"] == full
        assert c["compact_batches"] == int(rung == narrow)
        assert c["expert_rows"] == c["real_tokens"] * 2 * 2  # top-2, 2 layers
    # all-full rows take every slot
    assert pendings[1].counters["real_tokens"] == full
    counts = s.host_stats()["text_split"]
    assert counts["compact_batches"] == 3
    assert counts["expert_token_slots"] == 3 * narrow + 2 * full
    assert s.host_stats()["text_split"]["families"] == fam


@pytest.mark.parametrize("kind", ["split", "routed"])
def test_a_buckets_first_batch_builds_its_family_under_one_span(kind):
    """The first batch of a bucket that splits, and the first routed batch
    of a bucket, open ``build_programs`` once, round the whole family: the
    compile ledger saw as many fused programs compiled under it as
    ``host_stats()["text_split"]["families"]`` holds, ``pack``'s own time
    leaves the build out, and the next batch builds and compiles nothing."""
    import time

    from realtime_fraud_detection_tpu.obs import scopes
    from realtime_fraud_detection_tpu.obs.profiling import compile_ledger
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=36)
    if kind == "split":
        rows = 32
        s = FraudScorer(scorer_config=ScorerConfig(text_len=256))
    else:
        rows = 128               # the smallest launch with two rungs
        s = _moe_scorer(32)
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())

    def batch():
        recs = _worded(gen, rows, 3)
        recs[5]["description"] = " ".join(["x"] * 200)     # one long row
        return recs

    def fused_programs(since):
        return [r for r in compile_ledger().records()
                if r["phase"] == "compile" and r["start"] >= since
                and "score_fused_packed" in r["program"]]

    # another test file on this worker may have launched the same programs
    score_fused_packed.clear_cache()
    t0 = time.time()
    compiled0 = s.host_stats()["compile"]["programs"]
    s.finalize(s.dispatch(batch(), now=1000.0), now=1000.0)
    stats = s.host_stats()
    (size, family), = stats["text_split"]["families"].items()
    assert len(family) == (3 if kind == "split" else 2)
    stages = stats["stages"]
    build, pack = stages[scopes.BUILD_PROGRAMS], stages[scopes.PACK]
    assert build["count"] == 1 and build["parent"] == scopes.PACK
    assert pack["self_s"] == pytest.approx(
        pack["total_s"] - build["total_s"], abs=1e-9)
    assert pack["self_s"] < build["total_s"]
    under = [r for r in fused_programs(t0)
             if r["caused_by"].startswith(scopes.BUILD_PROGRAMS + " ")]
    assert len(under) == len(family)
    assert {r["caused_by"] for r in under} == {
        f"{scopes.BUILD_PROGRAMS} rows={size} programs={len(family)}"}
    # nothing of the bucket is left for its own launch to compile
    assert len(fused_programs(t0)) == len(family)
    assert stats["compile"]["programs"] - compiled0 >= len(family)
    assert any(r in stats["compile"]["records"] for r in under)

    t1 = time.time()
    compiled1 = stats["compile"]["programs"]
    s.finalize(s.dispatch(batch(), now=1001.0), now=1001.0)
    stats = s.host_stats()
    assert stats["stages"][scopes.BUILD_PROGRAMS]["count"] == 1
    assert stats["stages"][scopes.PACK]["count"] == 2
    assert fused_programs(t1) == []
    assert stats["compile"]["programs"] == compiled1
    assert stats["text_split"]["families"] == {size: family}


def test_a_short_bucket_has_empty_filler_rows_and_the_same_answers(
        monkeypatch):
    """90 full rows on the bucket of 128: the 38 filler rows hold no token,
    so the launch's tokens are the real rows' and a batch that would not
    fit the narrow rung with row 0 repeated does; the real rows' answers
    are those of the same rows launched at every slot."""
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=31)
    s = _moe_scorer()
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    recs = _worded(gen, 90, 40)
    batch = s.assemble(recs, now=1000.0)
    p = s.dispatch_assembled(batch, recs)
    c = p.counters
    assert c["real_tokens"] == int(batch.token_mask.sum()) == 90 * 32 <= 3072
    assert (c["expert_token_slots"], c["compact_batches"]) == (3072, 1)
    compact = np.asarray(p.out)[:90]
    monkeypatch.setattr(text_split, "capacity", lambda tokens, slots: slots)
    q = s.dispatch_assembled(batch, recs)
    assert (q.counters["expert_token_slots"],
            q.counters["compact_batches"]) == (4096, 0)
    np.testing.assert_allclose(compact, np.asarray(q.out)[:90],
                               atol=1e-6, rtol=0)


def test_a_capacity_that_cannot_hold_the_launch_is_refused_by_name():
    from realtime_fraud_detection_tpu.scoring.scorer import _Launch
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    gen = TransactionGenerator(num_users=20, num_merchants=8, seed=3)
    s = _moe_scorer()
    recs = _worded(gen, 8, 40)
    batch = s.assemble(recs, now=1000.0)
    forced = _Launch(None, 8, 8, 32, capacity=128, tokens=8 * 32)
    s._pack_launch(batch, forced)
    with pytest.raises(ValueError, match="text_capacity 128 cannot hold"):
        s._launch_packed(forced, s.effective_model_valid())
    # and the dense encoder has no such argument
    from realtime_fraud_detection_tpu.scoring.pipeline import text_predict
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG

    with pytest.raises(ValueError, match="text_capacity"):
        text_predict({}, batch.token_ids, batch.token_mask, TINY_CONFIG,
                     capacity=128)


def test_the_counters_follow_the_work_on_a_driven_job():
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.scoring import FraudScorer
    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )

    def drive(scorer, rows):
        broker = InMemoryBroker()
        cfg = JobConfig(max_batch=128)
        job = StreamJob(broker, scorer, cfg)
        gen = TransactionGenerator(num_users=64, num_merchants=16, seed=5)
        recs = gen.generate_batch(rows)
        broker.produce_batch_keyed(
            cfg.transactions_topic, [(r["user_id"], r) for r in recs])
        job.run_until_drained()
        job.close()
        assert job.counters["errors"] == 0 and job.counters["scored"] == rows
        return job.counters

    moe = _moe_scorer()
    c = drive(moe, 280)
    k, layers = TINY_OLMOE.num_experts_per_tok, TINY_OLMOE.num_hidden_layers
    assert c["expert_rows"] == c["real_tokens"] * k * layers > 0
    assert c["expert_rows"] <= c["expert_token_slots"] * k * layers \
        <= c["token_slots"] * k * layers
    # the simulator's short descriptors fit the narrow rung of the full
    # buckets of 128; the last 24 rows (a bucket of 32: under the
    # smallest launch with a rung) ran at every slot
    assert 0 < c["compact_batches"] < c["batches"]
    assert c["expert_token_slots"] < c["token_slots"]
    stats = moe.host_stats()["text_split"]
    assert (stats["expert_token_slots"], stats["compact_batches"]) == (
        c["expert_token_slots"], c["compact_batches"])
    dense = drive(FraudScorer(mesh=build_mesh(devices=jax.devices()[:1])), 40)
    assert dense["token_slots"] > 0
    assert [dense[key] for key in ("expert_rows", "expert_peak_rows",
                                   "expert_token_slots", "compact_batches")
            ] == [0, 0, 0, 0]
