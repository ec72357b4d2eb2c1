"""Stream layer tests: transport semantics, microbatching, the full job."""

import time

import numpy as np
import pytest

from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.stream import (
    FaultInjector,
    InMemoryBroker,
    JobConfig,
    MicrobatchAssembler,
    StreamJob,
)
from realtime_fraud_detection_tpu.stream import topics as T


def test_broker_keyed_partition_ordering():
    b = InMemoryBroker()
    for i in range(20):
        b.produce(T.TRANSACTIONS, {"n": i}, key="user_7")
    c = b.consumer([T.TRANSACTIONS], "g1")
    recs = c.poll(100)
    assert [r.value["n"] for r in recs] == list(range(20))
    assert len({r.partition for r in recs}) == 1  # same key -> same partition


def test_keyed_partitioning_is_restart_stable_crc32():
    """key->partition must be crc32 (process-stable), not salted hash():
    a WAL-backed broker replayed in a new process must route old keys to
    the same partitions, and the in-memory + Kafka transports must agree."""
    import zlib

    b = InMemoryBroker()
    n = b.partitions(T.TRANSACTIONS)
    for key in ("user_1", "user_42", "m-997", "", "unicode-é"):
        assert b.select_partition(T.TRANSACTIONS, key) == \
            zlib.crc32(key.encode()) % n


def test_fanout_failure_releases_inflight_ids_no_record_loss():
    """If fan-out raises mid-batch (broker down), the in-flight ids must be
    released and offsets NOT committed, so redelivery rescores the batch
    instead of dropping it as duplicates (ADVICE r2: silent record loss)."""
    gen = TransactionGenerator(num_users=20, num_merchants=10, seed=23)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=8))
    records = gen.generate_batch(6)
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    batch = job.assembler.next_batch(block=True, timeout_s=1.0)

    # break scoring (so txn-cache write-back never runs) AND fan-out
    real_produce = broker.produce
    real_dispatch = scorer.dispatch
    scorer.dispatch = lambda *a, **k: (_ for _ in ()).throw(RuntimeError())
    broker.produce = lambda *a, **k: (_ for _ in ()).throw(OSError("down"))
    ctx = job.dispatch_batch(batch, now=1000.0)
    with pytest.raises(OSError):
        job.complete_batch(ctx)
    broker.produce = real_produce
    scorer.dispatch = real_dispatch

    assert not job._inflight_ids          # released despite the exception
    assert broker.lag(job.config.group_id, T.TRANSACTIONS) == 6  # no commit

    # crash-restart: a new job in the same group replays from the committed
    # offset and must rescore the batch, not drop it as duplicates
    job2 = StreamJob(broker, scorer, JobConfig(max_batch=8))
    assert job2.run_until_drained(now=1001.0) == 6
    assert job2.counters["duplicates_skipped"] == 0
    assert broker.lag(job2.config.group_id, T.TRANSACTIONS) == 0


def test_consumer_commit_and_replay():
    b = InMemoryBroker()
    for i in range(10):
        b.produce(T.TRANSACTIONS, {"n": i}, key="k")
    c = b.consumer([T.TRANSACTIONS], "g")
    first = c.poll(4)
    assert len(first) == 4
    # crash without commit: a new consumer in the group re-reads everything
    c2 = b.consumer([T.TRANSACTIONS], "g")
    assert len(c2.poll(100)) == 10
    c2.commit()
    # committed: nothing left
    c3 = b.consumer([T.TRANSACTIONS], "g")
    assert c3.poll(100) == []
    assert b.lag("g", T.TRANSACTIONS) == 0


def test_unkeyed_round_robin_spreads():
    b = InMemoryBroker()
    for i in range(24):
        b.produce(T.TRANSACTIONS, {"n": i})
    ends = b.end_offsets(T.TRANSACTIONS)
    assert sum(ends) == 24
    assert max(ends) - min(ends) <= 1  # even spread


def test_fault_injection_at_least_once():
    """Drops delay delivery (position rewinds to the dropped record); every
    record still arrives eventually, and duplicates model redelivery."""
    b = InMemoryBroker()
    for i in range(200):
        b.produce(T.TRANSACTIONS, {"n": i}, key="k")
    f = FaultInjector(drop_prob=0.1, duplicate_prob=0.1, seed=42)
    c = b.consumer([T.TRANSACTIONS], "g", faults=f)
    ns = []
    polls = 0
    while len(set(ns)) < 200 and polls < 1000:
        ns.extend(r.value["n"] for r in c.poll(500))
        polls += 1
    assert set(ns) == set(range(200))  # at-least-once: nothing lost
    assert polls > 1                   # drops actually delayed delivery
    assert len(ns) > 200               # duplicates happened


def test_microbatch_size_trigger():
    b = InMemoryBroker()
    for i in range(300):
        b.produce(T.TRANSACTIONS, {"n": i}, key=str(i))
    a = MicrobatchAssembler(b.consumer([T.TRANSACTIONS], "g"), max_batch=256,
                            max_delay_ms=1e9)
    batch = a.next_batch(block=False)
    assert len(batch) == 256
    rest = a.next_batch(block=False)
    assert rest == []  # 44 pending, deadline infinite, size not reached
    assert len(a.flush()) == 44


def test_microbatch_deadline_trigger():
    b = InMemoryBroker()
    clock = [0.0]
    a = MicrobatchAssembler(
        b.consumer([T.TRANSACTIONS], "g"), max_batch=256, max_delay_ms=5.0,
        clock=lambda: clock[0],
    )
    for i in range(3):
        b.produce(T.TRANSACTIONS, {"n": i}, key="k")
    assert a.next_batch(block=False) == []   # pulls 3, deadline not passed
    clock[0] += 0.006                        # 6 ms later
    batch = a.next_batch(block=False)
    assert len(batch) == 3                   # deadline closed the batch


@pytest.fixture(scope="module")
def job_env():
    gen = TransactionGenerator(num_users=60, num_merchants=25, seed=11)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=32, max_delay_ms=1.0))
    return gen, broker, job


def test_stream_job_end_to_end(job_env):
    gen, broker, job = job_env
    records = gen.generate_batch(50)
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    scored = job.run_until_drained(now=1000.0)
    assert scored == 50
    preds = broker.consumer([T.PREDICTIONS], "check").poll(1000)
    assert len(preds) == 50
    enriched = broker.consumer([T.ENRICHED], "check").poll(1000)
    assert len(enriched) == 50
    assert all("fraud_score" in r.value for r in enriched)
    feats = broker.consumer([T.FEATURES], "check").poll(1000)
    assert len(feats) == 50
    assert len(feats[0].value["features"]) == 64
    # offsets are committed after fan-out
    assert broker.lag(job.config.group_id, T.TRANSACTIONS) == 0


def test_stream_job_replay_dedupe(job_env):
    """Re-delivering the same records must not double-score (exactly-once
    effect via txn-cache dedupe)."""
    gen, broker, job = job_env
    records = gen.generate_batch(10)
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    job.run_until_drained(now=2000.0)
    before = job.counters["scored"]
    # simulate redelivery (e.g. crash before commit): same records again
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    job.run_until_drained(now=2001.0)
    assert job.counters["scored"] == before
    assert job.counters["duplicates_skipped"] == 10
    # cache-hit duplicates re-emit their prediction ONCE each (at-least-
    # once delivery), even when redelivery lands both copies in one poll
    broker.produce_batch(T.TRANSACTIONS, records + records,
                         key_fn=lambda r: str(r["user_id"]))
    job.run_until_drained(now=2002.0)
    assert job.counters["scored"] == before
    preds = broker.consumer([T.PREDICTIONS], "rchk").poll(1000)
    from collections import Counter
    replayed = Counter(p.value["transaction_id"] for p in preds
                       if p.value["explanation"].get("replayed_from_cache"))
    # run 2 re-emitted each id once; run 3's double-copy collapsed to one
    assert set(replayed.values()) == {2}
    assert len(replayed) == 10


def test_enrichment_applies_with_analytics_only(job_env):
    """enable_enrichment must still blend when emit_enriched=False but the
    analytics stage consumes the enriched dicts."""
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator

    gen = TransactionGenerator(num_users=15, num_merchants=8, seed=13)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(
        max_batch=16, emit_enriched=False, enable_analytics=True,
        enable_enrichment=True))
    records = gen.generate_batch(20)
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    assert job.run_until_drained(now=1000.0) == 20
    # nothing on the enriched topic, but analytics saw blended scores
    assert not broker.consumer([T.ENRICHED], "c").poll(100)
    assert job.analytics.stats()["user_velocity"]["watermark"] > 0


def test_pipelined_dispatch_dedupes_in_flight():
    """A duplicate transaction_id in batch N+1 while batch N is still in
    flight (dispatched, not completed) must be skipped — the pipelined
    dedupe checks in-flight ids, not just the txn cache."""
    gen = TransactionGenerator(num_users=20, num_merchants=10, seed=17)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=8))
    records = gen.generate_batch(8)
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    batch1 = job.assembler.next_batch(block=True, timeout_s=1.0)
    ctx1 = job.dispatch_batch(batch1, now=1000.0)
    # redeliver the same records while ctx1 is in flight
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    batch2 = job.assembler.next_batch(block=True, timeout_s=1.0)
    ctx2 = job.dispatch_batch(batch2, now=1000.5)
    assert job.counters["duplicates_skipped"] == 8
    assert len(job.complete_batch(ctx1)) == 8
    assert job.complete_batch(ctx2) == []
    assert job.counters["scored"] == 8
    # all offsets committed (the empty ctx still commits its snapshot)
    assert broker.lag(job.config.group_id, T.TRANSACTIONS) == 0


def test_pipelined_commit_covers_only_dispatched_offsets():
    """Offsets snapshotted at dispatch: completing batch N must not commit
    past records polled for a later, still-uncommitted batch."""
    gen = TransactionGenerator(num_users=20, num_merchants=10, seed=19)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=8))
    broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(16),
                         key_fn=lambda r: str(r["user_id"]))
    batch1 = job.assembler.next_batch(block=True, timeout_s=1.0)
    ctx1 = job.dispatch_batch(batch1, now=1000.0)
    batch2 = job.assembler.next_batch(block=True, timeout_s=1.0)
    assert batch2
    job.dispatch_batch(batch2, now=1000.1)  # in flight, never completed
    job.complete_batch(ctx1)
    # only batch1's records are covered by the commit: batch2 replays
    lag = broker.lag(job.config.group_id, T.TRANSACTIONS)
    assert lag == len(batch2)


def test_depth3_crash_between_writeback_and_fanout_loses_nothing():
    """THE depth-3 failure drill: three batches in flight, the oldest
    crashes BETWEEN state write-back (finalize succeeded — records are in
    the txn cache) and fan-out (no prediction produced). The job dies
    (contract: completion failure propagates; later in-flights are
    abandoned). A restarted job must deliver a prediction for EVERY
    record: the cached-but-never-produced ones re-emit from the cache (not
    re-scored, velocity not double-counted), the rest re-score normally."""
    gen = TransactionGenerator(num_users=40, num_merchants=10, seed=31)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer,
                    JobConfig(max_batch=8, pipeline_depth=3))
    broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(24),
                         key_fn=lambda r: str(r["user_id"]))

    ctxs = []
    for i in range(3):
        batch = job.assembler.next_batch(block=True, timeout_s=1.0)
        assert batch
        ctxs.append(job.dispatch_batch(batch, now=1000.0 + i))
    n0 = len(ctxs[0].fresh)
    assert n0 > 0
    assert len(job._inflight_ids) == sum(len(c.fresh) for c in ctxs)

    real_produce = broker.produce
    broker.produce = lambda *a, **k: (_ for _ in ()).throw(OSError("down"))
    with pytest.raises(OSError):
        job.complete_batch(ctxs[0])   # finalize ran -> cache written;
    broker.produce = real_produce     # fan-out failed -> nothing produced

    assert len(job._inflight_ids) == sum(len(c.fresh) for c in ctxs[1:])
    # job crashes here: ctxs[1]/ctxs[2] are abandoned, nothing committed

    job2 = StreamJob(broker, scorer,
                     JobConfig(max_batch=8, pipeline_depth=3))
    rescored = job2.run_until_drained(now=1010.0)
    # batch-1 records are cache hits (scored, state written): re-emitted
    # from cache, not re-scored; everything else re-scores
    assert rescored == 24 - n0
    assert job2.counters["duplicates_skipped"] == n0
    assert broker.lag(job2.config.group_id, T.TRANSACTIONS) == 0
    preds = broker.consumer([T.PREDICTIONS], "chk").poll(1000)
    ids = {p.value["transaction_id"] for p in preds}
    assert len(preds) == 24 and len(ids) == 24   # every record delivered
    replayed = [p for p in preds
                if p.value["explanation"].get("replayed_from_cache")]
    assert len(replayed) == n0


def test_run_for_depth3_drains_and_scores_everything():
    """run_for with depth 3 completes every dispatched batch by return."""
    gen = TransactionGenerator(num_users=30, num_merchants=10, seed=37)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer,
                    JobConfig(max_batch=8, max_delay_ms=1.0,
                              pipeline_depth=3))
    broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(40),
                         key_fn=lambda r: str(r["user_id"]))
    scored = job.run_for(3.0)
    assert scored == 40
    assert not job._inflight_ids
    assert broker.lag(job.config.group_id, T.TRANSACTIONS) == 0


def test_topic_contract_mirrors_reference():
    """29 reference topics (27 regular + 2 compacted) with exact names and
    partition counts (create-topics.sh:60-151), plus the framework's one
    extension: the transaction-labels feedback stream."""
    from realtime_fraud_detection_tpu.stream.topics import TOPIC_SPECS

    assert len(TOPIC_SPECS) == 30
    assert TOPIC_SPECS[-1].name == "transaction-labels"
    by_name = {t.name: t for t in TOPIC_SPECS}
    assert by_name["payment-transactions"].partitions == 12
    assert by_name["user-profiles"].compacted
    assert by_name["merchant-profiles"].compacted
    assert sum(t.compacted for t in TOPIC_SPECS) == 2
    for expected in ("pattern-detection", "geographic-analysis",
                     "audit-logs", "user-sessions", "login-events",
                     "blacklist-updates", "system-alerts", "risk-signals",
                     "network-analysis", "dashboard-updates",
                     "reporting-data", "merchant-transactions",
                     "fraud-metrics", "transaction-metrics"):
        assert expected in by_name, expected


def test_poisoned_record_degrades_alone_not_the_batch():
    """Per-record degradation (TransactionProcessor.java:83-91): one record
    with a malformed amount must get its own REVIEW error result while its
    batch-mates score normally — not drag the whole batch onto the error
    path."""
    gen = TransactionGenerator(num_users=20, num_merchants=10, seed=37)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=16))
    records = gen.generate_batch(10)
    records[3] = dict(records[3], amount="not-a-number")
    records[7] = dict(records[7], geolocation="garbage",  # coerced, scores
                      hour_of_day="NaNish")
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    scored = job.run_until_drained(now=1000.0)
    assert scored == 9                       # record 3 diverted, 7 coerced
    assert job.counters["errors"] == 1
    preds = broker.consumer([T.PREDICTIONS], "check").poll(100)
    assert len(preds) == 10                  # nothing silently dropped
    by_id = {r.value["transaction_id"]: r.value for r in preds}
    bad = by_id[str(records[3]["transaction_id"])]
    assert bad["decision"] == "REVIEW" and bad["risk_level"] == "ERROR"
    assert "validation_errors" in bad["explanation"]
    ok = by_id[str(records[7]["transaction_id"])]
    assert ok["risk_level"] != "ERROR"       # coercion, not rejection
    good_scores = [v for k, v in by_id.items()
                   if k != str(records[3]["transaction_id"])]
    assert all(v["risk_level"] != "ERROR" for v in good_scores)
    assert broker.lag(job.config.group_id, T.TRANSACTIONS) == 0


def test_qos_overload_drill_ladder_shed_budget():
    """THE overload acceptance drill (ISSUE 1): offered load 2x the
    sustainable rate through the real assembler/job path on a virtual
    clock. Must hold, deterministically, on CPU:

    - the degradation ladder ENGAGES under overload and DISENGAGES with
      hysteresis once the backlog drains (transitions visible in the
      Prometheus exposition),
    - only low-priority records are shed, every shed record carries an
      explicit shed reason on the predictions topic,
    - admitted transactions' p99 stays inside the configured budget.
    """
    from realtime_fraud_detection_tpu.qos import run_overload_drill

    summary, job, plane = run_overload_drill(
        offered_multiplier=2.0, overload_s=1.0, recovery_s=1.0,
        budget_ms=20.0, seed=7, return_state=True)

    # every produced record is accounted for: scored or explicitly shed
    assert summary["scored"] + summary["shed"] == summary["produced"]
    assert summary["shed"] > 0

    # ladder engaged under overload and recovered after the drain
    assert summary["max_ladder_level"] >= 1
    ladder = summary["ladder"]
    assert ladder["transitions_down"] >= 1
    assert ladder["transitions_up"] >= 1
    assert ladder["level"] == 0                  # fully recovered

    # only low-priority records were shed (high never sheds by contract)
    for key in summary["shed_by_priority_reason"]:
        priority, _, reason = key.partition(":")
        assert priority != "high", key
        assert reason.startswith("shed:"), key

    # admitted p99 inside the budget — the whole point of the plane
    assert summary["admitted_latency_ms"]["p99"] <= summary["budget_ms"], \
        summary["admitted_latency_ms"]

    # the shed decisions are ON THE PREDICTIONS TOPIC as scores-with-reason
    preds = job.broker.consumer(
        [job.config.predictions_topic], "qos-check").poll(100_000)
    shed_records = [p.value for p in preds
                    if p.value.get("explanation", {}).get("shed")]
    assert len(shed_records) == summary["shed"]
    for rec in shed_records:
        assert rec["explanation"]["shed_reason"].startswith("shed:")
        assert rec["explanation"]["priority"] != "high"
        assert rec["risk_level"] == "SHED"
        assert rec["decision"] == "REVIEW"
    # scored + shed predictions all arrived: nothing silently dropped
    assert len(preds) == summary["produced"]

    # ladder transitions are observable through the Prometheus exposition
    text = plane.metrics.render_prometheus()
    assert "qos_ladder_level" in text
    down = [ln for ln in text.splitlines()
            if ln.startswith('qos_ladder_transitions_total{direction="down"}')]
    up = [ln for ln in text.splitlines()
          if ln.startswith('qos_ladder_transitions_total{direction="up"}')]
    assert down and int(float(down[0].split()[-1])) >= 1
    assert up and int(float(up[0].split()[-1])) >= 1
    assert "qos_shed_total" in text
    assert "qos_budget_remaining_seconds_bucket" in text


def test_qos_disabled_job_unchanged():
    """JobConfig without qos: no plane, no shed counter movement, results
    identical to the pre-QoS path."""
    gen = TransactionGenerator(num_users=10, num_merchants=5, seed=43)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=8))
    assert job.qos is None
    broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(8),
                         key_fn=lambda r: str(r["user_id"]))
    assert job.run_until_drained(now=1000.0) == 8
    assert job.counters["shed"] == 0


def test_job_topics_configurable_default_contract():
    """Topic names flow from JobConfig (reference JobConfig.java topic
    params); defaults are the §2.5 contract. A renamed predictions topic
    receives the results; the contract topic stays silent."""
    gen = TransactionGenerator(num_users=10, num_merchants=5, seed=41)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(
        max_batch=8, transactions_topic="shadow-txns",
        predictions_topic="shadow-preds", emit_features=False,
        emit_enriched=False))
    broker.produce_batch("shadow-txns", gen.generate_batch(8),
                         key_fn=lambda r: str(r["user_id"]))
    assert job.run_until_drained(now=1000.0) == 8
    assert len(broker.consumer(["shadow-preds"], "c").poll(100)) == 8
    assert broker.consumer([T.PREDICTIONS], "c2").poll(100) == []


def test_whole_batch_degradation_logs_its_cause(caplog):
    """A scorer failure degrades the batch (0.5 / REVIEW / ERROR, stream
    alive) — and the exception is readable from the log: first occurrence
    with its traceback, later ones a line each."""
    import logging

    gen = TransactionGenerator(num_users=20, num_merchants=10, seed=29)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=4))

    def refuse(*a, **k):
        raise ValueError("Mosaic failed to compile TPU kernel")

    scorer.dispatch = refuse
    broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(8),
                         key_fn=lambda r: str(r["user_id"]))
    with caplog.at_level(logging.ERROR,
                         logger="realtime_fraud_detection_tpu.stream.job"):
        job.run_until_drained()
    assert job.counters["errors"] == 8 and job.counters["scored"] == 8
    preds = broker.consumer([T.PREDICTIONS], "t").poll(100)
    assert all(p.value["risk_level"] == "ERROR" for p in preds)
    records = [r for r in caplog.records if "dispatch failed" in r.message]
    assert len(records) >= 2
    assert all("ValueError: Mosaic failed to compile" in r.getMessage()
               for r in records)
    assert records[0].exc_info is not None
    assert all(r.exc_info is None for r in records[1:])


# ---- the program's own spans and counters (ISSUE 23) ----------------------

@pytest.fixture(scope="module")
def spanned_run():
    """Five full batches of 8 through a traced job; the hand counts the
    span and counter tests compare with."""
    from realtime_fraud_detection_tpu.utils.config import TracingSettings

    gen = TransactionGenerator(num_users=30, num_merchants=10, seed=41)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(
        max_batch=8, max_delay_ms=1.0, tracing=TracingSettings(enabled=True)))
    records = gen.generate_batch(40)
    for i, r in enumerate(records):
        r["description"] = " ".join(["invoice"] * (1 + i % 7))
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    # what the tokenizer makes of the same records, through a scorer of
    # its own (assembling touches history and graph state)
    ref = FraudScorer(scorer_config=ScorerConfig(text_len=32))
    ref.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    masks = np.asarray(ref.assemble(records, now=1000.0).token_mask)
    scored = job.run_until_drained(now=1000.0)
    return job, scorer, records, masks, scored


def test_every_span_of_a_microbatch_once_per_batch(spanned_run):
    from realtime_fraud_detection_tpu.obs import scopes

    job, scorer, _, _, scored = spanned_run
    assert scored == 40 and job.counters["batches"] == 5
    stages = scorer.host_stats()["stages"]
    # 32 positions is one width: no bucket builds a family of programs
    every_batch = [(name, parent) for name, parent in scopes.BATCH_SPANS
                   if name != scopes.BUILD_PROGRAMS]
    assert set(stages) == {name for name, _ in every_batch}
    for name, parent in every_batch:
        if name == scopes.JOB_POLL:
            # the loop also polls when nothing is there (drained input)
            assert stages[name]["count"] >= 5
        else:
            assert stages[name]["count"] == 5, name
        assert stages[name]["parent"] == parent, name
        assert 0.0 <= stages[name]["self_s"] <= stages[name]["total_s"]
    # a parent's self time is its total minus its children's
    for parent in (scopes.ASSEMBLE, scopes.JOB_DISPATCH, scopes.JOB_COMPLETE):
        children = sum(stages[n]["total_s"] for n, p in every_batch
                       if p == parent)
        assert stages[parent]["self_s"] == pytest.approx(
            stages[parent]["total_s"] - children, abs=1e-9)
    # the five names the benchmark has read since PR 22
    assert {"assemble", "graph", "pack", "dispatch",
            "device_wait"} <= set(stages)


def test_token_counters_equal_a_hand_count(spanned_run):
    job, _, records, masks, _ = spanned_run
    text_len, bucket = 32, 8
    assert masks.shape == (len(records), text_len)
    assert len({int(n) for n in masks.sum(axis=1)}) > 3   # lengths vary
    assert job.counters["token_slots"] == 5 * bucket * text_len
    assert job.counters["token_slots_sq"] == 5 * bucket * text_len ** 2
    real = int(masks.sum())
    assert 0 < real < job.counters["token_slots"]
    assert job.counters["real_tokens"] == real


@pytest.fixture(scope="module")
def split_run():
    """Three full batches of 32 at ``text_len`` 256, one partition so that
    they hold the records in order: two long rows (two launches), none
    (one narrow launch), six (the unsplit launch)."""
    text_len, rows = 256, 32
    gen = TransactionGenerator(num_users=30, num_merchants=10, seed=43)
    broker = InMemoryBroker()
    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=text_len))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    job = StreamJob(broker, scorer, JobConfig(max_batch=rows,
                                              max_delay_ms=1.0))
    records = gen.generate_batch(3 * rows)
    long_rows = {3, 17} | {2 * rows + i for i in range(6)}
    for i, r in enumerate(records):
        r["description"] = " ".join(
            ["invoice"] * (200 if i in long_rows else 5 + i % 40))
    broker.produce_batch(T.TRANSACTIONS, records, key_fn=lambda r: "one")
    ref = FraudScorer(scorer_config=ScorerConfig(text_len=text_len))
    ref.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    masks = np.asarray(ref.assemble(records, now=1000.0).token_mask)
    scored = job.run_until_drained(now=1000.0)
    return job, scorer, masks, scored


def test_token_counters_sum_over_both_launches_of_a_split_batch(split_run):
    job, scorer, masks, scored = split_run
    narrow, full, rows, long_bucket = 128, 256, 32, 8
    lengths = masks.sum(axis=1)
    assert [int((lengths[i:i + rows] > narrow).sum())
            for i in (0, rows, 2 * rows)] == [2, 0, 6]
    c = job.counters
    assert scored == 3 * rows == c["scored"] and c["batches"] == 3
    # (32, 128) + (8, 256); (32, 128); (32, 256)
    launches = [(rows, narrow), (long_bucket, full), (rows, narrow),
                (rows, full)]
    assert c["token_slots"] == sum(b * w for b, w in launches)
    assert c["token_slots_sq"] == sum(b * w * w for b, w in launches)
    assert c["real_tokens"] == int(masks.sum())
    assert c["split_batches"] == 1
    assert c["short_text_rows"] == (rows - 2) + rows
    assert c["long_text_rows"] == 2 + rows        # the unsplit batch's rows
    assert c["short_text_rows"] + c["long_text_rows"] == c["scored"]
    stats = scorer.host_stats()["text_split"]
    assert {k: stats[k] for k in ("short_text_rows", "long_text_rows",
                                  "split_batches")} == {
        k: c[k] for k in ("short_text_rows", "long_text_rows",
                          "split_batches")}
    # the attention site once per launch; a family's compile-time launches
    # are not the traffic's and are not counted
    snap = scorer.kernel_snapshot()
    assert snap["dispatch"]["attention"] + snap["fallback"]["attention"] \
        == len(launches)
    # pack and dispatch stay one span each per job batch
    stages = scorer.host_stats()["stages"]
    assert stages["pack"]["count"] == stages["dispatch"]["count"] == 3


def test_the_attention_site_is_counted_at_the_launched_width():
    """192 positions are not a shape the fused core takes, 128 are: a
    split batch's narrow launch is a dispatch, its long one a fallback."""
    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    import jax

    scorer = FraudScorer(scorer_config=ScorerConfig(text_len=192),
                         mesh=build_mesh(devices=jax.devices()[:1]))
    scorer._platform = "tpu"      # the selector's view of a one-chip mesh
    assert not scorer.effective_use_pallas()
    assert scorer.effective_use_pallas(text_len=128)
    scorer._record_kernel_dispatch(32, 128)
    scorer._record_kernel_dispatch(8, 192)
    snap = scorer.kernel_snapshot()
    assert snap["dispatch"]["attention"] == 1
    assert snap["fallback"]["attention"] == 1


def test_traced_job_times_collections_into_the_tracer_snapshot(spanned_run):
    import gc

    job = spanned_run[0]
    before = len(gc.callbacks)
    job.run_for(0.05)                      # hook live only inside a loop
    assert len(gc.callbacks) == before
    snap = job.tracer.snapshot()["host_gc"]
    assert set(snap) == {"count", "seconds", "longest_ms"}
    assert snap["count"] >= 0 and snap["seconds"] >= 0.0
