"""End-to-end soak: simulator -> stream job -> trained scorer -> topics.

The reference has no test suite at all (SURVEY.md §4); its substitute is
dummy-model fallbacks plus a shell health check. This soak closes the loop
the reference never did: traffic with a known injected fraud mix (~5.5%,
simulator.py:106-127) flows through the full pipeline with TRAINED tree
models, and the output scores must actually separate the injected fraud.
"""

import numpy as np
import pytest

from realtime_fraud_detection_tpu.features.extract import extract_features
from realtime_fraud_detection_tpu.scoring import (
    FraudScorer,
    ScorerConfig,
    init_scoring_models,
)
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.stream import (
    InMemoryBroker,
    JobConfig,
    StreamJob,
)
from realtime_fraud_detection_tpu.stream import topics as T
from realtime_fraud_detection_tpu.training import GBDTTrainer


def _auc(y, score):
    order = np.argsort(score)
    rank = np.empty(len(score), float)
    rank[order] = np.arange(1, len(score) + 1)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float(
        (rank[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


@pytest.fixture(scope="module")
def trained_job():
    import jax

    gen = TransactionGenerator(num_users=400, num_merchants=100, seed=21,
                               tps=20.0)
    # train trees on the encoded path (same §2.3 feature contract)
    batch, labels = gen.generate_encoded(6000)
    x = np.asarray(extract_features(batch))
    y = labels["is_fraud"].astype(np.float32)
    trees = GBDTTrainer(n_estimators=40, max_depth=5, seed=2).fit(x, y)

    models = init_scoring_models(jax.random.PRNGKey(0))
    models = models.replace(trees=trees)

    scorer = FraudScorer(models=models,
                         scorer_config=ScorerConfig(text_len=32))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    broker = InMemoryBroker()
    job = StreamJob(broker, scorer, JobConfig(max_batch=128))

    records = gen.generate_batch(1500)
    broker.produce_batch(T.TRANSACTIONS, records,
                         key_fn=lambda r: str(r["user_id"]))
    scored = job.run_until_drained(now=1_000_000.0)
    return records, broker, scored


class TestSoak:
    def test_everything_scored_exactly_once(self, trained_job):
        records, broker, scored = trained_job
        assert scored == 1500
        preds = broker.consumer([T.PREDICTIONS], "soak").poll(10_000)
        assert len(preds) == 1500
        ids = [p.value["transaction_id"] for p in preds]
        assert len(set(ids)) == 1500

    def test_injected_fraud_rate_in_band(self, trained_job):
        """Simulator injects ~5.5% fraud (simulator.py:106-127)."""
        records, _, _ = trained_job
        rate = np.mean([bool(r.get("is_fraud")) for r in records])
        assert 0.02 <= rate <= 0.10, f"fraud mix drifted: {rate:.3f}"

    def test_trained_pipeline_separates_fraud(self, trained_job):
        """E2E AUC: scores coming out of the FULL pipeline (state joins,
        feature extraction, fused ensemble with 4 random branches + trained
        trees at weight 0.40) must rank injected fraud above normals."""
        records, broker, _ = trained_job
        labels = {str(r["transaction_id"]): bool(r.get("is_fraud"))
                  for r in records}
        preds = broker.consumer([T.PREDICTIONS], "soak2").poll(10_000)
        y = np.asarray([labels[p.value["transaction_id"]] for p in preds],
                       float)
        s = np.asarray([p.value["fraud_probability"] for p in preds])
        auc = _auc(y, s)
        assert auc > 0.75, f"end-to-end AUC too low: {auc:.3f}"

    def test_fraud_scores_higher_on_average(self, trained_job):
        records, broker, _ = trained_job
        labels = {str(r["transaction_id"]): bool(r.get("is_fraud"))
                  for r in records}
        preds = broker.consumer([T.PREDICTIONS], "soak3").poll(10_000)
        fraud = [p.value["fraud_probability"] for p in preds
                 if labels[p.value["transaction_id"]]]
        normal = [p.value["fraud_probability"] for p in preds
                  if not labels[p.value["transaction_id"]]]
        assert np.mean(fraud) > np.mean(normal) + 0.02


def test_multiprocess_group_failover_no_record_loss():
    """The 'done' criterion: two real StreamJob WORKER
    PROCESSES in one consumer group over the Kafka wire protocol; one is
    SIGKILLed mid-stream. The survivor adopts the dead worker's partitions
    from committed offsets: every transaction ends up scored (nothing
    lost), and duplicate predictions are bounded by the dead worker's
    uncommitted tail (at-least-once; a kill landing between fan-out and
    offset commit legitimately replays that window — cross-process
    exactly-once would need the shared state tier or a transactional
    outbox, asserted elsewhere via test_shared_state.py)."""
    import os
    import subprocess
    import sys
    import time

    from realtime_fraud_detection_tpu.stream.kafka import KafkaBroker
    from realtime_fraud_detection_tpu.stream.kafka_fake import FakeKafkaServer

    server = FakeKafkaServer(port=0).start()
    worker_src = r"""
import sys, time
import jax
jax.config.update("jax_platforms", "cpu")
from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.stream import JobConfig, StreamJob
from realtime_fraud_detection_tpu.stream.kafka import KafkaBroker

port = int(sys.argv[1])
broker = KafkaBroker(bootstrap=f"127.0.0.1:{port}")

class GroupBroker:
    def __getattr__(self, k): return getattr(broker, k)
    def consumer(self, topics, group_id, faults=None):
        return broker.consumer(topics, group_id, group_managed=True)

gen = TransactionGenerator(num_users=40, num_merchants=15, seed=101)
scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
job = StreamJob(GroupBroker(), scorer,
                JobConfig(max_batch=16, max_delay_ms=5.0))
job.consumer.membership.session_timeout_ms = 2000
print("READY", flush=True)
deadline = time.time() + 120
while time.time() < deadline:
    batch = job.assembler.next_batch(block=False)
    if not batch:
        batch = job.assembler.flush()
    if batch:
        job.process_batch(batch, now=1000.0)
        print(f"SCORED {job.counters['scored']}", flush=True)
    else:
        time.sleep(0.05)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(sys.path))

    def spawn():
        return subprocess.Popen(
            [sys.executable, "-c", worker_src, str(server.port)],
            env=env, stdout=subprocess.PIPE, text=True, bufsize=1)

    w1 = spawn()
    try:
        assert w1.stdout.readline().strip() == "READY"
        w2 = spawn()
        assert w2.stdout.readline().strip() == "READY"

        prod = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}",
                           idempotent=True)
        gen = TransactionGenerator(num_users=40, num_merchants=15, seed=101)
        records = gen.generate_batch(120)
        prod.produce_batch(T.TRANSACTIONS, records,
                           key_fn=lambda r: str(r["user_id"]))

        # let w1 score a couple of batches, then kill it hard. The reads
        # are select-bounded: partition skew can leave w1 with few records,
        # and a blocking readline would stall the test for the worker's
        # whole internal deadline.
        import select

        deadline = time.time() + 30
        scored_lines = 0
        while scored_lines < 2 and time.time() < deadline:
            ready, _, _ = select.select([w1.stdout], [], [], 1.0)
            if not ready:
                continue
            line = w1.stdout.readline()
            if line.startswith("SCORED"):
                scored_lines += 1
            elif not line:
                break
        w1.kill()                     # SIGKILL: no LeaveGroup, no commit
        w1.wait(timeout=10)

        # wait until the predictions topic covers every transaction id
        check = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}")
        want = {str(r["transaction_id"]) for r in records}
        seen: list = []
        deadline = time.time() + 90
        consumer = check.consumer([T.PREDICTIONS], "verify")
        while time.time() < deadline:
            seen.extend(r.value["transaction_id"] for r in consumer.poll(500))
            if set(seen) >= want:
                break
            time.sleep(0.25)
        w2.kill()
        assert set(seen) >= want, (
            f"lost {len(want - set(seen))} of {len(want)} transactions")
        # duplicates may only come from w1's uncommitted tail (one batch
        # window, max_batch=16 + one in-flight batch), never wholesale
        n_dups = len(seen) - len(set(seen))
        assert n_dups <= 32, (
            f"{n_dups} duplicate predictions — more than the uncommitted "
            "tail can explain; replay fencing is broken")
        prod.close()
        check.close()
    finally:
        for p in (w1, w2):
            if p.poll() is None:
                p.kill()
        server.stop()
