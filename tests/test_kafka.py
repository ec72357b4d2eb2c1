"""Kafka transport tests: wire codec units + the transport contract suite
over real sockets against the in-process protocol fake."""

import json
import struct
import threading
import time

import pytest

from realtime_fraud_detection_tpu.stream import topics as T
from realtime_fraud_detection_tpu.stream.kafka import (
    KafkaBroker,
    Reader,
    Writer,
    decode_message_set,
    encode_message_set,
)
from realtime_fraud_detection_tpu.stream.kafka_fake import FakeKafkaServer


# ---------------------------------------------------------------- wire codec


def test_message_set_round_trip():
    msgs = [(b"k1", b'{"a":1}', 123456), (None, b"v", 0), (b"k3", None, 7)]
    decoded = decode_message_set(encode_message_set(msgs))
    assert [(k, v, ts) for _o, k, v, ts in decoded] == msgs
    assert [o for o, *_ in decoded] == [0, 1, 2]


def test_message_set_truncated_tail_dropped():
    msgs = [(b"k", b"v1", 1), (b"k", b"v2", 2)]
    buf = encode_message_set(msgs)
    # chop mid-way through the second message (Kafka fetch semantics)
    decoded = decode_message_set(buf[: len(buf) - 3])
    assert len(decoded) == 1 and decoded[0][2] == b"v1"


def test_message_set_bad_crc_raises():
    buf = bytearray(encode_message_set([(b"k", b"value", 1)]))
    buf[-1] ^= 0xFF
    with pytest.raises(ValueError, match="CRC"):
        decode_message_set(bytes(buf))


def test_request_header_spec_shape():
    """The client must emit the spec header: api_key i16, api_version i16,
    correlation_id i32, client_id string — checked byte-for-byte, so a
    symmetric client/fake codec bug can't hide."""
    w = Writer().i16(3).i16(1).i32(42).string("cid")
    raw = w.done()
    assert raw == struct.pack(">hhi", 3, 1, 42) + struct.pack(">h", 3) + b"cid"
    r = Reader(raw)
    assert (r.i16(), r.i16(), r.i32(), r.string()) == (3, 1, 42, "cid")


# ------------------------------------------------------------ contract suite


@pytest.fixture()
def kafka_broker():
    server = FakeKafkaServer(port=0).start()
    broker = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}")
    try:
        yield broker
    finally:
        broker.close()
        server.stop()


def test_kafka_keyed_ordering(kafka_broker):
    b = kafka_broker
    for i in range(20):
        b.produce(T.TRANSACTIONS, {"n": i}, key="user_7")
    c = b.consumer([T.TRANSACTIONS], "g1")
    recs = c.poll(100)
    assert [r.value["n"] for r in recs] == list(range(20))
    assert len({r.partition for r in recs}) == 1


def test_kafka_commit_replay(kafka_broker):
    b = kafka_broker
    for i in range(10):
        b.produce(T.TRANSACTIONS, {"n": i}, key="k")
    c = b.consumer([T.TRANSACTIONS], "g")
    assert len(c.poll(4)) == 4
    c2 = b.consumer([T.TRANSACTIONS], "g")
    assert len(c2.poll(100)) == 10
    c2.commit()
    assert b.consumer([T.TRANSACTIONS], "g").poll(100) == []
    assert b.lag("g", T.TRANSACTIONS) == 0


def test_kafka_snapshot_commit(kafka_broker):
    b = kafka_broker
    for i in range(10):
        b.produce(T.TRANSACTIONS, {"n": i}, key="k")
    c = b.consumer([T.TRANSACTIONS], "g")
    assert len(c.poll(6)) == 6
    snap = c.snapshot_positions()
    assert len(c.poll(10)) == 4
    c.commit(snap)
    assert b.lag("g", T.TRANSACTIONS) == 4


def test_kafka_produce_batch_spreads(kafka_broker):
    b = kafka_broker
    n = b.produce_batch(T.TRANSACTIONS, [{"n": i} for i in range(24)],
                        key_fn=lambda v: str(v["n"] % 5))
    assert n == 24
    assert sum(b.end_offsets(T.TRANSACTIONS)) == 24
    # per-key ordering survives the batch path
    c = b.consumer([T.TRANSACTIONS], "g")
    recs = c.poll(100)
    per_key = {}
    for r in recs:
        per_key.setdefault(r.key, []).append(r.value["n"])
    for key, ns in per_key.items():
        assert ns == sorted(ns), f"key {key} out of order: {ns}"


def test_kafka_unicode_and_null_values(kafka_broker):
    b = kafka_broker
    b.produce(T.TRANSACTIONS, {"désc": "caffè ☕", "amount": 12.5}, key="ü")
    recs = b.consumer([T.TRANSACTIONS], "g").poll(10)
    assert recs[0].value == {"désc": "caffè ☕", "amount": 12.5}
    assert recs[0].key == "ü"


def test_stream_job_over_kafka():
    """The scoring job runs unchanged over the Kafka wire protocol."""
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
    from realtime_fraud_detection_tpu.stream import JobConfig, StreamJob

    server = FakeKafkaServer(port=0).start()
    broker = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}")
    try:
        gen = TransactionGenerator(num_users=30, num_merchants=12, seed=29)
        scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
        scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        job = StreamJob(broker, scorer, JobConfig(max_batch=16,
                                                  max_delay_ms=1.0))
        broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(40),
                             key_fn=lambda r: str(r["user_id"]))
        assert job.run_until_drained(now=1000.0) == 40
        preds = broker.consumer([T.PREDICTIONS], "check").poll(1000)
        assert len(preds) == 40
        assert broker.lag(job.config.group_id, T.TRANSACTIONS) == 0
    finally:
        broker.close()
        server.stop()


# --------------------------------------- RecordBatch v2 / idempotent producer


def test_record_batch_v2_layout_and_round_trip():
    """Spec-shape check written independently of the encoder: fixed header
    offsets (kafka.apache.org/protocol RecordBatch), CRC32C coverage, and a
    decode round-trip. The CRC32C known-answer ('123456789' -> 0xE3069283)
    pins the polynomial to Castagnoli, not zlib's CRC32."""
    from realtime_fraud_detection_tpu.stream.kafka import (
        crc32c,
        decode_record_batch,
        encode_record_batch,
    )

    assert crc32c(b"123456789") == 0xE3069283
    msgs = [(b"k1", b'{"a":1}', 1000), (None, b"v2", 1003)]
    buf = encode_record_batch(msgs, producer_id=9, producer_epoch=2,
                              base_sequence=17)
    base_offset, batch_len = struct.unpack_from(">qi", buf)
    assert base_offset == 0
    assert batch_len == len(buf) - 12            # bytes after the length field
    assert struct.unpack_from(">i", buf, 12)[0] == -1   # partitionLeaderEpoch
    assert buf[16] == 2                                 # magic
    crc = struct.unpack_from(">I", buf, 17)[0]
    assert crc == crc32c(buf[21:])               # crc covers attributes..end
    (attrs, last_delta, first_ts, max_ts, pid, epoch, seq,
     count) = struct.unpack_from(">hiqqqhii", buf, 21)
    assert (attrs, last_delta, first_ts, max_ts) == (0, 1, 1000, 1003)
    assert (pid, epoch, seq, count) == (9, 2, 17, 2)
    decoded, dpid, depoch, dseq = decode_record_batch(buf)
    assert (dpid, depoch, dseq) == (9, 2, 17)
    assert [(k, v, ts) for _o, k, v, ts in decoded] == msgs


def test_record_batch_gzip_round_trip():
    """Codec bit 1 (gzip) — the v2 analog of the reference's
    compression.type producer setting (producer.properties:11)."""
    import gzip

    from realtime_fraud_detection_tpu.stream.kafka import (
        crc32c,
        decode_record_batch,
        encode_record_batch,
    )

    msgs = [(b"k", json.dumps({"i": i, "pad": "x" * 200}).encode(), 1000 + i)
            for i in range(50)]
    plain = encode_record_batch(msgs, producer_id=3, producer_epoch=1,
                                base_sequence=5)
    packed = encode_record_batch(msgs, producer_id=3, producer_epoch=1,
                                 base_sequence=5, compression="gzip")
    # attributes codec bits say gzip; the wire form is genuinely smaller
    attrs = struct.unpack_from(">h", packed, 21)[0]
    assert attrs & 0x07 == 1
    assert len(packed) < len(plain) // 2
    # CRC covers the COMPRESSED form
    assert struct.unpack_from(">I", packed, 17)[0] == crc32c(packed[21:])
    decoded, pid, epoch, seq = decode_record_batch(packed)
    assert (pid, epoch, seq) == (3, 1, 5)
    assert [(k, v, ts) for _o, k, v, ts in decoded] == msgs

    with pytest.raises(ValueError, match="unsupported compression"):
        encode_record_batch(msgs, compression="lz4")


def test_kafka_gzip_producer_end_to_end():
    """Compressed idempotent produce through the wire client against the
    protocol fake; the consumer transparently decompresses."""
    server = FakeKafkaServer(port=0).start()
    broker = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}",
                         idempotent=True, compression="gzip")
    try:
        for i in range(30):
            broker.produce(T.TRANSACTIONS, {"n": i, "pad": "y" * 100},
                           key="user_1")
        recs = broker.consumer([T.TRANSACTIONS], "gz").poll(100)
        assert [r.value["n"] for r in recs] == list(range(30))
    finally:
        broker.close()
        server.stop()

    with pytest.raises(ValueError, match="compression requires"):
        KafkaBroker(bootstrap="127.0.0.1:1", compression="gzip")


def test_fetch_decode_gzip_wrapper_and_raw_v2():
    """What a REAL broker can hand a Fetch v2 consumer (the protocol fake
    re-serves uncompressed v1, so these forms are constructed by hand):
    a gzip wrapper message whose value is the inner message set, and a raw
    RecordBatch v2 the broker chose not to down-convert."""
    import gzip
    import zlib as _zlib

    from realtime_fraud_detection_tpu.stream.kafka import (
        Writer,
        decode_message_set,
        encode_message_set,
        encode_record_batch,
    )

    msgs = [(b"k0", b"v0", 10), (b"k1", b"v1", 11), (b"k2", b"v2", 12)]

    # --- gzip v1 wrapper: value = gzip(inner message set), wrapper offset
    # is the LAST inner message's absolute offset (v1 down-convert rule)
    inner = encode_message_set(msgs)
    body = (Writer().i8(1).i8(1)                  # magic=1, codec=gzip
            .i64(99).bytes_(None).bytes_(gzip.compress(inner)).done())
    crc = _zlib.crc32(body) & 0xFFFFFFFF
    wrapper_msg = Writer().u32(crc).raw(body).done()
    wire = Writer().i64(42).i32(len(wrapper_msg)).raw(wrapper_msg).done()
    decoded = decode_message_set(wire)
    assert [(k, v, ts) for _o, k, v, ts in decoded] == msgs
    assert [o for o, *_ in decoded] == [40, 41, 42]   # rebased to wrapper

    # --- raw RecordBatch v2 passthrough (no down-conversion)
    batch = encode_record_batch(msgs, compression="gzip")
    decoded2 = decode_message_set(batch)
    assert [(k, v, ts) for _o, k, v, ts in decoded2] == msgs


def test_record_batch_bad_crc_raises():
    from realtime_fraud_detection_tpu.stream.kafka import (
        decode_record_batch,
        encode_record_batch,
    )

    buf = bytearray(encode_record_batch([(b"k", b"v", 1)]))
    buf[-1] ^= 0xFF
    with pytest.raises(ValueError, match="CRC32C"):
        decode_record_batch(bytes(buf))


def test_idempotent_produce_dedupes_retried_batch():
    """enable.idempotence=true semantics: resending the SAME batch (same
    producer id + base sequence — what the client's retry path does after
    a lost ack) must append once; the broker acks the duplicate with the
    original base offset."""
    server = FakeKafkaServer(port=0).start()
    b = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}", idempotent=True)
    try:
        r1 = b.produce(T.TRANSACTIONS, {"n": 1}, key="k")
        # craft the retry: re-send the identical wire bytes (same sequence)
        from realtime_fraud_detection_tpu.stream.kafka import (
            encode_record_batch,
        )

        replay = encode_record_batch(
            [(b"k", b'{"n":1}', 1)], producer_id=b._pid,
            producer_epoch=b._pepoch, base_sequence=0)
        off = b._produce_request(T.TRANSACTIONS, r1.partition, replay,
                                 api_version=3)
        assert off == r1.offset                  # acked with original offset
        b.produce(T.TRANSACTIONS, {"n": 2}, key="k")   # next seq still works
        recs = b.read(T.TRANSACTIONS, r1.partition, 0, 100)
        assert [r.value["n"] for r in recs] == [1, 2]  # no duplicate append
    finally:
        b.close()
        server.stop()


def test_idempotent_sequence_gap_rejected():
    from realtime_fraud_detection_tpu.stream.kafka import (
        KafkaProtocolError,
        encode_record_batch,
    )

    server = FakeKafkaServer(port=0).start()
    b = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}", idempotent=True)
    try:
        r1 = b.produce(T.TRANSACTIONS, {"n": 1}, key="k")
        gap = encode_record_batch(
            [(b"k", b'{"n":9}', 1)], producer_id=b._pid,
            producer_epoch=b._pepoch, base_sequence=5)   # expected 1
        with pytest.raises(KafkaProtocolError, match="OUT_OF_ORDER"):
            b._produce_request(T.TRANSACTIONS, r1.partition, gap,
                               api_version=3)
    finally:
        b.close()
        server.stop()


# ------------------------------------------------------------ consumer groups


def _group_broker(server):
    return KafkaBroker(bootstrap=f"127.0.0.1:{server.port}")


def test_group_two_members_split_partitions():
    """Two members of one group get disjoint range assignments covering
    every partition; after one leaves, the survivor owns them all."""
    from realtime_fraud_detection_tpu.stream.kafka_group import (
        KafkaGroupConsumer,
    )

    server = FakeKafkaServer(port=0).start()
    b1, b2 = _group_broker(server), _group_broker(server)
    try:
        c1 = KafkaGroupConsumer(b1, [T.TRANSACTIONS], "g-split",
                                session_timeout_ms=2000,
                                heartbeat_interval_s=0.1)
        n_parts = b1.partitions(T.TRANSACTIONS)
        assert sorted(c1.assigned_partitions()[T.TRANSACTIONS]) == \
            list(range(n_parts))

        made = {}

        def _join_second():
            made["c2"] = KafkaGroupConsumer(
                b2, [T.TRANSACTIONS], "g-split",
                session_timeout_ms=2000, heartbeat_interval_s=0.1)

        t = threading.Thread(target=_join_second)
        t.start()
        # c1 discovers the rebalance via heartbeat inside poll and rejoins
        deadline = time.monotonic() + 8.0
        while "c2" not in made and time.monotonic() < deadline:
            c1.poll(10)
            time.sleep(0.05)
        t.join(timeout=8.0)
        c2 = made["c2"]
        p1 = set(c1.assigned_partitions().get(T.TRANSACTIONS, []))
        p2 = set(c2.assigned_partitions().get(T.TRANSACTIONS, []))
        assert p1 and p2 and not (p1 & p2)
        assert p1 | p2 == set(range(n_parts))
        # clean leave -> survivor reclaims everything
        c2.close()
        deadline = time.monotonic() + 8.0
        while (set(c1.assigned_partitions().get(T.TRANSACTIONS, []))
               != set(range(n_parts))
               and time.monotonic() < deadline):
            c1.poll(10)
            time.sleep(0.05)
        assert set(c1.assigned_partitions()[T.TRANSACTIONS]) == \
            set(range(n_parts))
        c1.close()
    finally:
        b1.close()
        b2.close()
        server.stop()


def test_group_kill_consumer_no_record_loss():
    """The 'done' criterion: kill a consumer mid-stream
    (process death: no LeaveGroup, heartbeats just stop). The survivor must
    adopt its partitions from the committed offsets — every record is
    consumed, nothing lost, and nothing the dead member committed is
    re-consumed."""
    import time as _time

    from realtime_fraud_detection_tpu.stream.kafka_group import (
        KafkaGroupConsumer,
    )

    server = FakeKafkaServer(port=0).start()
    b1, b2 = _group_broker(server), _group_broker(server)
    prod = _group_broker(server)
    try:
        prod.produce_batch(T.TRANSACTIONS, [{"n": i} for i in range(200)],
                           key_fn=lambda v: str(v["n"]))
        c1 = KafkaGroupConsumer(b1, [T.TRANSACTIONS], "g-kill",
                                session_timeout_ms=1000,
                                heartbeat_interval_s=0.1)
        seen_c1 = []
        # two-member group
        made = {}
        t = threading.Thread(target=lambda: made.update(c2=KafkaGroupConsumer(
            b2, [T.TRANSACTIONS], "g-kill", session_timeout_ms=1000,
            heartbeat_interval_s=0.1)))
        t.start()
        deadline = _time.monotonic() + 8.0
        while "c2" not in made and _time.monotonic() < deadline:
            c1.poll(0)          # heartbeat/rejoin only — read nothing, so
            _time.sleep(0.05)   # everything c1 commits is recorded below
        t.join(timeout=8.0)
        c2 = made["c2"]

        # c1 consumes + commits a first slice of its partitions, then DIES
        recs = c1.poll(40)
        seen_c1 = [r.value["n"] for r in recs]
        c1.commit()                               # committed: must not replay
        victim = c1.membership.member_id
        server.kill_member("g-kill", victim)      # session expiry, no leave

        # survivor polls until it has adopted everything and drained
        seen_c2 = []
        deadline = _time.monotonic() + 10.0
        while _time.monotonic() < deadline:
            for r in c2.poll(100):
                seen_c2.append(r.value["n"])
            c2.commit()
            n_parts = b2.partitions(T.TRANSACTIONS)
            owned = set(c2.assigned_partitions().get(T.TRANSACTIONS, []))
            if owned == set(range(n_parts)) and c2.lag() == 0:
                break
            _time.sleep(0.05)

        assert set(seen_c1) | set(seen_c2) == set(range(200))  # nothing lost
        # nothing c1 committed was re-delivered to the survivor
        assert not (set(seen_c1) & set(seen_c2))
        assert c2.membership.rebalances >= 2      # join + post-kill rejoin
        c2.close()
    finally:
        b1.close()
        b2.close()
        prod.close()
        server.stop()


def test_group_rebalance_mid_stream_survivor_no_double_processing():
    """Chaos satellite: `kill_member` MID-STREAM — records still arriving
    while the coordinator expires one member's session. The group
    rebalances onto the survivor (all partitions reassigned) and records
    produced across the rebalance all arrive. The surviving member never
    re-processes anything it COMMITTED (its committed positions survive
    the generation change); a round whose commit is fenced by the
    rebalance replays at-least-once — bounded, never a loop — and once
    the group settles the survivor replays nothing at all."""
    import time as _time

    from realtime_fraud_detection_tpu.stream.kafka_group import (
        KafkaGroupConsumer,
    )

    server = FakeKafkaServer(port=0).start()
    b1, b2 = _group_broker(server), _group_broker(server)
    prod = _group_broker(server)
    try:
        prod.produce_batch(T.TRANSACTIONS, [{"n": i} for i in range(120)],
                           key_fn=lambda v: str(v["n"]))
        c1 = KafkaGroupConsumer(b1, [T.TRANSACTIONS], "g-mid",
                                session_timeout_ms=1000,
                                heartbeat_interval_s=0.1)
        made = {}
        t = threading.Thread(target=lambda: made.update(c2=KafkaGroupConsumer(
            b2, [T.TRANSACTIONS], "g-mid", session_timeout_ms=1000,
            heartbeat_interval_s=0.1)))
        t.start()
        deadline = _time.monotonic() + 8.0
        while "c2" not in made and _time.monotonic() < deadline:
            c1.poll(0)
            _time.sleep(0.05)
        t.join(timeout=8.0)
        c2 = made["c2"]

        # both members consume mid-stream, committing every round; this
        # pre-kill commit lands in a stable group, so it MUST stick
        seen_c1, seen_c2 = [], []
        pre_slots = set()               # (topic, partition, offset) at c2
        for consumer, seen in ((c1, seen_c1), (c2, seen_c2)):
            for r in consumer.poll(30):
                seen.append(r.value["n"])
                if consumer is c2:
                    pre_slots.add((r.topic, r.partition, r.offset))
            consumer.commit()

        # the kill lands between commits, with more records still to come
        server.kill_member("g-mid", c1.membership.member_id)
        prod.produce_batch(T.TRANSACTIONS,
                           [{"n": i} for i in range(120, 200)],
                           key_fn=lambda v: str(v["n"]))

        post_slots: list = []
        deadline = _time.monotonic() + 10.0
        while _time.monotonic() < deadline:
            for r in c2.poll(100):
                seen_c2.append(r.value["n"])
                post_slots.append((r.topic, r.partition, r.offset))
            c2.commit()
            n_parts = b2.partitions(T.TRANSACTIONS)
            owned = set(c2.assigned_partitions().get(T.TRANSACTIONS, []))
            if owned == set(range(n_parts)) and c2.lag() == 0:
                break
            _time.sleep(0.05)

        # partitions reassigned: the survivor owns every one
        n_parts = b2.partitions(T.TRANSACTIONS)
        assert set(c2.assigned_partitions()[T.TRANSACTIONS]) == \
            set(range(n_parts))
        assert c2.membership.rebalances >= 2
        # nothing lost across the rebalance (c1's uncommitted reads are
        # re-delivered to the survivor — at-least-once across MEMBERS)
        assert set(seen_c1) | set(seen_c2) == set(range(200))
        # the survivor NEVER re-processed a record it committed...
        assert not pre_slots & set(post_slots)
        # ...and a rebalance-fenced round replays at most once (bounded
        # at-least-once, not a redelivery loop)
        counts: dict = {}
        for slot in post_slots:
            counts[slot] = counts.get(slot, 0) + 1
        assert max(counts.values()) <= 2
        # settled group: everything committed, nothing replays
        assert c2.poll(100) == []
        c2.close()
    finally:
        b1.close()
        b2.close()
        prod.close()
        server.stop()


def test_group_zombie_commit_is_fenced():
    """A member evicted by the coordinator must NOT be able to advance
    offsets (ILLEGAL_GENERATION/UNKNOWN_MEMBER fencing) — the new owner's
    position wins, so a zombie can't cause silent skips."""
    from realtime_fraud_detection_tpu.stream.kafka_group import (
        KafkaGroupConsumer,
    )

    server = FakeKafkaServer(port=0).start()
    b1 = _group_broker(server)
    try:
        prod = _group_broker(server)
        prod.produce_batch(T.TRANSACTIONS, [{"n": i} for i in range(20)],
                           key_fn=lambda v: str(v["n"]))
        c1 = KafkaGroupConsumer(b1, [T.TRANSACTIONS], "g-fence",
                                session_timeout_ms=1000,
                                heartbeat_interval_s=0.1)
        c1.poll(20)
        positions = c1.snapshot_positions()
        # evict c1 (simulated zombie: it still thinks it's a member)
        server.kill_member("g-fence", c1.membership.member_id)
        c1.commit(positions)                      # fenced: swallowed + rejoin
        committed = {
            (t, p): b1.committed("g-fence", t, p) for (t, p) in positions
        }
        assert all(off == 0 for off in committed.values())
        prod.close()
    finally:
        b1.close()
        server.stop()


def test_group_background_heartbeat_survives_processing_gap():
    """A processing gap longer than the session timeout (e.g. a first-batch
    XLA compile) must NOT get the member evicted: the background heartbeat
    thread keeps the session alive between poll() calls, so the post-gap
    commit is not fenced."""
    from realtime_fraud_detection_tpu.stream.kafka_group import (
        KafkaGroupConsumer,
    )

    server = FakeKafkaServer(port=0).start()
    b = _group_broker(server)
    prod = _group_broker(server)
    try:
        prod.produce_batch(T.TRANSACTIONS, [{"n": i} for i in range(10)],
                           key_fn=lambda v: str(v["n"]))
        c = KafkaGroupConsumer(b, [T.TRANSACTIONS], "g-gap",
                               session_timeout_ms=800,
                               heartbeat_interval_s=0.2)
        recs = c.poll(10)
        assert recs
        gen_before = c.membership.generation
        time.sleep(2.0)                   # >2x the session timeout, no poll
        c.commit()                        # must not be fenced
        assert c.membership.generation == gen_before   # no eviction/rejoin
        committed = sum(
            b.committed("g-gap", T.TRANSACTIONS, p)
            for p in range(b.partitions(T.TRANSACTIONS)))
        assert committed == len(recs)
        c.close()
    finally:
        b.close()
        prod.close()
        server.stop()


# ------------------------------------------------- golden wire-byte fixtures
# No Kafka broker or JVM exists in this image (a review asked for
# real-broker bytes; that is impossible here), so these fixtures are the next
# strongest thing: complete frames hand-assembled with raw struct.pack from
# the PUBLIC spec (kafka.apache.org/protocol), sharing no code with the
# client's Writer/encoder — a symmetric client/fake codec bug cannot satisfy
# both the encoder test and these byte-level expectations.


def _raw_str(s: str) -> bytes:
    b = s.encode()
    return struct.pack(">h", len(b)) + b


def _raw_bytes(b: bytes) -> bytes:
    return struct.pack(">i", len(b)) + b


def test_golden_produce_v2_request_bytes():
    """KafkaBroker's Produce v2 body must equal the spec frame assembled
    by hand: acks i16, timeout i32, [topic -> [partition, record_set]]."""
    import zlib

    from realtime_fraud_detection_tpu.stream.kafka import encode_message_set

    record_set = encode_message_set([(b"k", b"v", 1234)])
    # hand-build the same MessageSet: offset i64=0, size i32, crc u32,
    # magic i8=1, attrs i8=0, ts i64, key bytes, value bytes
    body = struct.pack(">bbq", 1, 0, 1234) + _raw_bytes(b"k") + _raw_bytes(b"v")
    msg = struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF) + body
    expected_set = struct.pack(">qi", 0, len(msg)) + msg
    assert record_set == expected_set

    got = (
        Writer().i16(-1).i32(30000)
        .array([None], lambda w, _:
               w.string("topic-a").array([None], lambda w2, _2:
                                         w2.i32(3).bytes_(record_set)))
        .done()
    )
    expected = (
        struct.pack(">hi", -1, 30000)
        + struct.pack(">i", 1) + _raw_str("topic-a")
        + struct.pack(">i", 1) + struct.pack(">i", 3)
        + _raw_bytes(expected_set)
    )
    assert got == expected


def test_golden_join_group_v1_request_bytes():
    """JoinGroup v1 body layout: group, session i32, rebalance i32, member,
    protocol_type, [protocol name + metadata bytes] — and the subscription
    metadata itself (version i16, topics array, user_data bytes)."""
    from realtime_fraud_detection_tpu.stream.kafka_group import (
        encode_subscription,
    )

    meta = encode_subscription(["t-b", "t-a"])
    expected_meta = (
        struct.pack(">h", 0)                      # version
        + struct.pack(">i", 2) + _raw_str("t-a") + _raw_str("t-b")  # sorted
        + _raw_bytes(b"")                         # user_data
    )
    assert meta == expected_meta

    got = (
        Writer().string("grp").i32(10000).i32(10000).string("")
        .string("consumer")
        .array([("range", meta)], lambda w, p: w.string(p[0]).bytes_(p[1]))
        .done()
    )
    expected = (
        _raw_str("grp") + struct.pack(">ii", 10000, 10000) + _raw_str("")
        + _raw_str("consumer")
        + struct.pack(">i", 1) + _raw_str("range") + _raw_bytes(expected_meta)
    )
    assert got == expected


def test_golden_record_batch_v2_full_bytes():
    """A one-record idempotent batch, byte-for-byte: every header field at
    its spec offset, varint record body assembled by hand (zigzag LEB128)."""
    from realtime_fraud_detection_tpu.stream.kafka import (
        crc32c,
        encode_record_batch,
    )

    got = encode_record_batch([(b"K", b"VAL", 5000)], producer_id=77,
                              producer_epoch=3, base_sequence=9)
    # record: attrs i8=0, ts_delta varint(0)=0x00, offset_delta varint(0),
    # key len varint(1)=0x02 + b"K", val len varint(3)=0x06 + b"VAL",
    # headers varint(0)
    record_body = bytes([0, 0x00, 0x00, 0x02]) + b"K" + bytes([0x06]) + b"VAL" + bytes([0x00])
    record = bytes([len(record_body) << 1]) + record_body   # varint length
    after_crc = (
        struct.pack(">hiqqqhii", 0, 0, 5000, 5000, 77, 3, 9, 1) + record
    )
    expected = (
        struct.pack(">qi", 0, 4 + 1 + 4 + len(after_crc))   # base, length
        + struct.pack(">ibI", -1, 2, crc32c(after_crc))
        + after_crc
    )
    assert got == expected


def test_group_membership_churn_no_deadlock():
    """Members joining and leaving repeatedly while others poll must never
    deadlock the membership lock / background heartbeat thread, and the
    group must converge to full coverage after the churn stops."""
    from realtime_fraud_detection_tpu.stream.kafka_group import (
        KafkaGroupConsumer,
    )

    server = FakeKafkaServer(port=0).start()
    stable_b = _group_broker(server)
    try:
        stable = KafkaGroupConsumer(stable_b, [T.TRANSACTIONS], "g-churn",
                                    session_timeout_ms=2000,
                                    heartbeat_interval_s=0.1)
        stop = time.monotonic() + 6.0
        errors: list = []

        def churner(n: int):
            try:
                while time.monotonic() < stop:
                    b = _group_broker(server)
                    c = KafkaGroupConsumer(b, [T.TRANSACTIONS], "g-churn",
                                           session_timeout_ms=2000,
                                           heartbeat_interval_s=0.1)
                    c.poll(5)
                    time.sleep(0.1)
                    c.close()
                    b.close()
            except Exception as e:  # noqa: BLE001
                errors.append(f"churner {n}: {type(e).__name__}: {e}")

        churners = [threading.Thread(target=churner, args=(i,))
                    for i in range(3)]
        for t in churners:
            t.start()
        # the stable member keeps polling through the churn
        while time.monotonic() < stop:
            stable.poll(5)
            time.sleep(0.05)
        for t in churners:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in churners), "churner hung"
        assert not errors, errors

        # after the churn: stable member reconverges to ALL partitions
        n_parts = stable_b.partitions(T.TRANSACTIONS)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            stable.poll(5)
            owned = set(stable.assigned_partitions().get(T.TRANSACTIONS, []))
            if owned == set(range(n_parts)):
                break
            time.sleep(0.1)
        assert set(stable.assigned_partitions()[T.TRANSACTIONS]) == \
            set(range(n_parts))
        assert stable.membership.rebalances >= 2
        stable.close()
    finally:
        stable_b.close()
        server.stop()


def test_netbroker_three_node_rf3_minisr2_failover_drill():
    """The compose-topology failover drill (deploy/docker-compose.yml: one
    primary + TWO sync replicas, minISR=2 — the reference's 3-broker
    RF=3/minISR=2 cluster, create-topics.sh:9-12): kill the primary
    mid-traffic, promote replica 1, re-attach replica 2 to the survivor.
    Every acked record must survive, committed offsets must carry over
    (nothing already committed re-delivers), and the ISR must re-form."""
    from realtime_fraud_detection_tpu.stream.netbroker import (
        BrokerServer,
        HaBrokerClient,
        NetBrokerClient,
    )

    primary = BrokerServer(port=0, role="primary", min_isr=2).start()
    replica1 = BrokerServer(port=0, role="replica").start()
    replica2 = BrokerServer(port=0, role="replica").start()
    client = None
    try:
        primary.add_replica("127.0.0.1", replica1.port)
        primary.add_replica("127.0.0.1", replica2.port)
        assert primary.isr_size() == 3            # RF=3: self + 2 replicas

        addrs = [("127.0.0.1", primary.port), ("127.0.0.1", replica1.port),
                 ("127.0.0.1", replica2.port)]
        client = HaBrokerClient(addrs)
        acked = []
        for i in range(50):
            client.produce(T.TRANSACTIONS, {"n": i}, key="k")
            acked.append(i)                       # min_isr=2 ack: durable

        # a consumer group makes progress and commits on the primary;
        # commits forward to BOTH replicas synchronously
        consumer = client.consumer([T.TRANSACTIONS], "drill")
        first = consumer.poll(20)
        assert len(first) == 20
        consumer.commit()

        # ---- primary dies mid-traffic ----
        primary.stop()
        NetBrokerClient(port=replica1.port).promote()
        # the survivor re-forms the ISR with the remaining replica (its
        # link belonged to the dead primary)
        replica1.add_replica("127.0.0.1", replica2.port)
        assert replica1.isr_size() == 2

        # the SAME HA client keeps working: rotates off the dead address,
        # produces against the promoted node (an ack-lost retry may
        # duplicate — at-least-once, consumers dedupe by id)
        for i in range(50, 60):
            client.produce(T.TRANSACTIONS, {"n": i}, key="k")
            acked.append(i)

        # a post-failover consumer in the SAME group resumes from the
        # committed offset on the survivor: nothing committed re-delivers,
        # nothing acked is lost
        survivor_consumer = client.consumer([T.TRANSACTIONS], "drill")
        rest = [r.value["n"] for r in survivor_consumer.poll(1000)]
        seen_before = {r.value["n"] for r in first}
        assert not (set(rest) & seen_before)      # committed => not replayed
        assert set(rest) | seen_before >= set(acked)  # every ack survived
        survivor_consumer.commit()
        assert client.lag("drill", T.TRANSACTIONS) == 0

        # replica 2 kept replicating through the promotion: its log holds
        # every acked record too (read-only reads are allowed on replicas)
        r2 = NetBrokerClient(port=replica2.port)
        r2_total = sum(r2.end_offsets(T.TRANSACTIONS))
        assert r2_total >= len(acked)
        r2.close()
    finally:
        if client is not None:
            client.close()
        for server in (primary, replica1, replica2):
            try:
                server.stop()
            except Exception:  # noqa: BLE001 — primary already stopped
                pass


def test_fetch_large_backlog_across_polls():
    """A backlog far larger than one fetch response (4 MiB cap, truncated
    tail per Kafka semantics) must stream completely and in order across
    successive polls."""
    server = FakeKafkaServer(port=0).start()
    b = KafkaBroker(bootstrap=f"127.0.0.1:{server.port}")
    try:
        big = "x" * 64_000                       # ~64 KB per record value
        n = 200                                  # ~12.8 MB total, 4 MiB cap
        b.produce_batch(T.TRANSACTIONS, [{"n": i, "pad": big}
                                         for i in range(n)],
                        key_fn=lambda v: "one-key")   # single partition
        c = b.consumer([T.TRANSACTIONS], "g-big")
        seen = []
        for _ in range(50):
            recs = c.poll(500)
            if not recs:
                break
            seen.extend(r.value["n"] for r in recs)
        assert seen == list(range(n))            # complete and ordered
    finally:
        b.close()
        server.stop()
