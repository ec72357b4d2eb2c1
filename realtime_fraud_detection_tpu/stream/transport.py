"""Streaming transport: partitioned in-memory broker + gated Kafka backend.

The reference's data backbone is a 3-broker Kafka cluster with idempotent
lz4 producers and read_committed consumers (config/kafka/*.properties,
FraudDetectionJob.java:141-213). This module provides the same *semantics*
behind one interface:

- ``InMemoryBroker`` — partitioned, offset-addressed, consumer-group topic
  log entirely in process. This is the test/dev transport and the
  SURVEY.md §4 "fake in-process transport" testing strategy. Supports
  deterministic fault injection (drop/dup/delay) for failure-path tests.
- ``KafkaBroker`` (stream/kafka.py) — a real Kafka wire-protocol client
  (no library dependency) behind the same interface; ``NetBrokerClient``
  (stream/netbroker.py) — the framework's own networked durable broker.
  The interface is the contract, so transports are a deployment choice,
  not a rewrite (contract suite: tests/test_netbroker.py, test_kafka.py).

Offset semantics (the exactly-once story, SURVEY.md §5.4): consumers read
from their group's committed offset; commit happens only after downstream
write-back, so a crash replays the tail. Replay-idempotence is provided by
the scorer's transaction cache keyed on transaction_id.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Sequence

import numpy as np

from realtime_fraud_detection_tpu.stream.topics import TOPIC_SPECS, TopicSpec


@dataclasses.dataclass
class Record:
    topic: str
    partition: int
    offset: int
    key: Optional[str]
    value: Any
    timestamp: float


class StaleGenerationError(RuntimeError):
    """A generation-stamped produce/commit hit a partition fenced at a
    NEWER assignment generation: the writer lost ownership in a rebalance
    it has not observed yet — the classic zombie of an asymmetric
    partition (deaf to the coordinator, still reaching the broker). The
    write is refused loudly at the broker, the same way Kafka's producer
    epoch fences a zombie transactional producer; unstamped producers
    (external feeds that never participate in assignment) are unaffected.
    """


@dataclasses.dataclass
class FaultInjector:
    """Deterministic transport fault injection (absent in the reference —
    SURVEY.md §5.3 'fault injection: none').

    A *drop* models an in-flight delivery failure: the record is withheld
    from this poll AND the consumer position must not advance past it, so it
    is re-delivered on the next poll (at-least-once preserved). A *duplicate*
    models redelivery: the record appears twice in one poll.
    """

    drop_prob: float = 0.0
    duplicate_prob: float = 0.0
    seed: int = 0

    def __post_init__(self) -> None:
        self._rng = np.random.default_rng(self.seed)

    def apply(self, records: List[Record]) -> tuple[List[Record], Optional[Record]]:
        """Returns (delivered, first_dropped). Delivery truncates at the
        first drop so the caller can rewind its position to it."""
        out: List[Record] = []
        for r in records:
            u = self._rng.random()
            if u < self.drop_prob:
                return out, r
            out.append(r)
            if u > 1.0 - self.duplicate_prob:
                out.append(r)
        return out, None


class _PartitionLog:
    __slots__ = ("records", "lock")

    def __init__(self) -> None:
        self.records: List[Record] = []
        self.lock = threading.Lock()


class InMemoryBroker:
    """Partitioned topic log with consumer groups, single process."""

    def __init__(self, topics: Sequence[TopicSpec] = TOPIC_SPECS,
                 auto_create_partitions: int = 4):
        self._topics: Dict[str, List[_PartitionLog]] = {}
        self._committed: Dict[tuple, int] = {}   # (group, topic, part) -> next offset
        self._rr: Dict[str, int] = {}            # round-robin cursor per topic
        self._lock = threading.Lock()
        self._auto_partitions = auto_create_partitions
        # producer generation fences: (topic, partition) -> minimum
        # assignment generation a STAMPED produce/commit must carry. The
        # cluster coordinator bumps these in its rebalance fence step so
        # a partitioned-away worker is fenced at the WRITE seam, not just
        # the checkpoint seam (see StaleGenerationError).
        self._gen_fence: Dict[tuple, int] = {}
        self.fenced_produces = 0
        self.fenced_commits = 0
        for t in topics:
            self.create_topic(t.name, t.partitions)

    # ------------------------------------------------------------- topology
    def create_topic(self, name: str, partitions: int) -> None:
        with self._lock:
            if name not in self._topics:
                self._topics[name] = [_PartitionLog() for _ in range(partitions)]

    def _logs(self, topic: str) -> List[_PartitionLog]:
        logs = self._topics.get(topic)
        if logs is None:
            self.create_topic(topic, self._auto_partitions)
            logs = self._topics[topic]
        return logs

    def partitions(self, topic: str) -> int:
        return len(self._logs(topic))

    # -------------------------------------------------------------- produce
    def select_partition(self, topic: str, key: Optional[str]) -> int:
        """Key hash (same key -> same partition -> per-key ordering), or
        round-robin for unkeyed records, like Kafka's default partitioner.

        crc32, NOT ``hash()``: Python salts ``str.__hash__`` per process, so
        a WAL-backed broker restarted with ``hash()`` would route old keys to
        new partitions and break per-key ordering. Matches stream/kafka.py's
        partitioner so the two transports agree on key->partition."""
        logs = self._logs(topic)
        if key is not None:
            return zlib.crc32(key.encode()) % len(logs)
        with self._lock:
            part = self._rr.get(topic, 0) % len(logs)
            self._rr[topic] = part + 1
        return part

    def append(self, topic: str, partition: int, value: Any,
               key: Optional[str] = None,
               timestamp: Optional[float] = None) -> Record:
        """Append to a specific partition (produce = select + append; split
        so a durable front-end can write its WAL between the two)."""
        log = self._logs(topic)[partition]
        with log.lock:
            rec = Record(topic, partition, len(log.records), key, value,
                         # rtfd-lint: allow[wall-clock] record-timestamp default; callers pass ts
                         timestamp if timestamp is not None else time.time())
            log.records.append(rec)
        return rec

    def produce(self, topic: str, value: Any, key: Optional[str] = None,
                timestamp: Optional[float] = None,
                generation: Optional[int] = None) -> Record:
        """Append one record; partition chosen by key hash. A stamped
        ``generation`` is checked against the partition's producer fence
        (unstamped produces pass — generation fencing is opt-in, like
        Kafka's producer epochs)."""
        part = self.select_partition(topic, key)
        self.check_producer_generation(topic, part, generation)
        return self.append(topic, part, value, key, timestamp)

    # ------------------------------------------------ generation fencing
    def fence_producers(self, topic: str, partitions: Sequence[int],
                        generation: int) -> None:
        """Refuse future STAMPED produces/commits for these partitions
        whose generation is older than ``generation`` (monotonic: a fence
        never moves backwards)."""
        with self._lock:
            for p in partitions:
                key = (topic, int(p))
                if int(generation) > self._gen_fence.get(key, 0):
                    self._gen_fence[key] = int(generation)

    def producer_fence(self, topic: str, partition: int) -> int:
        return self._gen_fence.get((topic, int(partition)), 0)

    def check_producer_generation(self, topic: str, partition: int,
                                  generation: Optional[int],
                                  op: str = "produce") -> None:
        """Raise :class:`StaleGenerationError` when a stamped write hits
        a newer fence. ``None`` (unstamped) always passes."""
        if generation is None:
            return
        fence = self._gen_fence.get((topic, int(partition)))
        if fence is not None and int(generation) < fence:
            with self._lock:
                if op == "commit":
                    self.fenced_commits += 1
                else:
                    self.fenced_produces += 1
            raise StaleGenerationError(
                f"{op} to {topic}-{partition} at generation {generation} "
                f"refused: partition fenced at generation {fence} "
                f"(writer lost ownership in an unobserved rebalance)")

    def producer_fence_stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "fenced_produces": self.fenced_produces,
                "fenced_commits": self.fenced_commits,
                "fenced_partitions": len(self._gen_fence),
            }

    def produce_batch(self, topic: str, values: Iterable[Any],
                      key_fn: Optional[Callable[[Any], str]] = None) -> int:
        n = 0
        for v in values:
            self.produce(topic, v, key_fn(v) if key_fn else None)
            n += 1
        return n

    def produce_batch_stamped(self, topic: str,
                              items: Iterable[tuple]) -> int:
        """(key, value, timestamp) triples — contract parity with
        ``NetBrokerClient.produce_batch_stamped`` so drill producers run
        unchanged against either transport."""
        n = 0
        for k, v, ts in items:
            self.produce(topic, v, k, timestamp=ts)
            n += 1
        return n

    def produce_batch_keyed(self, topic: str,
                            items: Iterable[tuple]) -> int:
        """Batch produce of explicit (key, value) pairs — for payloads that
        do not carry their own routing key (e.g. the predictions fan-out,
        keyed by user but the §2.7 response has no user field). Networked
        brokers override this with a single-frame implementation; per-call
        produces over TCP cost one round trip EACH (measured 8.6x slower
        on loopback for a 256-record fan-out)."""
        n = 0
        for k, v in items:
            self.produce(topic, v, k)
            n += 1
        return n

    # -------------------------------------------------------------- consume
    def consumer(self, topics: Sequence[str], group_id: str,
                 faults: Optional[FaultInjector] = None,
                 partitions: Optional[Mapping[str, Sequence[int]]] = None,
                 ) -> "Consumer":
        """``partitions`` scopes the consumer to an explicit topic →
        partition-list assignment (the partition-parallel worker plane,
        cluster/fleet.py) instead of every partition of every topic."""
        return Consumer(self, list(topics), group_id, faults,
                        partitions=partitions)

    def end_offsets(self, topic: str) -> List[int]:
        return [len(p.records) for p in self._logs(topic)]

    def read(self, topic: str, partition: int, start: int, limit: int) -> List[Record]:
        log = self._logs(topic)[partition]
        with log.lock:
            return log.records[start:start + limit]

    # -------------------------------------------------------------- offsets
    def committed(self, group: str, topic: str, partition: int) -> int:
        return self._committed.get((group, topic, partition), 0)

    def commit(self, group: str, offsets: Mapping[tuple, int],
               generation: Optional[int] = None) -> None:
        # a stamped commit is fence-checked for EVERY partition BEFORE
        # any offset is applied: a zombie's commit must not advance the
        # group past records whose predictions were refused at the
        # produce fence (that would silently lose them)
        if generation is not None:
            for (topic, part) in offsets:
                self.check_producer_generation(topic, part, generation,
                                               op="commit")
        with self._lock:
            for (topic, part), off in offsets.items():
                key = (group, topic, part)
                if off > self._committed.get(key, 0):
                    self._committed[key] = off

    def lag(self, group: str, topic: str) -> int:
        return sum(
            max(0, end - self.committed(group, topic, p))
            for p, end in enumerate(self.end_offsets(topic))
        )


class Consumer:
    """Offset-tracking consumer over the in-memory broker.

    ``poll`` returns up to max_records across all assigned partitions from
    the *position* (not yet committed); ``commit`` durably advances the
    group offset. ``seek_to_committed`` rewinds to the last commit —
    the crash-recovery path.

    With an explicit ``partitions`` assignment (topic → partition list)
    the consumer reads ONLY those partitions — the partition-parallel
    worker plane's affinity contract (cluster/): N workers in one group,
    each scoped to a disjoint partition set. ``set_assignment`` adopts a
    new assignment mid-life (rebalance) and rewinds the new partitions to
    their committed offsets, exactly like a fresh member would.
    """

    def __init__(self, broker: InMemoryBroker, topics: List[str],
                 group_id: str, faults: Optional[FaultInjector] = None,
                 partitions: Optional[Mapping[str, Sequence[int]]] = None):
        self.broker = broker
        self.topics = topics
        self.group_id = group_id
        self.faults = faults
        self._assignment: Optional[Dict[str, List[int]]] = (
            {t: sorted(int(p) for p in parts)
             for t, parts in partitions.items()}
            if partitions is not None else None)
        self._position: Dict[tuple, int] = {}
        # networked brokers expose a monotonic reconnect epoch; each
        # consumer tracks its OWN last-seen value, so every consumer
        # sharing one client observes every reconnect (see poll)
        self._epoch_fn = getattr(broker, "reconnect_epoch", None)
        self._seen_epoch = self._epoch_fn() if self._epoch_fn else 0
        self.seek_to_committed()

    def _assigned(self, topic: str) -> Sequence[int]:
        if self._assignment is not None:
            return self._assignment.get(topic, ())
        return range(self.broker.partitions(topic))

    def set_assignment(self,
                       partitions: Mapping[str, Sequence[int]]) -> None:
        """Adopt a new explicit partition assignment (rebalance).

        Cooperative-sticky semantics: partitions RETAINED across the
        change keep their in-memory positions (rewinding them would
        re-poll records already sitting in the owner's assembler or in
        flight — a storm of cached-dup re-emissions for no safety gain);
        newly ACQUIRED partitions start from their committed offsets (the
        handoff contract: state was restored/replayed exactly to there);
        released partitions drop out of the position map."""
        self._assignment = {t: sorted(int(p) for p in parts)
                            for t, parts in partitions.items()}
        old = self._position
        self._position = {
            (t, p): old.get((t, p),
                            self.broker.committed(self.group_id, t, p))
            for t, parts in self._assignment.items()
            for p in parts
        }

    def assigned_partitions(self) -> Dict[str, List[int]]:
        return {t: list(self._assigned(t)) for t in self.topics}

    def seek_to_committed(self) -> None:
        self._position = {
            (t, p): self.broker.committed(self.group_id, t, p)
            for t in self.topics
            for p in self._assigned(t)
        }

    def poll(self, max_records: int = 256) -> List[Record]:
        # Networked brokers bump a reconnect epoch after a connection loss
        # (possibly a broker RESTART): the in-memory cursor may sit past
        # records that were polled but never committed when the connection
        # died — continuing from it would let the NEXT commit advance past
        # them (silent loss). Rewind to the committed offsets instead;
        # re-delivered records dedupe downstream (scorer txn-cache).
        # Epoch-compared per consumer: a shared client's OTHER consumers
        # each still see the reconnect on their own next poll.
        if self._epoch_fn is not None:
            epoch = self._epoch_fn()
            if epoch != self._seen_epoch:
                self._seen_epoch = epoch
                self.seek_to_committed()
        out: List[Record] = []
        for (t, p), pos in self._position.items():
            if len(out) >= max_records:
                break
            recs = self.broker.read(t, p, pos, max_records - len(out))
            if not recs:
                continue
            if self.faults is not None:
                recs, dropped = self.faults.apply(recs)
                if dropped is not None:
                    # position stops AT the dropped record: re-delivered on
                    # the next poll, never silently lost past a commit
                    self._position[(t, p)] = dropped.offset
                    out.extend(recs)
                    continue
            if recs:
                self._position[(t, p)] = recs[-1].offset + 1
                out.extend(recs)
        return out

    def commit(self, offsets: Optional[Dict[tuple, int]] = None) -> None:
        """Commit positions. With ``offsets`` (a ``snapshot_positions()``
        result), commit exactly those — the pipelined job snapshots positions
        at dispatch time so a batch still in flight on the device is never
        committed past by a later poll."""
        self.broker.commit(
            self.group_id,
            dict(self._position) if offsets is None else offsets)

    def snapshot_positions(self) -> Dict[tuple, int]:
        """Copy of current read positions keyed (topic, partition)."""
        return dict(self._position)

    def positions(self) -> Dict[str, int]:
        """JSON-safe snapshot of current read positions
        ("topic:partition" -> next offset) for checkpoint manifests."""
        return {f"{t}:{p}": pos for (t, p), pos in self._position.items()}

    def seek_to_positions(self, offsets: Mapping[str, int]) -> None:
        """Inverse of ``positions()``: restore read positions from a
        checkpoint manifest. The offsets-as-truth resume path (reference:
        Flink restores Kafka offsets from ITS checkpoint, not the broker,
        JobConfig.java exactly-once contract): scorer state and transport
        positions come from the SAME checkpoint, so effectively-once
        scoring holds across a restart even against a broker whose group
        offsets were lost."""
        for key, off in offsets.items():
            t, _, p = key.rpartition(":")
            self._position[(t, int(p))] = int(off)

    def lag(self) -> int:
        """Uncommitted lag over THIS consumer's assigned partitions (all
        partitions when unscoped) — a fleet of scoped consumers summing
        their lags must count each partition once, not once per worker."""
        total = 0
        for t in self.topics:
            ends = self.broker.end_offsets(t)
            for p in self._assigned(t):
                total += max(0, ends[p] - self.broker.committed(
                    self.group_id, t, p))
        return total


def KafkaTransport(bootstrap_servers: str = "localhost:9092", **kwargs):
    """Real Kafka adapter: the framework's own wire-protocol client
    (stream/kafka.py — no client-library dependency). Returns a
    ``KafkaBroker`` implementing this module's broker interface, so
    ``StreamJob(broker=KafkaTransport(...))`` runs unchanged against a
    cluster. Kept as a factory here for backward-compatible imports."""
    from realtime_fraud_detection_tpu.stream.kafka import KafkaBroker

    return KafkaBroker(bootstrap=bootstrap_servers, **kwargs)
