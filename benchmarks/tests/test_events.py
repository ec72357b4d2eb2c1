import json

import numpy as np
import pytest

from benchmarks.harness import events as E
from benchmarks.harness import spec


def _pool(seed, traffic_name="s64-saturated", n=2048):
    traffic = json.loads(
        (spec.BENCH / "traffic" / f"{traffic_name}.json").read_text())
    traffic["pool_events"] = n
    rng = np.random.default_rng(seed)
    pop = E.Population(3000, 400, rng)
    return pop, E.build_pool(pop, traffic, rng), traffic


def test_same_seed_same_events_other_seed_other_events():
    _, a, _ = _pool(7)
    _, b, _ = _pool(7)
    _, c, _ = _pool(8)
    sa = a.materialize(range(300), np.arange(300) / 100.0)
    assert sa == b.materialize(range(300), np.arange(300) / 100.0)
    assert sa != c.materialize(range(300), np.arange(300) / 100.0)


def test_replay_gives_fresh_ids_references_and_times():
    _, pool, _ = _pool(1, n=64)
    first, again = pool.materialize([3, 3 + 64], [0.0, 10.0])
    assert first["user_id"] == again["user_id"]          # same pool event
    assert first["transaction_id"] != again["transaction_id"]
    assert first["description"] != again["description"]
    assert first["timestamp"] < again["timestamp"]
    assert E.seq_of(again["transaction_id"]) == 67
    assert E.seq_of("txn_0001") == -1


@pytest.mark.parametrize("name,text_len", [("s64-saturated", 64),
                                           ("s512-longtail-saturated", 512),
                                           ("s512-fulltext-saturated", 512)])
def test_text_length_is_what_the_tokenizer_counts(name, text_len):
    """The pool's recorded length equals what the scorer's tokenizer makes
    of the combined text, and follows the traffic file's distribution
    above the floor the fixed words of the text set."""
    from realtime_fraud_detection_tpu.models.text import combined_text
    from realtime_fraud_detection_tpu.models.tokenizer import FraudTokenizer

    pop, pool, traffic = _pool(3, name, n=4096)
    tok = FraudTokenizer(max_length=4096)
    merchants = pop.merchant_profiles()
    for i, ev in enumerate(pool.materialize(range(200), np.zeros(200))):
        mp = merchants[ev["merchant_id"]]
        text = combined_text({"merchant_name": mp["name"],
                              "description": ev["description"],
                              "category": mp["category"], "location": ""})
        assert len(tok.encode(text)) == pool.text_tokens[i]
    d = traffic["text_tokens"]
    p50, p99 = np.percentile(pool.text_tokens, [50, 99])
    assert abs(p50 - d["median"]) <= max(1, 0.03 * d["median"])
    want99 = min(d["max"], d["median"] * np.exp(2.326 * d["sigma"]))
    assert 0.8 * want99 <= p99 <= 1.2 * want99
    assert pool.text_tokens.max() <= d["max"]


def test_memo_share_sets_how_often_a_text_repeats():
    """memo_share 1.0: every description is unique, also on replay. Below
    it, an event without a memo carries its merchant's one descriptor, so
    the same combined text recurs with the merchant, and the recorded
    length is still what the tokenizer counts."""
    from realtime_fraud_detection_tpu.models.text import combined_text
    from realtime_fraud_detection_tpu.models.tokenizer import FraudTokenizer

    def texts(share, n=2048):
        traffic = json.loads(
            (spec.BENCH / "traffic" / "s64-saturated.json").read_text())
        traffic.update(pool_events=n, memo_share=share)
        rng = np.random.default_rng(11)
        pop = E.Population(3000, 400, rng)
        pool = E.build_pool(pop, traffic, rng)
        merchants = pop.merchant_profiles()
        evs = pool.materialize(range(2 * n), np.zeros(2 * n))
        out = [combined_text({
            "merchant_name": merchants[e["merchant_id"]]["name"],
            "description": e["description"],
            "category": merchants[e["merchant_id"]]["category"],
            "location": ""}) for e in evs]
        return pool, evs, out

    pool, evs, all_unique = texts(1.0)
    assert len(set(all_unique)) == len(all_unique)
    pool3, evs3, some = texts(0.3)
    assert [e["user_id"] for e in evs3] == [e["user_id"] for e in evs]
    share_unique = len(set(some)) / len(some)
    assert 0.3 <= share_unique < 0.5       # 30% memos + <= 400 descriptors
    tok = FraudTokenizer(max_length=4096)
    for i in range(100):
        assert len(tok.encode(some[i])) == pool3.text_tokens[i]
    assert abs(np.median(pool3.text_tokens) - 12) <= 2


def test_merchants_are_zipf_users_uniform():
    pop, pool, _ = _pool(5, n=8192)
    ranks = np.array([int(e["merchant_id"].split("_")[1], 16)
                      for e in pool.events])
    # Zipf(1) over 400 merchants: rank 0 draws ~15%, the top 10 about 45%
    assert 0.10 < np.mean(ranks == 0) < 0.20
    assert 0.35 < np.mean(ranks < 10) < 0.55
    users = {e["user_id"] for e in pool.events}
    assert len(users) > 0.9 * 3000 * (1 - np.exp(-8192 / 3000))


# sha256 of the first 1,000 events (JSON, sorted keys) of seed 7 over 3,000
# users / 400 merchants and a 1,024-event pool, made by the generator as it
# stood before ``user_zipf_s`` existed (commit 5359c62)
STREAMS_BEFORE_USER_ZIPF = {
    "s512-longtail-saturated":
        "c39c66ad600b0219c33564867016faef7c5002e79e2608a7d467a0893f11cb9e",
    "s512-fulltext-saturated":
        "bcd08c45a092664fb622b6a120177dc2abb4e28ad1963d63ad2801b13bbd1903",
}


@pytest.mark.parametrize("name", sorted(STREAMS_BEFORE_USER_ZIPF))
def test_a_traffic_file_without_user_zipf_s_streams_what_it_always_did(name):
    import hashlib

    _, pool, traffic = _pool(7, name, n=1024)
    assert "user_zipf_s" not in traffic
    events = pool.materialize(range(1000), np.zeros(1000))
    assert hashlib.sha256(json.dumps(events, sort_keys=True).encode()
                          ).hexdigest() == STREAMS_BEFORE_USER_ZIPF[name]


def test_user_zipf_s_makes_users_return():
    traffic = json.loads(
        (spec.BENCH / "traffic" / "s64-saturated.json").read_text())
    traffic.update(pool_events=8192, user_zipf_s=1.0)
    rng = np.random.default_rng(5)
    pop = E.Population(3000, 400, rng)
    pool = E.build_pool(pop, traffic, rng)
    ranks = np.array([int(e["user_id"].split("_")[1], 16)
                      for e in pool.events])
    # Zipf(1) over 3,000 users: rank 0 draws ~12%, the top 10 about 34%
    assert 0.08 < np.mean(ranks == 0) < 0.16
    assert 0.27 < np.mean(ranks < 10) < 0.41
    # merchants keep their own skew
    m = np.array([int(e["merchant_id"].split("_")[1], 16)
                  for e in pool.events])
    assert 0.10 < np.mean(m == 0) < 0.20


def test_events_pass_the_stream_sanitizer_unchanged_in_count():
    from realtime_fraud_detection_tpu.serving.validation import (
        sanitize_for_stream,
    )

    _, pool, _ = _pool(2, n=128)
    for ev in pool.materialize(range(128), np.zeros(128)):
        txn, errors = sanitize_for_stream(ev)
        assert not errors and txn["description"] == ev["description"]


def test_two_processes_derive_the_same_stream_from_cell_and_seed(monkeypatch):
    """Parent and producer process each call ``make_stream``: same
    schedule, same events, and another seed gives another stream."""
    import rehearsal

    # the open-loop cell is parked (``benchmarks/parked.json``)
    monkeypatch.setattr(spec, "benchmark", rehearsal.with_parked)
    cell = spec.cell("s64-steady")
    cell["config_data"]["population"] = {"users": 500, "merchants": 50}
    cell["traffic_data"].update(pool_events=256, rate_txn_per_s=100)
    a = E.make_stream(cell, 4, 3.0)
    b = E.make_stream(cell, 4, 3.0)
    c = E.make_stream(cell, 5, 3.0)
    assert a.mode == "open_loop" and len(a.offsets) > 300
    assert np.array_equal(a.offsets, b.offsets)
    assert a.pool.events == b.pool.events
    assert a.pool.desc_prefix == b.pool.desc_prefix
    assert not np.array_equal(a.offsets[:50], c.offsets[:50])
    assert (np.diff(a.offsets) >= 0).all()
    assert a.offsets[-1] < 3.0 + cell["traffic_data"]["warmup_s"]
