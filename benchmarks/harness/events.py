"""Seeded population and transaction events: the benchmark's own generator.

A copy of ``sim/simulator.TransactionGenerator``'s schema (the reference
JSON transaction, simulator.py:78-101) extended for a benchmark:

- every event carries a ``description``. A share ``memo_share`` of the
  events (traffic file; 1.0 = all) ends it with a per-event unique
  reference, so the scorer's whole-text token cache cannot answer for the
  tokenizer; the others carry their merchant's one fixed descriptor, so
  the cache answers when the merchant recurs;
- the length of the combined text (``models/text.combined_text``), counted
  as the scorer's tokenizer counts it, follows the traffic file's
  distribution;
- merchants are drawn Zipf(s); users uniformly, or Zipf(``user_zipf_s``)
  where the traffic file has that key (returning users: entity state,
  history windows);
- a pool of distinct events is built once and replayed with a fresh
  ``transaction_id``, reference and event time per pass, so a run of any
  length costs a few seconds of generation.

No fraud patterns are applied (no operation of the traffic may fail and no
label is read). Everything is drawn from one ``numpy`` generator made from
``--seed``: the same seed gives the same events.
"""

from __future__ import annotations

import dataclasses
from datetime import datetime, timedelta, timezone
from typing import Any, Dict, List, Sequence

import numpy as np

# (category, mcc, risk, average amount, fraud rate) — simulator.py's tuples
MERCHANT_CATEGORY_TUPLES = (
    ("retail", "5399", "low", 50.0, 0.01),
    ("grocery", "5411", "low", 25.0, 0.005),
    ("gas_station", "5542", "medium", 40.0, 0.02),
    ("restaurant", "5812", "low", 35.0, 0.008),
    ("online_retail", "5399", "medium", 75.0, 0.025),
    ("gambling", "7995", "high", 200.0, 0.15),
    ("adult_entertainment", "5967", "high", 100.0, 0.12),
    ("pharmacy", "5912", "medium", 30.0, 0.01),
    ("jewelry", "5944", "high", 500.0, 0.08),
    ("electronics", "5732", "medium", 300.0, 0.03),
)
_SUSPICIOUS_TOKENS = ("Crypto Exchange", "Gift Card Outlet",
                      "Wire Transfer Co", "Casino Royale", "Bitcoin Mart")
_PLAIN_TOKENS = ("Market", "Store", "Shop", "House", "Depot", "Corner", "Bros")
_KYC = ("verified", "pending", "rejected")
_TXN_TYPES = ("purchase", "refund", "authorization")
_PAYMENT_METHODS = ("credit_card", "debit_card", "digital_wallet",
                    "bank_transfer")
_CARD_TYPES = ("visa", "mastercard", "amex", "discover")
_USER_AGENTS = (
    "Mozilla/5.0 (X11; Linux x86_64) AppleWebKit/537.36 Chrome/120.0",
    "Mozilla/5.0 (iPhone; CPU iPhone OS 17_0 like Mac OS X) Safari/604.1",
    "Mozilla/5.0 (Windows NT 10.0; Win64; x64) Gecko/20100101 Firefox/121.0",
    "Mozilla/5.0 (Macintosh; Intel Mac OS X 14_2) Version/17.2 Safari/605.1",
)
# memo / invoice vocabulary the descriptions are drawn from
_WORDS = (
    "payment invoice order subscription renewal monthly annual transfer "
    "purchase store online pos terminal memo rent utilities deposit refund "
    "booking ticket delivery shipping service fee charge installment plan "
    "membership account balance top up recurring contract customer number "
    "reference item items quantity total tax discount coupon loyalty points "
    "branch counter kiosk mobile app web checkout cart basket gift voucher "
    "insurance premium policy claim tuition course hotel flight taxi ride "
    "fuel parking toll grocery pharmacy clinic dental repair parts labour "
    "hardware software license cloud hosting domain streaming music video "
    "game donation charity salary bonus expense travel office supplies "
    "furniture appliance electronics phone tablet laptop accessory cable"
).split()

EPOCH = datetime(2026, 1, 5, 8, 0, tzinfo=timezone.utc)   # event-time base


class Population:
    """Users and merchants, with the profile dicts the scorer joins on
    (``simulator.UserPool`` / ``MerchantPool`` distributions)."""

    def __init__(self, num_users: int, num_merchants: int,
                 rng: np.random.Generator):
        n = self.num_users = int(num_users)
        self.user_ids = [f"user_{i:08x}" for i in range(n)]
        self.risk_score = rng.beta(2, 8, n)
        self.avg_amount = rng.lognormal(4, 1, n)
        self.txn_frequency = rng.gamma(2, 2, n).astype(np.int64) + 1
        self.kyc_code = rng.choice(3, n, p=[0.85, 0.12, 0.03])
        self.account_age_days = rng.uniform(0, 730, n)
        self.pref_start = rng.integers(6, 11, n)
        self.pref_end = rng.integers(18, 24, n)
        self.weekend_activity = rng.uniform(0.3, 1.0, n)
        self.intl_ratio = rng.uniform(0.0, 0.1, n)
        self.online_preference = rng.uniform(0.5, 0.95, n)
        self.home_lat = rng.uniform(-60, 60, n)
        self.home_lon = rng.uniform(-180, 180, n)
        self.n_devices = rng.integers(1, 4, n)

        m = self.num_merchants = int(num_merchants)
        self.merchant_ids = [f"merchant_{i:08x}" for i in range(m)]
        cat_idx = rng.integers(0, len(MERCHANT_CATEGORY_TUPLES), m)
        cats = [MERCHANT_CATEGORY_TUPLES[c] for c in cat_idx]
        self.category = [c[0] for c in cats]
        self.mcc = [c[1] for c in cats]
        self.risk_level = [c[2] for c in cats]
        self.m_avg_amount = (np.array([c[3] for c in cats])
                             * rng.uniform(0.5, 2.0, m))
        suspicious = rng.random(m) < 0.05
        self.m_fraud_rate = np.where(
            suspicious, np.minimum(np.array([c[4] for c in cats]) * 3.0, 0.3),
            np.array([c[4] for c in cats]))
        self.is_blacklisted = rng.random(m) < 0.02
        self.op_start = rng.integers(6, 11, m)
        self.op_end = rng.integers(20, 25, m)
        self.m_lat = rng.uniform(-60, 60, m)
        self.m_lon = rng.uniform(-180, 180, m)
        tok = rng.integers(0, 5, m)
        self.names = [
            f"Biz {i} " + (_SUSPICIOUS_TOKENS if suspicious[i]
                           else _PLAIN_TOKENS)[tok[i]]
            for i in range(m)]

    def user_profiles(self) -> Dict[str, Dict[str, Any]]:
        out = {}
        for i, uid in enumerate(self.user_ids):
            out[uid] = {
                "user_id": uid,
                "risk_score": float(self.risk_score[i]),
                "account_age_days": float(self.account_age_days[i]),
                "kyc_status": _KYC[self.kyc_code[i]],
                "avg_transaction_amount": float(self.avg_amount[i]),
                "transaction_frequency": int(self.txn_frequency[i]),
                "device_fingerprints": [
                    f"dev_{i:08x}_{d}" for d in range(self.n_devices[i])],
                "behavioral_patterns": {
                    "preferred_time_start": int(self.pref_start[i]),
                    "preferred_time_end": int(self.pref_end[i]),
                    "weekend_activity": float(self.weekend_activity[i]),
                    "international_transactions": float(self.intl_ratio[i]),
                    "online_preference": float(self.online_preference[i]),
                },
            }
        return out

    def merchant_profiles(self) -> Dict[str, Dict[str, Any]]:
        out = {}
        for i, mid in enumerate(self.merchant_ids):
            out[mid] = {
                "merchant_id": mid,
                "name": self.names[i],
                "category": self.category[i],
                "mcc": self.mcc[i],
                "risk_level": self.risk_level[i],
                "avg_transaction_amount": float(self.m_avg_amount[i]),
                "fraud_rate": float(self.m_fraud_rate[i]),
                "is_blacklisted": bool(self.is_blacklisted[i]),
                "operating_hours": {"start_hour": str(int(self.op_start[i])),
                                    "end_hour": str(int(self.op_end[i]))},
            }
        return out


def token_count(text: str) -> int:
    """Tokens the scorer's word tokenizer makes of ``text`` before it
    truncates: [CLS] + words + [SEP] (``models/tokenizer.FraudTokenizer``
    preprocessing: lowercase, non-alphanumerics to spaces)."""
    from realtime_fraud_detection_tpu.models.tokenizer import FraudTokenizer

    return len(FraudTokenizer.preprocess(text).split()) + 2


def target_lengths(dist: Dict[str, Any], n: int,
                   rng: np.random.Generator) -> np.ndarray:
    """Combined-text lengths in tokens from the traffic file's
    distribution (``lognormal``: median, sigma, clipped to [min, max])."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = rng.lognormal(np.log(dist["median"]), dist["sigma"], n)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass
class EventPool:
    """Distinct events, replayed: ``materialize(i, seq, t)`` gives pool
    event ``i`` its own id, reference and event time."""

    events: List[Dict[str, Any]]
    desc_prefix: List[str]          # description words before the reference
    text_tokens: np.ndarray         # combined-text length, untruncated
    # the merchant's fixed descriptor where the event carries no memo of
    # its own, else None
    fixed_desc: List[Any] = None

    def __len__(self) -> int:
        return len(self.events)

    def materialize(self, seqs: Sequence[int], offsets_s: Sequence[float],
                    id_prefix: str = "b") -> List[Dict[str, Any]]:
        """Events for stream positions ``seqs`` (pool index = seq modulo
        pool size) at event times ``EPOCH + offsets_s``."""
        n_pool, events = len(self.events), self.events
        prefix = self.desc_prefix
        fixed = self.fixed_desc or [None] * n_pool
        out = []
        last_off, stamp, hour = None, "", 0
        for seq, off in zip(seqs, offsets_s):
            i = seq % n_pool
            ev = dict(events[i])
            ev["transaction_id"] = f"{id_prefix}{seq:09d}"
            ev["description"] = fixed[i] or f"{prefix[i]}ref{seq:x}"
            if off != last_off:         # a backlog is all due at once
                clock = EPOCH + timedelta(seconds=float(off))
                last_off, stamp, hour = off, clock.isoformat(), clock.hour
            ev["timestamp"] = stamp
            ev["hour_of_day"] = hour
            out.append(ev)
        return out


def seq_of(transaction_id: str, id_prefix: str = "b") -> int:
    """Inverse of ``materialize``'s id; -1 for an id it did not make."""
    if transaction_id.startswith(id_prefix):
        try:
            return int(transaction_id[len(id_prefix):])
        except ValueError:
            pass
    return -1


def build_pool(pop: Population, traffic: Dict[str, Any],
               rng: np.random.Generator) -> EventPool:
    n = int(traffic["pool_events"])

    def zipf(population: int, s: float) -> np.ndarray:
        # Zipf(s) over rank: p(rank r) ~ r^-s
        p = np.arange(1, population + 1, dtype=np.float64) ** -s
        return rng.choice(population, n, p=p / p.sum())

    # without the key, the draw every stream made before it existed
    u = (zipf(pop.num_users, float(traffic["user_zipf_s"]))
         if "user_zipf_s" in traffic else rng.integers(0, pop.num_users, n))
    m = zipf(pop.num_merchants, float(traffic["merchant_zipf_s"]))
    amount = np.maximum(1.0, np.round(
        pop.avg_amount[u] * rng.normal(1.0, 0.3, n) * rng.normal(1.0, 0.2, n),
        2))
    intl = rng.random(n) < pop.intl_ratio[u]
    lat = np.where(intl, rng.uniform(-90, 90, n),
                   pop.home_lat[u] + rng.normal(0, 0.5, n))
    lon = np.where(intl, rng.uniform(-180, 180, n),
                   pop.home_lon[u] + rng.normal(0, 0.5, n))
    dev = rng.integers(0, 1 << 30, n) % pop.n_devices[u]
    ttype = rng.integers(0, len(_TXN_TYPES), n)
    pmeth = rng.integers(0, len(_PAYMENT_METHODS), n)
    ctype = rng.integers(0, len(_CARD_TYPES), n)
    last4 = rng.integers(1000, 10000, n)
    agent = rng.integers(0, len(_USER_AGENTS), n)
    ip = rng.integers(0, 256, (n, 4))
    ip[:, 0] = 11 + ip[:, 0] % 212
    prior = rng.uniform(0.0, 0.3, n)

    # tokens of the combined text with a one-word description, per merchant
    from realtime_fraud_detection_tpu.models.text import combined_text

    base_len: Dict[int, int] = {}
    for mi in np.unique(m):
        base_len[int(mi)] = token_count(combined_text({
            "merchant_name": pop.names[mi], "description": "x",
            "category": pop.category[mi], "location": ""}))
    base = np.array([base_len[int(mi)] for mi in m])
    want = target_lengths(traffic["text_tokens"], n, rng)
    extra = np.maximum(0, want - base)          # words before the reference
    words = rng.integers(0, len(_WORDS), int(extra.sum()))

    # plain Python values up front: indexing numpy scalars in the loop
    # below costs more than everything else in it
    u_l, m_l, dev_l = u.tolist(), m.tolist(), dev.tolist()
    amount_l, prior_l = amount.tolist(), prior.tolist()
    lat_l, lon_l = lat.tolist(), lon.tolist()
    m_lat, m_lon = pop.m_lat.tolist(), pop.m_lon.tolist()
    ttype_l, pmeth_l, ctype_l = ttype.tolist(), pmeth.tolist(), ctype.tolist()
    last4_l, agent_l, ip_l = last4.tolist(), agent.tolist(), ip.tolist()
    extra_l, words_l = extra.tolist(), words.tolist()
    events: List[Dict[str, Any]] = []
    prefixes: List[str] = []
    pos = 0
    weekday = EPOCH.weekday()
    for i in range(n):
        ui, mi = u_l[i], m_l[i]
        device = f"dev_{ui:08x}_{dev_l[i]}"
        a, b, c, d = ip_l[i]
        events.append({
            "transaction_id": "",
            "user_id": pop.user_ids[ui],
            "merchant_id": pop.merchant_ids[mi],
            "amount": amount_l[i],
            "currency": "USD",
            "transaction_type": _TXN_TYPES[ttype_l[i]],
            "payment_method": _PAYMENT_METHODS[pmeth_l[i]],
            "card_type": _CARD_TYPES[ctype_l[i]],
            "card_last_four": str(last4_l[i]),
            "timestamp": "",
            "ip_address": f"{a}.{b}.{c}.{d}",
            "device_id": device,
            "device_fingerprint": device,
            "user_agent": _USER_AGENTS[agent_l[i]],
            "geolocation": {"lat": lat_l[i], "lon": lon_l[i]},
            "merchant_location": {"lat": m_lat[mi], "lon": m_lon[mi]},
            "is_weekend": weekday >= 5,
            "hour_of_day": EPOCH.hour,
            "day_of_week": weekday + 1,
            "day_of_month": EPOCH.day,
            "description": "",
            "is_fraud": False,
            "fraud_type": None,
            "fraud_score": prior_l[i],
        })
        k = extra_l[i]
        prefixes.append(
            "".join([_WORDS[w] + " " for w in words_l[pos:pos + k]]))
        pos += k
    tokens = base + extra
    # drawn last, so that memo_share 1.0 leaves every other draw as it was
    memo = rng.random(n) < float(traffic.get("memo_share", 1.0))
    fixed: List[Any] = [None] * n
    if not memo.all():
        _, first = np.unique(m, return_index=True)
        first_of = dict(zip(m[first].tolist(), first.tolist()))
        for i in np.flatnonzero(~memo).tolist():
            j = first_of[m_l[i]]      # the merchant's first event in the pool
            fixed[i] = f"{prefixes[j]}ref{m_l[i]:x}"
            tokens[i] = tokens[j]
    return EventPool(events, prefixes, tokens, fixed)


@dataclasses.dataclass
class Stream:
    """What parent and producer process both derive from (cell, seed)."""

    population: Population
    pool: EventPool
    offsets: np.ndarray          # due times, seconds from the stream's start
    mode: str                    # the arrival kind's MODE


def make_stream(cell: Dict[str, Any], seed: int, seconds: float) -> Stream:
    """Population, event pool and arrival schedule of one run, drawn in a
    fixed order from ONE generator made from ``seed``: two processes that
    call this with the same arguments hold the same stream."""
    from benchmarks.harness import spec

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    arrival = spec.arrival(traffic["arrival"])
    rng = np.random.default_rng(seed)
    pop = Population(cfg["population"]["users"],
                     cfg["population"]["merchants"], rng)
    pool = build_pool(pop, traffic, rng)
    offsets = arrival.schedule(
        traffic, seconds + float(traffic["warmup_s"]), rng)
    return Stream(pop, pool, offsets, arrival.MODE)
