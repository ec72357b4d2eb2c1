"""Golden-vector tests for the 64-feature contract and rule scoring.

Expected values are hand-derived from the cited reference formulas
(FeatureExtractor.java, TransactionProcessor.java) — not from the
implementation under test.
"""

import math

import numpy as np
import pytest

from realtime_fraud_detection_tpu.features import (
    FEATURE_NAMES,
    NUM_FEATURES,
    DECISIONS,
    encode_transactions,
    extract_features,
    feature_index,
    make_decision,
    rule_score,
    risk_level_code,
)
from realtime_fraud_detection_tpu.features.serving import ServingFeatureProcessor

USER = {
    "user_id": "user_a",
    "risk_score": 0.2,
    "account_age_days": 400,
    "kyc_status": "verified",
    "avg_transaction_amount": 50.0,
    "transaction_frequency": 3,
    "device_fingerprints": ["dev1", "dev2"],
    "behavioral_patterns": {
        "preferred_time_start": 8,
        "preferred_time_end": 20,
        "weekend_activity": 0.6,
        "international_transactions": 0.05,
        "online_preference": 0.9,
    },
}
MERCHANT = {
    "merchant_id": "merchant_a",
    "name": "Acme Groceries",
    "category": "grocery",
    "risk_level": "low",
    "avg_transaction_amount": 30.0,
    "fraud_rate": 0.005,
    "is_blacklisted": False,
    "operating_hours": {"start_hour": "8", "end_hour": "22"},
}
TXN = {
    "transaction_id": "t1",
    "user_id": "user_a",
    "merchant_id": "merchant_a",
    "amount": 120.0,
    "currency": "USD",
    "transaction_type": "purchase",
    "payment_method": "credit_card",
    "card_type": "visa",
    "hour_of_day": 14,
    "day_of_week": 3,
    "day_of_month": 15,
    "is_weekend": False,
    "ip_address": "8.8.8.8",
    "device_fingerprint": "dev1",
    "user_agent": "Mozilla/5.0 (X11; Linux x86_64) AppleWebKit",
    "geolocation": {"lat": 40.7, "lon": -74.0},
    "merchant_location": {"lat": 40.8, "lon": -73.9},
    "fraud_score": 0.1,
}


def fv(batch_or_rows, name):
    return np.asarray(batch_or_rows)[:, feature_index(name)]


class TestFeatureContract:
    def test_sixty_four_features(self):
        assert NUM_FEATURES == 64
        assert len(set(FEATURE_NAMES)) == 64

    def test_known_transaction_golden_values(self):
        batch = encode_transactions([TXN], {"user_a": USER}, {"merchant_a": MERCHANT})
        feats = np.asarray(extract_features(batch))
        assert feats.shape == (1, 64)
        row = feats[0]
        get = lambda n: row[feature_index(n)]

        # amount category
        assert get("amount") == pytest.approx(120.0)
        assert get("amount_log") == pytest.approx(math.log(121.0), rel=1e-6)
        assert get("amount_sqrt") == pytest.approx(math.sqrt(120.0), rel=1e-6)
        assert get("is_round_amount") == 1.0  # 120.00 is integral
        assert get("is_round_10") == 1.0
        assert get("is_round_100") == 0.0
        assert get("amount_to_user_avg_ratio") == pytest.approx(120.0 / 50.0)
        assert get("amount_deviation_zscore") == pytest.approx((120 - 50) / 50)
        assert get("is_large_for_user") == 0.0  # ratio 2.4 < 3
        assert get("amount_to_merchant_avg_ratio") == pytest.approx(4.0)
        assert get("is_large_for_merchant") == 1.0  # 120 > 60
        assert get("amount_category") == 2.0  # medium [100, 1000)

        # temporal
        assert get("hour_of_day") == 14.0
        assert get("time_period") == 1.0  # afternoon
        assert get("is_business_hours") == 1.0
        assert get("is_night_time") == 0.0
        assert get("in_user_preferred_time") == 1.0  # 8 <= 14 <= 20

        # geographic: haversine of (40.7,-74.0)-(40.8,-73.9)
        lat1, lon1, lat2, lon2 = map(math.radians, (40.7, -74.0, 40.8, -73.9))
        a = (math.sin((lat2 - lat1) / 2) ** 2
             + math.cos(lat1) * math.cos(lat2) * math.sin((lon2 - lon1) / 2) ** 2)
        expected_km = 6371 * 2 * math.atan2(math.sqrt(a), math.sqrt(1 - a))
        assert get("distance_to_merchant_km") == pytest.approx(expected_km, rel=1e-4)
        assert get("is_high_risk_country") == 0.0
        assert get("user_intl_preference") == pytest.approx(0.05)
        assert get("unexpected_intl_transaction") == 1.0  # 0.05 < 0.1

        # user
        assert get("is_new_account") == 0.0
        assert get("user_risk_score") == pytest.approx(0.2)
        assert get("is_kyc_verified") == 1.0
        assert get("kyc_status") == 0.0  # verified

        # merchant
        assert get("merchant_risk_level") == 0.0  # low
        assert get("is_high_risk_category") == 0.0
        assert get("within_merchant_hours") == 1.0
        assert get("merchant_risk_multiplier") == pytest.approx(1.0)
        assert get("suspicious_merchant_name") == 0.0

        # device / network
        assert get("is_known_device") == 1.0
        assert get("is_new_device") == 0.0
        assert get("is_private_ip") == 0.0
        assert get("ip_risk_score") == pytest.approx(0.3)
        assert get("suspicious_user_agent") == 0.0

        # contextual
        assert get("is_high_risk_payment") == 0.0
        assert get("is_refund") == 0.0

    def test_unknown_profiles_defaults(self):
        batch = encode_transactions([TXN])  # no profile stores
        row = np.asarray(extract_features(batch))[0]
        get = lambda n: row[feature_index(n)]
        # FeatureExtractor.java:244-251 unknown-user defaults
        assert get("account_age_days") == 0.0
        assert get("is_new_account") == 1.0
        assert get("is_very_new_account") == 1.0
        assert get("user_risk_score") == pytest.approx(0.8)
        assert get("is_kyc_verified") == 0.0
        # :288-295 unknown-merchant defaults
        assert get("merchant_fraud_rate") == pytest.approx(0.1)
        assert get("is_blacklisted_merchant") == 0.0
        assert get("is_high_risk_category") == 0.0
        assert get("merchant_risk_multiplier") == pytest.approx(2.0)
        assert get("within_merchant_hours") == 1.0  # no info is not "outside"

    def test_suspicious_merchant_regex(self):
        merch = dict(MERCHANT, name="QuickBitcoin Exchange")
        batch = encode_transactions([TXN], {"user_a": USER}, {"merchant_a": merch})
        assert fv(extract_features(batch), "suspicious_merchant_name")[0] == 1.0

    def test_private_ip_and_bad_agent(self):
        txn = dict(TXN, ip_address="192.168.1.5", user_agent="curl-bot")
        batch = encode_transactions([txn], {"user_a": USER}, {"merchant_a": MERCHANT})
        row = np.asarray(extract_features(batch))[0]
        assert row[feature_index("is_private_ip")] == 1.0
        assert row[feature_index("ip_risk_score")] == pytest.approx(0.1)
        assert row[feature_index("suspicious_user_agent")] == 1.0

    def test_velocity_flags(self):
        vel = {"user_a": {"5min": {"count": 6, "amount": 300.0},
                          "1hour": {"count": 25, "amount": 1200.0},
                          "24hour": {"count": 40, "amount": 2000.0}}}
        batch = encode_transactions([TXN], {"user_a": USER}, {"merchant_a": MERCHANT}, vel)
        row = np.asarray(extract_features(batch))[0]
        assert row[feature_index("velocity_5min_count")] == 6.0
        assert row[feature_index("high_velocity_5min")] == 1.0  # > 5
        assert row[feature_index("high_velocity_1hour")] == 1.0  # > 20
        assert row[feature_index("velocity_24hour_amount")] == 2000.0

    def test_batch_shapes_and_vectorization(self):
        txns = [dict(TXN, amount=float(a)) for a in (5, 50, 500, 5000, 50000)]
        batch = encode_transactions(txns, {"user_a": USER}, {"merchant_a": MERCHANT})
        cats = fv(extract_features(batch), "amount_category")
        np.testing.assert_array_equal(cats, [0, 1, 2, 3, 4])


class TestRuleScore:
    def test_benign_transaction_score(self):
        batch = encode_transactions([TXN], {"user_a": USER}, {"merchant_a": MERCHANT})
        score = float(np.asarray(rule_score(batch))[0])
        # hand-derived: 0.5*0.1 (prior) + 0.2*0.2 (user risk) + 0 (old, verified)
        # + 0 merchant (low risk, rate .005, not blacklisted) + 0 flags
        assert score == pytest.approx(0.05 + 0.04, abs=1e-6)

    def test_risky_transaction_score(self):
        user = dict(USER, risk_score=0.9, account_age_days=5, kyc_status="pending")
        merch = dict(MERCHANT, risk_level="high", fraud_rate=0.15,
                     category="gambling", is_blacklisted=False)
        txn = dict(TXN, fraud_score=0.8, amount=300.0, device_fingerprint="unknown-dev",
                   hour_of_day=3)
        batch = encode_transactions([txn], {"user_a": user}, {"merchant_a": merch})
        score = float(np.asarray(rule_score(batch))[0])
        # 0.5*0.8 + (0.9*0.2 + 0.1 + 0.15) + (0.2 + 0.15*2 + 0.15 gambling)
        # + 0.1 new device + 0.05 unusual hour + 0.1 outside hours (3 < 8)
        expected = 0.4 + 0.43 + 0.65 + 0.25
        assert score == pytest.approx(min(1.0, expected), abs=1e-6)

    def test_unknown_profiles_minimal_defaults(self):
        txn = dict(TXN, fraud_score=0.0, hour_of_day=14)
        batch = encode_transactions([txn])
        score = float(np.asarray(rule_score(batch))[0])
        # minimal user 0.35 + minimal merchant 0.1 (TransactionProcessor.java:489-508)
        assert score == pytest.approx(0.45, abs=1e-6)

    def test_decision_ladder(self):
        scores = np.array([0.2, 0.55, 0.75, 0.95], np.float32)
        blk = np.zeros(4, bool)
        dec, risk = make_decision(scores, blk)
        assert [DECISIONS[d] for d in np.asarray(dec)] == [
            "APPROVE", "APPROVE", "REVIEW", "DECLINE"]
        assert list(np.asarray(risk)) == [1, 2, 3, 4]  # LOW MEDIUM HIGH CRITICAL

    def test_blacklist_override(self):
        dec, risk = make_decision(np.array([0.1], np.float32), np.array([True]))
        assert DECISIONS[int(np.asarray(dec)[0])] == "DECLINE"
        assert int(np.asarray(risk)[0]) == 4

    def test_ensemble_risk_ladder(self):
        probs = np.array([0.1, 0.4, 0.7, 0.85, 0.99], np.float32)
        codes = np.asarray(risk_level_code(probs))
        np.testing.assert_array_equal(codes, [0, 1, 2, 3, 4])


class TestServingProcessor:
    def test_required_feature_missing_raises(self):
        with pytest.raises(ValueError, match="amount"):
            ServingFeatureProcessor().process_features({})

    def test_bounds_and_defaults(self):
        p = ServingFeatureProcessor().process_features(
            {"amount": 100.0, "hour_of_day": 99, "merchant_fraud_rate": -5}
        )
        assert p["hour_of_day"] == 23  # clamped to max
        assert p["merchant_fraud_rate"] == 0.0  # clamped to min
        assert p["country_risk_score"] == 0.5  # default
        assert p["amount_log"] == pytest.approx(math.log1p(100.0))
        assert p["is_business_hours"] in (0.0, 1.0)

    def test_nan_replaced(self):
        p = ServingFeatureProcessor().process_features(
            {"amount": 10.0, "amount_zscore": float("nan")}
        )
        assert p["amount_zscore"] == 0.0

    def test_flink_features_dict_merged(self):
        p = ServingFeatureProcessor().process_features(
            {"amount": 10.0, "features": {"velocity_score": 0.9}}
        )
        assert p["velocity_score"] == pytest.approx(0.9)

    def test_model_matrix_clipped_64(self):
        proc = ServingFeatureProcessor()
        rows = proc.process_batch([{"amount": 1e9}, {"amount": 5.0}])
        mat = proc.to_model_matrix(rows)
        assert mat.shape[1] >= 64
        assert mat.max() <= 10.0 and mat.min() >= -10.0


class TestReviewRegressions:
    def test_no_device_fingerprint_no_penalty(self):
        # TransactionProcessor.java:252-262: rule fires only when the txn
        # carries a fingerprint that is unknown
        txn_nofp = dict(TXN)
        del txn_nofp["device_fingerprint"]
        txn_badfp = dict(TXN, device_fingerprint="stranger-device")
        batch = encode_transactions(
            [txn_nofp, txn_badfp, TXN], {"user_a": USER}, {"merchant_a": MERCHANT}
        )
        scores = np.asarray(rule_score(batch))
        assert scores[1] == pytest.approx(scores[0] + 0.1, abs=1e-6)  # penalty
        assert scores[2] == pytest.approx(scores[0], abs=1e-6)  # known device

    def test_negative_amount_features_finite(self):
        txn = dict(TXN, amount=-20.0, transaction_type="refund")
        batch = encode_transactions([txn], {"user_a": USER}, {"merchant_a": MERCHANT})
        feats = np.asarray(extract_features(batch))
        assert np.isfinite(feats).all()

    def test_fast_path_day_of_month_matches_clock(self):
        from realtime_fraud_detection_tpu.sim import TransactionGenerator

        gen = TransactionGenerator(num_users=10, num_merchants=5, seed=0)
        day0 = gen.clock.day
        batch, _ = gen.generate_encoded(4)
        assert int(np.asarray(batch.day_of_month)[0]) == day0


class TestEnrichment:
    """FeatureEnrichmentProcessor semantics (java :84-150, 122-344)."""

    @staticmethod
    def _features(**overrides):
        from realtime_fraud_detection_tpu.features.extract import (
            NUM_FEATURES,
            feature_index,
        )

        f = np.zeros((1, NUM_FEATURES), np.float32)
        # defaults that zero out the "absence" penalties
        f[0, feature_index("in_user_preferred_time")] = 1.0
        f[0, feature_index("is_kyc_verified")] = 1.0
        f[0, feature_index("within_merchant_hours")] = 1.0
        f[0, feature_index("amount_category")] = 2.0
        for name, v in overrides.items():
            f[0, feature_index(name)] = v
        return f

    def test_zero_risk_features_score_zero(self):
        from realtime_fraud_detection_tpu.features.rules import enrichment_score

        assert float(np.asarray(enrichment_score(self._features()))[0]) == 0.0

    def test_category_weights(self):
        from realtime_fraud_detection_tpu.features.rules import enrichment_score

        # blacklisted merchant alone: 0.8 * 0.2 category weight
        s = enrichment_score(self._features(is_blacklisted_merchant=1.0))
        assert float(np.asarray(s)[0]) == pytest.approx(0.8 * 0.2)
        # high velocity 5min alone: 0.6 * 0.15
        s = enrichment_score(self._features(high_velocity_5min=1.0))
        assert float(np.asarray(s)[0]) == pytest.approx(0.6 * 0.15)
        # very-new account + unverified: (0.4 + 0.3) * 0.25
        s = enrichment_score(self._features(is_very_new_account=1.0,
                                            is_kyc_verified=0.0))
        assert float(np.asarray(s)[0]) == pytest.approx(0.7 * 0.25)

    def test_blend_60_40_and_relevel(self):
        from realtime_fraud_detection_tpu.features.rules import (
            DECISIONS,
            RISK_LEVEL_NAMES,
            blend_enrichment,
        )

        f = self._features(is_blacklisted_merchant=1.0, high_velocity_5min=1.0,
                           is_very_new_account=1.0, is_kyc_verified=0.0,
                           user_risk_score=1.0, merchant_fraud_rate=0.3,
                           is_high_risk_category=1.0, ip_risk_score=1.0,
                           is_new_device=1.0, suspicious_user_agent=1.0,
                           is_night_time=1.0, is_large_for_user=1.0)
        prior = np.asarray([0.9], np.float32)
        blended, dec, risk = blend_enrichment(prior, f)
        b = float(np.asarray(blended)[0])
        assert 0.6 * 0.9 < b <= 1.0
        # enrichment ladder: >=0.6 -> REVIEW/MEDIUM+ (java :341-367)
        assert DECISIONS[int(np.asarray(dec)[0])] in ("REVIEW", "DECLINE")
        assert RISK_LEVEL_NAMES[int(np.asarray(risk)[0])] in (
            "MEDIUM", "HIGH", "CRITICAL")

    def test_job_wires_enrichment(self):
        from realtime_fraud_detection_tpu.scoring import (
            FraudScorer,
            ScorerConfig,
        )
        from realtime_fraud_detection_tpu.sim.simulator import (
            TransactionGenerator,
        )
        from realtime_fraud_detection_tpu.stream import (
            InMemoryBroker,
            JobConfig,
            StreamJob,
        )
        from realtime_fraud_detection_tpu.stream import topics as T

        gen = TransactionGenerator(num_users=20, num_merchants=10, seed=6)
        broker = InMemoryBroker()
        scorer = FraudScorer(scorer_config=ScorerConfig(text_len=32))
        scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        job = StreamJob(broker, scorer,
                        JobConfig(max_batch=32, enable_enrichment=True))
        records = gen.generate_batch(40)
        broker.produce_batch(T.TRANSACTIONS, records,
                             key_fn=lambda r: str(r["user_id"]))
        assert job.run_until_drained(now=1000.0) == 40
        enriched = broker.consumer([T.ENRICHED], "c").poll(1000)
        assert len(enriched) == 40
        for r in enriched:
            assert "ensemble_score" in r.value       # pre-blend score kept
            assert 0.0 <= r.value["fraud_score"] <= 1.0
            assert r.value["decision"] in ("APPROVE", "REVIEW", "DECLINE")


class TestIngestFuzz:
    """Property: NO input shape may crash the sanitize -> encode path.

    The stream ingests arbitrary JSON from the wire; a crash in assembly is
    a whole-batch degradation, so the sanitizer must turn any garbage into
    either a clean reject or an encodable record."""

    @staticmethod
    def _strategies():
        import pytest

        st = pytest.importorskip(
            "hypothesis.strategies",
            reason="hypothesis not installed in this image")

        scalar = st.one_of(
            st.none(), st.booleans(), st.integers(-10**12, 10**12),
            st.floats(allow_nan=True, allow_infinity=True), st.text(max_size=20),
            st.lists(st.integers(), max_size=3),
        )
        geo = st.one_of(
            scalar,
            st.fixed_dictionaries({}, optional={
                "lat": scalar, "lon": scalar}),
        )
        return st.fixed_dictionaries({}, optional={
            "transaction_id": scalar, "user_id": scalar,
            "merchant_id": scalar, "amount": scalar,
            "hour_of_day": scalar, "day_of_week": scalar,
            "day_of_month": scalar, "is_weekend": scalar,
            "geolocation": geo, "merchant_location": geo,
            "payment_method": scalar, "transaction_type": scalar,
            "card_type": scalar, "user_agent": scalar,
            "ip_address": scalar, "device_fingerprint": scalar,
            "description": scalar, "fraud_score": scalar,
            "timestamp": scalar, "unexpected_field": scalar,
        })

    def test_sanitize_then_encode_never_crashes(self):
        import pytest

        hypothesis = pytest.importorskip(
            "hypothesis",
            reason="hypothesis not installed in this image")
        given, settings = hypothesis.given, hypothesis.settings

        from realtime_fraud_detection_tpu.features.schema import (
            encode_transactions,
        )
        from realtime_fraud_detection_tpu.serving.validation import (
            sanitize_for_stream,
        )

        @given(self._strategies())
        @settings(max_examples=300, deadline=None)
        def check(rec):
            txn, errors = sanitize_for_stream(rec)
            if errors:
                return                      # clean reject is a valid outcome
            batch = encode_transactions([txn])
            assert batch.batch_size == 1
            assert float(batch.amount[0]) >= 0.0

        check()


def test_missing_cpu_backend_fails_with_a_message_naming_the_fix(monkeypatch):
    """extract_features_host runs on JAX's CPU backend; a process whose
    JAX_PLATFORMS lists only the accelerator must fail with a clear
    message (FraudScorer asks at construction), not as ERROR results."""
    import jax

    from realtime_fraud_detection_tpu.features import extract

    def no_cpu(backend=None):
        raise RuntimeError("Unknown backend cpu")

    extract.host_cpu_device.cache_clear()
    monkeypatch.setattr(jax, "local_devices", no_cpu)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    try:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS='tpu'.*tpu,cpu"):
            extract.host_cpu_device()
    finally:
        extract.host_cpu_device.cache_clear()
